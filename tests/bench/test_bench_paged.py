"""The paged KRLS cell runs whole on the CPU and is judged sound.

``krls_paged.ingest`` shrunk to 8 slots, 80 tenants and D=32, its LRU
policy and a log that holds every tenant's history, on its own closed loop
of writes whose hot set moves. A sound run is correct, loses nothing,
evicts and readmits inside the window and compiles nothing there (a
compile would raise ``CompileInWindow``); a rebuild that installs a fresh
row instead of replaying the log makes it not correct.
"""
from bench import cells, harness
from test_bench_faults import SEED
from test_bench_policy import fresh_rebuild


def _paged_cell():
    cell = cells.resolve("krls_paged.ingest")
    hp = {**cell.cfg["hp"], "log_capacity": 256}
    cell.cfg = {**cell.cfg, "slots": 8, "tenants": 80, "num_features": 32,
                "hp": hp}
    cell.mix = {**cell.mix, "warmup": 200,
                "keys": {**cell.mix["keys"], "shift": {"every": 40, "by": 3}}}
    return cell


def _run(inject=None):
    return harness.run_cell(_paged_cell(), SEED, 0.5, require_tpu=False,
                            inject=inject)


def test_sound_paged_run_is_correct():
    result, details = _run()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and details["unpublished"] == 0
    assert details["window_requests"] > 0
    assert details["evictions_in_window"] >= 1
    assert details["readmissions_in_window"] >= 1
    assert not any(details["compiles_in_window"].values())


def test_paged_run_with_fresh_rebuilds_is_not_correct():
    result, _ = _run(inject=fresh_rebuild)
    assert not result["correct"], result["checks"]
