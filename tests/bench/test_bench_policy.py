"""The harness runs a fleet with more tenants than slots.

With a slot policy a flush's results are keyed by slot, an evicted
tenant's queued writes are dropped and later trained by the rebuild from
its log, a read of a tenant that is not resident is served cold, and a
checked tenant may have to be readmitted before its row can be read. Each
case drives a whole run of ``klms_fleet.ingest`` shrunk to 8 slots and 32
tenants, an LRU policy and a log that holds every tenant's history, on an
open loop of half reads whose hot set moves. A sound run is correct and
loses nothing; each planted fault makes it not correct.

The program compiles its replay for each new log length, so a window that
readmits a tenant at a length the warm-up did not compiles: ``run_cell``
then raises ``CompileInWindow`` (the benchmark's runs exit 4) with the
run's result and details on it, which these cases read.
"""
import math

import numpy as np
import pytest

from bench import cells, harness, traffic
from test_bench_faults import (
    CELLS, SEED, _tiny, altered_prior, altered_read, half_the_bank,
    unchanged_state,
)

POLICY_MIX = {
    "arrivals": "poisson", "rate": 300.0, "read_share": 0.5,
    "age_watermark": 0.1, "warmup": 40,
    "keys": {"dist": "zipf", "theta": 0.99, "shift": {"every": 40, "by": 3}},
}


def _policy_cell():
    cell = cells.resolve("klms_fleet.ingest")
    hp = {**cell.cfg["hp"], "policy": "lru", "log_capacity": 4096}
    cell.cfg = {**cell.cfg, "slots": 8, "tenants": 32, "num_features": 64,
                "hp": hp}
    cell.mix = {**cell.mix, **POLICY_MIX}
    return cell


def _run(inject=None):
    try:
        return harness.run_cell(_policy_cell(), SEED, 0.3, require_tpu=False,
                                inject=inject)
    except harness.CompileInWindow as e:
        return e.result, e.details


def fresh_rebuild(server):
    """An admission installs a fresh row instead of replaying the log."""
    inner = server.snapshot_server
    evict = inner._evict_fn
    inner._rebuild_fn = lambda state, slot, xs, ys: evict(state, slot)


def test_sound_policy_run_is_correct():
    seen = []
    result, details = _run(inject=seen.append)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and details["unpublished"] == 0
    assert details["window_requests"] > 0
    metrics = seen[0].metrics
    assert metrics.count("evictions") >= 1 and metrics.count("readmissions") >= 1
    assert details["evictions_in_window"] >= 1
    assert details["readmissions_in_window"] >= 1
    assert details["checked_not_resident"] >= 1
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize(
    "fault",
    [unchanged_state, half_the_bank, altered_prior, altered_read, fresh_rebuild],
    ids=lambda f: f.__name__,
)
def test_broken_policy_run_is_not_correct(fault):
    result, _ = _run(inject=fault)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("family", sorted(CELLS))
def test_policy_less_accounting_is_unchanged(family):
    """Without a policy a flush's keys are tenants: its outputs settle onto
    that tenant's writes in order, and a read has seen the writes of its
    tenant that flushes before it returned. Driven closed loop over a fixed
    stretch, so the flushes are the same on every run."""
    cell = _tiny(*CELLS[family])
    cfg, mix = cell.cfg, cell.mix
    sched = traffic.build(mix, cfg, SEED, 0.3)
    w, b = harness.make_feature_map(cfg, SEED)
    server = harness.build_server(cfg, mix, w, b)
    assert server.policy is None
    led = harness.Ledger(sched, cfg["tenants"])
    harness.capture_flushes(server, led)
    raw = []  # (request being issued, {tenant: priors})
    flush = server.queue.flush

    def recorded():
        res = flush()
        if res:
            at = int(np.count_nonzero(~np.isnan(led.issued))) - 1
            raw.append((at, {k: [float(p) for p, _ in o] for k, o in res.items()}))
        return res

    server.queue.flush = recorded
    n = harness.drive(server, led, sched, 0, len(sched), math.inf, None)
    raw_before_drain = len(raw)
    server.drain()
    led.settle(harness.clock())
    assert n == len(sched) and raw_before_drain < len(raw)

    is_read, key, _, _ = sched.arrays(n)
    writes = {k: list(np.flatnonzero(~is_read & (key == k))) for k in set(key)}
    prior = np.full(n, np.nan)
    seen = np.zeros((n + 1, cfg["tenants"]), np.int64)  # writes published
    for at, res in raw:
        for k, preds in res.items():
            for p in preds:
                prior[writes[k].pop(0)] = p
            seen[at + 1:, k] += len(preds)
    assert not any(writes.values())
    value, done = np.asarray(led.value), np.asarray(led.done)
    np.testing.assert_array_equal(value[~is_read], prior[~is_read])
    assert np.isfinite(done).all()
    assert led.published == np.bincount(key[~is_read],
                                        minlength=cfg["tenants"]).tolist()
    assert len(led.flushes) == len(raw)
    assert [f[3] for f in led.flushes] == [sum(map(len, r.values())) for _, r in raw]
    r = np.flatnonzero(is_read)
    np.testing.assert_array_equal(np.asarray(led.pub)[r], seen[r, key[r]])
    assert not led.rebuilds and not led.rebuilt and led.owner is None
