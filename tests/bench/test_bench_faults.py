"""``correct`` separates sound runs from broken ones.

Each case drives a whole run of a cell, shrunk to a CPU-sized bank and a
short window, past the harness's look for a chip. With the timed path
broken underneath (the flush leaves the state as it was; half the bank's
arrivals are left out; a prediction or a read is altered where it is
produced) ``correct`` comes out false; the bf16 control fails at least one
of the configuration's limits. The cells' mixes are writes only, so each
case also sends reads (an open loop, half reads) to cover the read path
the harness checks. One chip has no exchange between chips to leave out.
"""
import numpy as np
import pytest

from bench import cells, harness

SEED = 2**35 + 1
MIXED = {"arrivals": "poisson", "read_share": 0.5, "age_watermark": 0.1,
         "keys": {"dist": "zipf", "theta": 0.99}}
CELLS = {
    "klms": ("klms_fleet.ingest", {**MIXED, "rate": 300.0}),
    "krls": ("krls_fleet.ingest", {**MIXED, "rate": 200.0}),
}


def _tiny(name, mix):
    cell = cells.resolve(name)
    cell.cfg = {**cell.cfg, "slots": 8, "tenants": 8, "num_features": 64}
    cell.mix = {**cell.mix, "warmup": 40, **mix}
    return cell


def _run(family, inject=None, control=False):
    name, mix = CELLS[family]
    return harness.run_cell(_tiny(name, mix), SEED, 0.3, require_tpu=False,
                            inject=inject, control=control)


def _wrap_step(server, fn):
    queue = server.queue
    step = queue._chunk_step
    queue._chunk_step = lambda s, xs, ys, m: fn(step, s, xs, ys, m)


def unchanged_state(server):
    _wrap_step(server, lambda step, s, xs, ys, m: (s, step(s, xs, ys, m)[1]))


def half_the_bank(server):
    keep = (np.arange(server.slots) % 2 == 0).astype(np.float32)[:, None]
    _wrap_step(server, lambda step, s, xs, ys, m: step(s, xs, ys, m * keep))


def altered_prior(server):
    def fn(step, s, xs, ys, m):
        state, out = step(s, xs, ys, m)
        return state, out._replace(prediction=out.prediction * 1.05)

    _wrap_step(server, fn)


def altered_read(server):
    inner = server.snapshot_server
    predict = inner.predict
    inner.predict = lambda tenant, x: predict(tenant, x) * 1.05


@pytest.mark.parametrize("family", sorted(CELLS))
def test_sound_run_is_correct_and_control_fails(family):
    result, details = _run(family, control=True)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and details["window_requests"] > 0
    assert list(result)[-1] == "checks"
    control = details["control"]
    assert any(c["value"] > c["limit"] for c in control.values()), control


@pytest.mark.parametrize(
    "fault", [unchanged_state, half_the_bank, altered_prior, altered_read],
    ids=lambda f: f.__name__,
)
@pytest.mark.parametrize("family", sorted(CELLS))
def test_broken_timed_path_is_not_correct(family, fault):
    result, _ = _run(family, inject=fault)
    assert not result["correct"], result["checks"]
