"""Chaos suite: injected faults -> detection -> quarantine -> repair.

The property, for every fault kind x all five learners: a fault injected
at a flush boundary raises a DegradationEvent at that same fold, the
offending tenant is quarantined and repaired by the ladder
(resymmetrize -> rebuild -> reset), no event ever re-fires after the
release, and the recovered server matches a never-faulted control that
had the *equivalent operator op* applied at the same boundary —
**bitwise** on every state leaf for reset and rebuild-from-complete-log
(the repair replays the same history through the same engine the
operator path uses), within a pinned f32 bound for re-symmetrize (the
symmetric projection of a perturbed P is not the unperturbed P; the
bound pins how far the perturbation can propagate into predictions).

Durability rides the same standard: kill-at-arbitrary-flush ->
restore(checkpoint + WAL suffix) matches the never-killed control
bitwise on all state leaves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rff import sample_rff
from repro.obs.faults import Fault, FaultInjector, FaultPlan
from repro.serve.api import make_server
from repro.serve.recovery import restore_checkpoint

_RFF = sample_rff(jax.random.PRNGKey(0), 3, 32, 1.0)

FAMILIES = ["klms", "nklms", "krls", "qklms", "ald"]

_KW = {
    "klms": dict(mu=0.3),
    "nklms": dict(mu=0.3),
    "krls": dict(lam=0.1, beta=0.99),
    "qklms": dict(sigma=1.0, mu=0.3, quant_eps=0.1, capacity=32),
    "ald": dict(sigma=1.0, nu=5e-4, capacity=32),
}

# Max relative prediction error after a resymmetrize repair vs the
# never-faulted control: the injected off-symmetric delta (5% of max|P|)
# is halved by the symmetric projection and only touches predictions
# through subsequent P-weighted updates.
_RESYM_TOL = 5e-2

_TENANT = 1  # the faulted tenant in every scenario (resident from warmup)


def _make(learner, **kw):
    return make_server(
        learner, feature_map=_RFF, bank=4, chunk=4,
        policy="lru", log_capacity=512, **_KW[learner], **kw,
    )


def _traffic(seed, n, tenants=3):
    rng = np.random.default_rng(seed)
    return [
        (
            int(rng.integers(0, tenants)),
            rng.standard_normal(3).astype(np.float32),
            float(rng.standard_normal()),
        )
        for _ in range(n)
    ]


def _assert_leaves_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(
            np.asarray(la), np.asarray(lb), equal_nan=True
        ), (la, lb)


def _expected_outcome(kind, learner):
    """(probe that must fire, history the ladder must record)."""
    if kind == "drop_flush":
        return "ticks_lag", [("rebuild", True)]
    if kind == "log_corrupt":
        return "finite", [("rebuild", None), ("reset", True)]
    if kind == "asym_pmat" and learner == "krls":
        return "pmat.asym_rel", [("resymmetrize", True)]
    # nan_state everywhere; asym_pmat degrades to an Inf poison on the
    # non-RLS families. A complete log means the ladder stops at rebuild.
    return "finite", [("rebuild", True)]


@pytest.mark.parametrize("learner", FAMILIES)
@pytest.mark.parametrize(
    "kind", ["nan_state", "asym_pmat", "log_corrupt", "drop_flush"]
)
def test_fault_matrix_detect_quarantine_repair(
    kind, learner, assert_watermark_state
):
    srv = _make(learner, recovery=True)
    ctrl = _make(learner, probe=True)
    traffic = _traffic(3, 60)
    warm, mid, tail = traffic[:30], traffic[30:42], traffic[42:]
    if kind != "drop_flush":
        # The fused kernels overwrite / wash out a poisoned row they
        # train, so the corruption must land on a masked slot to survive
        # to the tap; drop_flush instead needs a backlog to drop.
        mid = [a for a in mid if a[0] != _TENANT]
    for s in (srv, ctrl):
        for t, x, y in warm:
            s.submit(t, x, y)
        s.drain()
    assert srv.probe.total_events == 0

    if kind == "drop_flush":
        # Between the fault and the flush it precedes, the dropped backlog
        # has left the queue's running counts and the watermark decision.
        inner = srv.snapshot_server
        inner_flush = inner.flush

        def checked_flush():
            assert_watermark_state(srv)
            return inner_flush()

        inner.flush = checked_flush
    inj = FaultInjector(
        srv, FaultPlan([Fault(kind, tenant=_TENANT, at_flush=0)])
    ).attach()
    for t, x, y in mid:
        srv.submit(t, x, y)
        ctrl.submit(t, x, y)
    srv.flush()
    ctrl.flush()
    srv.drain()
    ctrl.drain()
    inj.detach()
    assert inj.applied and inj.applied[0]["flush"] == 0
    if kind == "drop_flush":
        assert inj.applied[0]["dropped"] > 0
        assert_watermark_state(srv)

    # Detection, quarantine and the full repair all happened inside the
    # faulted flush's fold.
    probe_name, ladder = _expected_outcome(kind, learner)
    at_detect = srv.probe.total_events
    assert at_detect >= 1
    assert probe_name in {ev.probe for ev in srv.probe.events}
    assert [
        (h["action"], h.get("verified")) for h in srv.recovery.history
    ] == ladder
    assert srv.recovery.quarantined == frozenset()
    counters = srv.metrics.snapshot()["counters"]
    assert counters["recovery.quarantines"] == 1
    assert counters["recovery.releases"] == 1
    assert counters[f"recovery.repairs{{action={ladder[-1][0]}}}"] == 1

    # The control takes the equivalent operator op at the same boundary.
    final_action = ladder[-1][0]
    if final_action == "reset":
        ctrl.reset_tenant(_TENANT)
    elif final_action == "rebuild":
        ctrl.evict(_TENANT)
        ctrl.readmit(_TENANT)

    for t, x, y in tail:
        srv.submit(t, x, y)
        ctrl.submit(t, x, y)
    srv.drain()
    ctrl.drain()

    # No event ever re-fires after the release.
    assert srv.probe.total_events == at_detect
    assert srv.recovery.quarantined == frozenset()
    for leaf in jax.tree.leaves(srv.queue.state):
        assert np.isfinite(np.asarray(leaf)).all()
    assert all(lag <= 0 for lag in srv._slot_lags())

    if final_action == "resymmetrize":
        # Symmetric again, exactly (f32 rounding of the projection)...
        slot = srv.resident[_TENANT]
        p = np.asarray(srv.queue.state.pmat[slot])
        assert np.max(np.abs(p - p.T)) <= 1e-5 * np.max(np.abs(p))
        # ...and predictions within the pinned bound of the control.
        xq = np.asarray(_traffic(9, 8)[0][1])[None].repeat(8, axis=0)
        a = np.asarray(srv.predict(_TENANT, xq))
        b = np.asarray(ctrl.predict(_TENANT, xq))
        denom = max(float(np.max(np.abs(b))), 1e-6)
        assert float(np.max(np.abs(a - b))) / denom < _RESYM_TOL
    else:
        _assert_leaves_equal(srv.queue.state, ctrl.queue.state)
        assert srv._expected == ctrl._expected


def test_clock_skew_is_detected_and_reclocked():
    import time

    srv = _make(
        "klms",
        probe={"clock_skew": 0.25},
        recovery={"reference_clock": time.monotonic},
    )
    traffic = _traffic(4, 50)
    for t, x, y in traffic[:30]:
        srv.submit(t, x, y)
    srv.drain()
    assert srv.recovery.measure_skew() < 0.25

    inj = FaultInjector(
        srv,
        FaultPlan([
            Fault("clock_skew", tenant=0, at_flush=0, magnitude=2.0)
        ]),
    ).attach()
    for t, x, y in traffic[30:40]:
        srv.submit(t, x, y)
    srv.flush()
    srv.drain()
    inj.detach()

    # One event, one reclock repair, no quarantine (global fault), and
    # the snapshot clock is back on the reference baseline.
    assert srv.probe.total_events == 1
    assert srv.probe.events[0].probe == "clock_skew"
    assert srv.recovery.history == [
        {
            "event": "clock_skew",
            "action": "reclock",
            "skew": pytest.approx(2.0, abs=0.05),
        }
    ]
    assert srv.recovery.quarantined == frozenset()
    counters = srv.metrics.snapshot()["counters"]
    assert counters["recovery.repairs{action=reclock}"] == 1
    assert srv.recovery.measure_skew() < 0.25
    before = srv.probe.total_events
    for t, x, y in traffic[40:]:
        srv.submit(t, x, y)
    srv.drain()
    assert srv.probe.total_events == before


@pytest.mark.parametrize("learner", ["klms", "krls", "ald"])
@pytest.mark.parametrize("cut", [7, 23, 41])
def test_kill_at_arbitrary_flush_restore_matches_never_killed(
    tmp_path, learner, cut
):
    args = dict(
        feature_map=_RFF, bank=4, chunk=4, policy="lru",
        log_capacity=512, size_watermark=4, **_KW[learner],
    )
    wal_path = str(tmp_path / "wal.jsonl")
    traffic = _traffic(5, 48)

    # The original server checkpoints mid-stream (mid-chunk backlogs
    # included) and keeps going — its drained end state is the
    # never-killed truth. Every arrival is in the WAL.
    orig = make_server(learner, wal=wal_path, **args)
    for t, x, y in traffic[:cut]:
        orig.submit(t, x, y)
    orig.checkpoint(tmp_path / "ckpt")
    for t, x, y in traffic[cut:]:
        orig.submit(t, x, y)
    orig.drain()

    # "Kill" = the process is gone; all that survives is the checkpoint
    # directory and the WAL. A fresh identically-configured server
    # restores the generation and replays the WAL suffix.
    restored = make_server(learner, wal=wal_path, **args)
    info = restore_checkpoint(restored, tmp_path / "ckpt")
    assert info["replayed"] == len(traffic) - cut
    restored.drain()

    _assert_leaves_equal(orig.queue.state, restored.queue.state)
    _assert_leaves_equal(orig.snapshot.state, restored.snapshot.state)
    assert orig.policy.state_dict() == restored.policy.state_dict()
    assert orig._expected == restored._expected
    # And both serve identical predictions going forward.
    xq = np.stack([x for _, x, _ in traffic[:6]])
    for tenant in range(3):
        a = np.asarray(orig.predict(tenant, xq))
        b = np.asarray(restored.predict(tenant, xq))
        assert np.array_equal(a, b)
