"""Snapshot-decoupled serving invariants: every prediction reflects a whole
publish boundary (no torn reads), staleness is bounded by publish_every,
and the watermark-driven background flush actually flushes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import resize_bank
from repro.core.rff import sample_rff
from repro.features.base import featurize
from repro.obs import telemetry
from repro.obs.faults import Fault, FaultInjector, FaultPlan
from repro.serve import api as serve_api
from repro.serve import klms_snapshot_server, krls_snapshot_server, make_server
from repro.serve.snapshot import SnapshotServer

RFF = sample_rff(jax.random.PRNGKey(0), 4, 48, sigma=3.0)
_RNG = np.random.RandomState(7)
XS = _RNG.randn(400, 4).astype(np.float32)
YS = _RNG.randn(400).astype(np.float32)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _expected_pred(theta_row, x):
    z = featurize(RFF, jnp.asarray(x))
    return jnp.sum(theta_row.astype(jnp.float32) * z.astype(jnp.float32))


def _drive(server, schedule, publish_every):
    """Run a submit/flush/predict schedule; verify every prediction against
    an offline replay of the publish history.

    ``schedule`` items: ("submit", tenant, i) enqueues sample i,
    ("flush",) flushes, ("predict", tenant, i) queries with sample i.
    The replay records theta at every publish boundary; a torn read — a
    prediction built from thetas of two different flushes — would match
    no recorded boundary.
    """
    boundary_thetas = [np.asarray(server.queue.state.theta)]
    for step in schedule:
        if step[0] == "submit":
            _, tenant, i = step
            server.submit(tenant, XS[i], YS[i])
        elif step[0] == "flush":
            ver_before = server.snapshot.version
            server.flush()
            if server.snapshot.version != ver_before:
                boundary_thetas.append(np.asarray(server.snapshot.state.theta))
        else:
            _, tenant, i = step
            got = float(server.predict(tenant, XS[i]))
            snap = server.snapshot
            # The served replica IS the latest recorded publish boundary.
            assert snap.version == len(boundary_thetas) - 1
            want = float(_expected_pred(
                jnp.asarray(boundary_thetas[snap.version][tenant]), XS[i]
            ))
            np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
            # Staleness never reaches publish_every outside a flush.
            assert 0 <= server.staleness < publish_every
    return boundary_thetas


def test_interleaved_reads_consistent_with_publish_boundaries():
    """Deterministic interleaving: reads between flushes keep returning the
    frozen replica even as the live state trains past it."""
    publish_every = 6
    srv = klms_snapshot_server(
        RFF, 3, mu=0.5, chunk=4, publish_every=publish_every, mode="xla"
    )
    schedule = []
    i = 0
    for round_ in range(12):
        for _ in range(1 + round_ % 3):
            schedule.append(("submit", round_ % 3, i))
            i += 1
        schedule.append(("predict", round_ % 3, i % 50))
        schedule.append(("flush",))
        schedule.append(("predict", (round_ + 1) % 3, i % 50))
    boundaries = _drive(srv, schedule, publish_every)
    assert len(boundaries) >= 3  # publishes actually happened
    srv.drain()
    assert srv.staleness < publish_every


def test_reads_are_stale_until_publish():
    """publish_every > backlog: flushes advance the live state while the
    replica (and therefore reads) stay at version 0 until enough ticks
    accumulate — the read path provably does NOT track the live state."""
    srv = klms_snapshot_server(
        RFF, 1, mu=0.5, chunk=4, publish_every=100, mode="xla"
    )
    p0 = float(srv.predict(0, XS[0]))
    for i in range(8):
        srv.submit(0, XS[i], YS[i])
    srv.flush()
    srv.flush()
    assert srv.queue.ticks_served == 8
    assert srv.snapshot.version == 0 and srv.staleness == 8
    assert float(srv.predict(0, XS[0])) == p0  # frozen replica, frozen read
    srv.publish()  # manual publish releases the new state to readers
    assert srv.staleness == 0
    assert float(srv.predict(0, XS[0])) != p0


def test_size_watermark_background_flush():
    srv = klms_snapshot_server(
        RFF, 2, mu=0.5, chunk=8, publish_every=1, mode="xla", size_watermark=3
    )
    srv.submit(0, XS[0], YS[0])
    srv.submit(1, XS[1], YS[1])
    srv.submit(0, XS[2], YS[2])
    assert srv.queue.flushes == 0
    srv.submit(0, XS[3], YS[3])  # tenant 0 hits depth 3 -> flush
    assert srv.queue.flushes == 1
    assert srv.queue.backlog() == [0, 0]
    assert srv.snapshot.version == 1  # publish_every=1 published it


def test_age_watermark_background_flush():
    clock = FakeClock()
    srv = klms_snapshot_server(
        RFF,
        2,
        mu=0.5,
        chunk=8,
        publish_every=1,
        mode="xla",
        age_watermark=5.0,
        clock=clock,
    )
    srv.submit(0, XS[0], YS[0])
    clock.t = 4.0
    srv.submit(1, XS[1], YS[1])
    assert srv.queue.flushes == 0  # oldest is 4s — under the watermark
    clock.t = 5.5
    srv.maybe_flush()  # the event-loop poll hook
    assert srv.queue.flushes == 1 and srv.queue.backlog() == [0, 0]
    # Age resets with the queue drained: no spurious follow-up flush.
    srv.maybe_flush()
    assert srv.queue.flushes == 1


def test_direct_queue_flush_still_counts_toward_publish():
    """Publish due-ness derives from replica staleness, so ticks applied by
    calling queue.flush() directly (the queue's own API) still trigger a
    publish at the server's next flush."""
    srv = klms_snapshot_server(
        RFF, 1, mu=0.5, chunk=4, publish_every=3, mode="xla"
    )
    for i in range(4):
        srv.queue.submit(0, XS[i], YS[i])
    srv.queue.flush()  # bypasses the server: 4 ticks, no publish
    assert srv.snapshot.version == 0 and srv.staleness == 4
    srv.submit(0, XS[4], YS[4])
    srv.flush()  # staleness 5 >= publish_every 3 -> publish catches up
    assert srv.snapshot.version == 1 and srv.staleness == 0


def test_age_watermark_survives_interleaved_direct_submits():
    """A timed observation keeps its deadline even when untimed direct
    queue submissions sit ahead of it in the backlog."""
    clock = FakeClock()
    srv = klms_snapshot_server(
        RFF,
        1,
        mu=0.5,
        chunk=1,  # one observation per flush: the direct one goes first
        publish_every=1,
        mode="xla",
        age_watermark=5.0,
        clock=clock,
    )
    srv.queue.submit(0, XS[0], YS[0])  # untimed, position 0
    srv.submit(0, XS[1], YS[1])  # timed at t=0, position 1
    srv.flush()  # serves only the untimed head (chunk=1)
    assert srv.queue.backlog() == [1]
    clock.t = 6.0
    srv.maybe_flush()  # the timed observation's deadline must still fire
    assert srv.queue.backlog() == [0]


def test_krls_snapshot_server_predicts_from_replica():
    srv = krls_snapshot_server(
        RFF, 2, lam=1e-2, chunk=8, publish_every=4, mode="xla"
    )
    for i in range(6):
        srv.submit(0, XS[i], YS[i])
    srv.drain()
    assert srv.snapshot.version >= 1
    got = float(srv.predict(0, XS[10]))
    want = float(_expected_pred(srv.snapshot.state.theta[0], XS[10]))
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
    # Block reads serve the whole bank from the same replica.
    blk = srv.predict_block(np.broadcast_to(XS[10][None, None], (2, 1, 4)))
    np.testing.assert_allclose(float(blk[0, 0]), got, atol=1e-6)


def test_predict_block_precision_knob():
    srv = klms_snapshot_server(
        RFF, 2, mu=0.5, chunk=8, publish_every=1, mode="xla", precision="bf16"
    )
    for i in range(8):
        srv.submit(0, XS[i], YS[i])
        srv.submit(1, XS[i], YS[i])
    srv.drain()
    f32 = _expected_pred(srv.snapshot.state.theta[0], XS[20])
    got = float(srv.predict(0, XS[20]))
    assert abs(got - float(f32)) < 2e-2  # the documented bf16 read bound
    # Training state is untouched by the read precision knob.
    assert srv.queue.state.theta.dtype == jnp.float32


def test_reset_requires_drained_queue():
    srv = klms_snapshot_server(RFF, 1, chunk=4, mode="xla")
    srv.submit(0, XS[0], YS[0])
    with pytest.raises(RuntimeError):
        srv.reset(srv.queue.state)
    srv.drain()
    srv.reset(srv.queue.state)
    assert srv.snapshot.version == 0 and srv.staleness == 0


# ---------------------------------------------------------------------------
# The watermark test reads the queue's running counts: same decisions as a
# scan of every slot's backlog, at O(1) per arrival.
# ---------------------------------------------------------------------------


def _scan_watermark(srv):
    """The watermark decision recomputed from a scan of every backlog."""
    backlog = srv.queue.backlog()
    if not any(backlog):
        return None
    if srv.size_watermark is not None and max(backlog) >= srv.size_watermark:
        return "size"
    if srv.age_watermark is not None:
        oldest = min(
            (t[0][1] for t in srv._arrival_times if t), default=None
        )
        if oldest is not None and srv._clock() - oldest >= srv.age_watermark:
            return "age"
    return None


class _ScanWatermarkServer(SnapshotServer):
    """Reference server: decides every watermark by the scan."""

    def _watermark_due(self):
        return _scan_watermark(self)


def _assert_counts(queue):
    backlog = queue.backlog()
    assert queue.pending_total == sum(backlog)
    assert queue.max_backlog() == max(backlog, default=0)


def assert_trees_bitwise(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


_HP = {"klms": dict(mu=0.5), "krls": dict(lam=1e-2, beta=0.999)}
_OPS = ("submit", "queue.submit", "flush", "queue.flush", "maybe_flush",
        "drop_pending", "evict", "readmit", "move_slot", "adopt", "reset")
_OP_P = np.array([12, 3, 1, 1, 2, 1, 1, 1, 1, 0.5, 0.3])


def _apply_op(srv, op, rng):
    """One random operation on a facade ``srv`` (no policy: tenant = slot)."""
    inner, slots = srv.snapshot_server, srv.slots
    hot = rng.choice(slots, p=np.arange(slots, 0, -1) / (slots * (slots + 1) / 2))
    i = rng.integers(len(XS))
    if op == "submit":
        srv.submit(int(hot), XS[i], YS[i])
    elif op == "queue.submit":
        srv.queue.submit(int(hot), XS[i], YS[i])
    elif op == "flush":
        srv.flush()
    elif op == "queue.flush":
        srv.queue.flush()
    elif op == "maybe_flush":
        srv.maybe_flush()
    elif op == "drop_pending":
        srv.queue.drop_pending(int(hot))
    elif op == "evict":
        if int(hot) not in srv.evicted:
            srv.evict(int(hot))
    elif op == "readmit":
        if srv.evicted:
            srv.readmit(min(srv.evicted))
    elif op == "move_slot":
        src, dst = rng.choice(slots, size=2, replace=False)
        inner.move_slot(int(src), int(dst))
    elif op == "adopt":
        new = 8 if slots == 4 else 4
        for s in range(new, slots):
            srv.queue.drop_pending(s)
        inner.adopt_resized(
            resize_bank(srv.queue.state, new, fresh_row=srv._fresh_row)
        )
    else:
        srv.drain()
        srv.reset()


@pytest.mark.parametrize("age_watermark", [None, 0.1])
@pytest.mark.parametrize("size_watermark", [3, 6])  # below / above chunk 4
@pytest.mark.parametrize("learner", ["klms", "krls"])
@pytest.mark.parametrize("seed", [0, 1])
def test_running_counts_match_backlog_scan(
    seed, learner, size_watermark, age_watermark
):
    """Random mixes of every operation that changes a backlog: after each
    step the running counts equal a scan of ``backlog()``, the watermark
    decision equals the scan's, and a reference server that decides by the
    scan flushes at the same steps and ends bitwise equal."""
    rng = np.random.default_rng(seed)
    clock = FakeClock()
    kw = dict(
        feature_map=RFF, bank=4, chunk=4, mode="xla", publish_every=2,
        size_watermark=size_watermark, age_watermark=age_watermark,
        clock=clock, **_HP[learner],
    )
    srv = make_server(learner, **kw)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve_api, "SnapshotServer", _ScanWatermarkServer)
        ref = make_server(learner, **kw)
    assert type(ref.snapshot_server) is _ScanWatermarkServer
    # Both take the same drop_flush faults at the same flush indices; the
    # counts are checked between a fault and the flush it precedes too.
    plan = FaultPlan([
        Fault("drop_flush", tenant=int(rng.integers(4)), at_flush=int(f))
        for f in rng.choice(40, size=6, replace=False)
    ])
    inner_flush = srv.snapshot_server.flush

    def checked_flush():
        _assert_counts(srv.queue)
        return inner_flush()

    srv.snapshot_server.flush = checked_flush
    injectors = [FaultInjector(s, plan).attach() for s in (srv, ref)]
    for _ in range(200):
        clock.t += 0.04 * (rng.random() < 0.15)
        op = _OPS[rng.choice(len(_OPS), p=_OP_P / _OP_P.sum())]
        step = rng.bit_generator.state
        for s in (srv, ref):
            rng.bit_generator.state = step
            _apply_op(s, op, rng)
        _assert_counts(srv.queue)
        assert srv.snapshot_server._watermark_due() == _scan_watermark(
            srv.snapshot_server
        ), op
        assert srv.queue.backlog() == ref.queue.backlog(), op
        assert srv.queue.flushes == ref.queue.flushes, op
        assert srv.snapshot.version == ref.snapshot.version, op
    assert injectors[0].applied == injectors[1].applied
    for s in (srv, ref):
        s.drain()
    assert_trees_bitwise(srv.queue.state, ref.queue.state)
    assert_trees_bitwise(srv.snapshot.state, ref.snapshot.state)


def test_submit_path_walks_no_slot(monkeypatch):
    """At 4096 slots the per-arrival watermark test never builds the
    backlog list; every flush it fires counts once under its reason."""
    srv = make_server(
        "klms", feature_map=RFF, bank=4096, chunk=4, mode="xla", mu=0.5,
        size_watermark=4,
    )

    def walked():
        raise AssertionError("backlog() walked every slot on the submit path")

    monkeypatch.setattr(srv.queue, "backlog", walked)
    counted = telemetry.registry().count(
        "snapshot.watermark_flushes", reason="size"
    )
    for n in range(300):
        srv.submit(n % 8, XS[n % len(XS)], YS[n % len(YS)])
    # Round robin over 8 tenants: some tenant reaches depth 4 at arrival
    # 24, and after each flush the next one does 25 arrivals later.
    fired = srv.queue.flushes
    assert fired == len(range(24, 300, 25))
    assert telemetry.registry().count(
        "snapshot.watermark_flushes", reason="size"
    ) - counted == fired


# ---------------------------------------------------------------------------
# Property test: ANY interleaving of submit/flush/predict serves every read
# from some whole publish boundary with bounded staleness.
# ---------------------------------------------------------------------------

try:  # optional dep: only the hypothesis test skips without it — the
    # deterministic interleaving tests above must always run.
    from hypothesis import given, settings, strategies as st

    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _ops = st.lists(
        st.one_of(
            st.tuples(
                st.just("submit"), st.integers(0, 2), st.integers(0, 99)
            ),
            st.tuples(st.just("flush")),
            st.tuples(
                st.just("predict"), st.integers(0, 2), st.integers(0, 99)
            ),
        ),
        min_size=5,
        max_size=40,
    )

    @given(schedule=_ops, publish_every=st.integers(1, 9))
    @settings(max_examples=15, deadline=None)
    def test_any_interleaving_no_torn_reads(schedule, publish_every):
        srv = klms_snapshot_server(
            RFF, 3, mu=0.5, chunk=4, publish_every=publish_every, mode="xla"
        )
        _drive(srv, schedule, publish_every)
        srv.drain()
        assert srv.staleness < publish_every
