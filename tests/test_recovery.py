"""Self-healing + durability tier units (serve/recovery.py + friends).

Covers the pieces the chaos matrix (tests/test_chaos.py) composes: the
JSONL write-ahead log's bitwise round-trip and torn-tail tolerance,
checkpoint save/restore (atomic generations, corrupt-newest fallback,
config validation, GC), the slot policy's durable state, the queue's
stale-arrival watchdog, the per-slot diagnostics, the expected-ticks
ledger, and the recovery ladder's escalation / backoff / give-up
mechanics driven directly (no fault injector).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import krls_bank_init, resymmetrize_tenant
from repro.core.rff import sample_rff
from repro.obs.probes import ProbeMonitor, slot_stats
from repro.serve.api import make_server
from repro.serve.policy import SlotPolicy
from repro.serve.queue import MicroBatchQueue
from repro.serve.recovery import (
    DurableLog,
    RecoveryPolicy,
    restore_checkpoint,
    save_checkpoint,
)

_RFF = sample_rff(jax.random.PRNGKey(0), 3, 32, 1.0)


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


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(la), np.asarray(lb), equal_nan=True)
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# -- DurableLog --------------------------------------------------------------


def test_wal_roundtrips_f32_bitwise_including_nan(tmp_path):
    path = tmp_path / "wal.jsonl"
    wal = DurableLog(path)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((8, 3)).astype(np.float32)
    xs[3, 1] = np.nan
    ys = rng.standard_normal(8).astype(np.float32)
    ys[5] = np.inf
    for i in range(8):
        assert wal.append(i % 3, xs[i], ys[i]) == i
    wal.close()
    back = DurableLog(path)
    entries = back.entries()
    assert [e["s"] for e in entries] == list(range(8))
    for i, e in enumerate(entries):
        assert np.array_equal(
            np.asarray(e["x"], np.float32), xs[i], equal_nan=True
        )
        assert np.array_equal(
            np.float32(e["y"]), ys[i], equal_nan=True
        )
    back.close()


def test_wal_tolerates_torn_tail_and_resumes_seq(tmp_path):
    path = tmp_path / "wal.jsonl"
    wal = DurableLog(path)
    for i in range(4):
        wal.append(0, np.zeros(3, np.float32), float(i))
    wal.close()
    # A crash mid-append leaves a torn final line.
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"s": 4, "t": 0, "x": [0.0')
    resumed = DurableLog(path)
    assert resumed.seq == 3  # torn record ignored
    assert [e["s"] for e in resumed.entries()] == [0, 1, 2, 3]
    assert resumed.append(1, np.ones(3, np.float32), 9.0) == 4
    # The new record replaces the torn tail in the readable suffix.
    assert resumed.entries(after=3)[0]["t"] == 1
    resumed.close()


# -- checkpoint / restore ----------------------------------------------------


@pytest.mark.parametrize("learner", ["klms", "krls", "qklms"])
def test_checkpoint_restore_roundtrip_bitwise(
    tmp_path, learner, assert_watermark_state
):
    kw = {
        "klms": dict(mu=0.3),
        "krls": dict(lam=0.1, beta=0.99),
        "qklms": dict(sigma=1.0, mu=0.3, quant_eps=0.1, capacity=32),
    }[learner]
    args = dict(
        feature_map=_RFF, bank=4, chunk=4, policy="lru",
        log_capacity=64, **kw,
    )
    a = make_server(learner, **args)
    for t, x, y in _traffic(1, 30):
        a.submit(t, x, y)
    a.flush()  # leave a mid-stream backlog in the pending buffers
    path = a.checkpoint(tmp_path / "ckpt")
    assert os.path.basename(path) == "gen_00000000.ckpt"

    b = make_server(learner, **args)
    info = restore_checkpoint(b, tmp_path / "ckpt")
    assert info["generation"] == 0 and info["replayed"] == 0
    assert _leaves_equal(a.queue.state, b.queue.state)
    assert _leaves_equal(a.snapshot.state, b.snapshot.state)
    assert a.snapshot.version == b.snapshot.version
    assert a.queue.backlog() == b.queue.backlog()
    assert max(b.queue.backlog()) > 0
    assert_watermark_state(a)
    assert_watermark_state(b)
    assert a.queue.ticks_served == b.queue.ticks_served
    assert a.queue.flushes == b.queue.flushes
    assert a.policy.state_dict() == b.policy.state_dict()
    assert a._expected == b._expected
    for t in a.log.tenants():
        assert a.log.size(t) == b.log.size(t)
        assert a.log.dropped(t) == b.log.dropped(t)
        ax, ay = a.log.arrays(t)
        bx, by = b.log.arrays(t)
        assert np.array_equal(ax, bx) and np.array_equal(ay, by)
    # Both servers continue identically from here.
    for t, x, y in _traffic(2, 20):
        a.submit(t, x, y)
        b.submit(t, x, y)
    a.drain()
    b.drain()
    assert _leaves_equal(a.queue.state, b.queue.state)


def test_checkpoint_preserves_ring_overflow_flag(tmp_path):
    args = dict(
        feature_map=_RFF, bank=2, chunk=4, policy="lru",
        log_capacity=4, mu=0.3,
    )
    a = make_server("klms", **args)
    for t, x, y in _traffic(3, 12, tenants=1):
        a.submit(0, x, y)
    a.drain()
    assert not a.log.complete(0)  # ring overflowed
    a.checkpoint(tmp_path / "ckpt")
    b = make_server("klms", **args)
    restore_checkpoint(b, tmp_path / "ckpt")
    assert not b.log.complete(0)
    assert b.log.dropped(0) == a.log.dropped(0)


def test_restore_skips_corrupt_newest_generation(tmp_path):
    args = dict(feature_map=_RFF, bank=2, chunk=4, mu=0.3,
                policy="lru", log_capacity=16)
    a = make_server("klms", **args)
    for t, x, y in _traffic(4, 10):
        a.submit(t % 2, x, y)
    a.drain()
    ckdir = tmp_path / "ckpt"
    a.checkpoint(ckdir)
    good_state = jax.tree.map(np.asarray, a.queue.state)
    for t, x, y in _traffic(5, 6):
        a.submit(t % 2, x, y)
    a.drain()
    newest = a.checkpoint(ckdir)
    with open(newest, "wb") as fh:
        fh.write(b"\x80garbage")  # torn write / disk corruption
    b = make_server("klms", **args)
    info = restore_checkpoint(b, ckdir)
    assert info["generation"] == 0  # fell back past the torn gen 1
    assert _leaves_equal(b.queue.state, good_state)


def test_restore_raises_on_config_mismatch(tmp_path):
    a = make_server("klms", feature_map=_RFF, bank=2, chunk=4, mu=0.3,
                    policy="lru")
    a.checkpoint(tmp_path / "ckpt")
    b = make_server("klms", feature_map=_RFF, bank=2, chunk=4, mu=0.7,
                    policy="lru")
    with pytest.raises(ValueError, match="mismatch"):
        restore_checkpoint(b, tmp_path / "ckpt")


def test_checkpoint_gc_keeps_newest_generations(tmp_path):
    a = make_server("klms", feature_map=_RFF, bank=2, chunk=4, mu=0.3,
                    policy="lru")
    ckdir = tmp_path / "ckpt"
    for i in range(5):
        save_checkpoint(a, ckdir, keep=2)
    names = sorted(n for n in os.listdir(ckdir) if n.endswith(".ckpt"))
    assert names == ["gen_00000003.ckpt", "gen_00000004.ckpt"]
    with open(ckdir / "LATEST") as fh:
        assert fh.read().strip() == "gen_00000004.ckpt"


def test_wal_replay_is_idempotent_across_restores(tmp_path):
    args = dict(feature_map=_RFF, bank=4, chunk=4, mu=0.3,
                policy="lru", log_capacity=64, size_watermark=4)
    wal_path = str(tmp_path / "wal.jsonl")
    a = make_server("klms", wal=wal_path, **args)
    traffic = _traffic(6, 40)
    for t, x, y in traffic[:25]:
        a.submit(t, x, y)
    a.checkpoint(tmp_path / "ckpt")
    for t, x, y in traffic[25:]:
        a.submit(t, x, y)
    a.drain()
    wal_size = os.path.getsize(wal_path)
    b = make_server("klms", wal=wal_path, **args)
    info = restore_checkpoint(b, tmp_path / "ckpt")
    assert info["replayed"] == 15
    # Replay suspended WAL appends: the file did not grow.
    assert os.path.getsize(wal_path) == wal_size
    b.drain()
    c = make_server("klms", wal=wal_path, **args)
    restore_checkpoint(c, tmp_path / "ckpt")
    c.drain()
    assert _leaves_equal(b.queue.state, c.queue.state)
    assert _leaves_equal(a.queue.state, b.queue.state)


# -- SlotPolicy durability ---------------------------------------------------


def test_policy_state_roundtrip_preserves_decisions():
    pol = SlotPolicy(2, scorer="lfu")
    for t in (7, 7, 8, 9, 9, 9):
        pol.touch(t)
        pol.admit(t)
    clone = SlotPolicy(2, scorer="lfu")
    clone.load_state(pol.state_dict())
    assert clone.resident == pol.resident
    assert clone.victim() == pol.victim()
    # Same future admission decision on both.
    pol.touch(11)
    clone.touch(11)
    assert pol.admit(11) == clone.admit(11)


def test_policy_load_state_rejects_scorer_mismatch():
    pol = SlotPolicy(2, scorer="lru")
    other = SlotPolicy(2, scorer="lfu")
    with pytest.raises(ValueError, match="scorer"):
        other.load_state(pol.state_dict())


# -- queue watchdog ----------------------------------------------------------


def test_queue_watchdog_force_flushes_stale_arrivals():
    fake = [0.0]
    queue = MicroBatchQueue(
        jax.jit(lambda s, xs, ys, m: (s, _fake_out(ys))),
        klms_init_state(),
        3,
        chunk=4,
        stale_after=5.0,
        clock=lambda: fake[0],
    )
    assert queue.maybe_flush() == {}
    queue.submit(1, np.zeros(3, np.float32), 1.0)
    fake[0] = 4.9
    assert not queue.has_stale()
    assert queue.maybe_flush() == {}
    fake[0] = 5.0
    assert queue.has_stale()
    res = queue.maybe_flush()
    assert 1 in res and queue.stale_flushes == 1
    assert not queue.has_stale()  # ledger cleared with the backlog


def test_queue_watchdog_keeps_stamp_across_partial_flush():
    fake = [0.0]
    queue = MicroBatchQueue(
        jax.jit(lambda s, xs, ys, m: (s, _fake_out(ys))),
        klms_init_state(),
        3,
        chunk=2,
        stale_after=10.0,
        clock=lambda: fake[0],
    )
    for i in range(5):  # deeper than one chunk
        queue.submit(0, np.zeros(3, np.float32), float(i))
    fake[0] = 10.0
    queue.maybe_flush()  # consumes 2, leaves 3 — still stale
    assert queue.backlog()[0] == 3
    assert queue.has_stale()
    queue.drop_pending(0)
    assert not queue.has_stale()


def _fake_out(ys):
    from repro.core.klms import StepOut

    return StepOut(prediction=jnp.zeros_like(ys), error=jnp.zeros_like(ys))


def klms_init_state():
    from repro.core.bank import klms_bank_init

    return klms_bank_init(_RFF, 3)


# -- per-slot diagnostics and the ledger -------------------------------------


def test_slot_stats_matches_numpy_oracle():
    state = krls_bank_init(_RFF, 3, 0.1)
    theta = np.asarray(state.theta).copy()
    theta[1] = 3.0
    pmat = np.asarray(state.pmat).copy()
    pmat[2, 0, 1] += 0.5
    state = state._replace(
        theta=jnp.asarray(theta), pmat=jnp.asarray(pmat)
    )
    stats = {k: np.asarray(v) for k, v in slot_stats(state).items()}
    np.testing.assert_allclose(
        stats["theta.norm"],
        np.linalg.norm(theta, axis=-1),
        rtol=1e-6,
    )
    asym = np.max(np.abs(pmat - np.swapaxes(pmat, -1, -2)), axis=(-2, -1))
    scale = np.max(np.abs(pmat), axis=(-2, -1))
    np.testing.assert_allclose(
        stats["pmat.asym_rel"], asym / (scale + 1e-30), rtol=1e-5
    )
    assert stats["finite"].tolist() == [1.0, 1.0, 1.0]
    bad = state._replace(theta=jnp.asarray(theta).at[0, 0].set(np.nan))
    assert slot_stats(bad)["finite"].tolist() == [0.0, 1.0, 1.0]


def test_resymmetrize_tenant_symmetrizes_one_slot_only():
    state = krls_bank_init(_RFF, 3, 0.1)
    pmat = np.asarray(state.pmat).copy()
    pmat[1, 0, 1] += 0.5
    pmat[2, 0, 1] += 0.5
    state = state._replace(pmat=jnp.asarray(pmat))
    fixed = resymmetrize_tenant(state, 1)
    p1 = np.asarray(fixed.pmat[1])
    assert np.allclose(p1, p1.T)
    # Slot 2 untouched (still asymmetric), theta untouched.
    assert not np.allclose(
        np.asarray(fixed.pmat[2]), np.asarray(fixed.pmat[2]).T
    )
    assert np.array_equal(np.asarray(fixed.theta), np.asarray(state.theta))


def test_ticks_lag_ledger_tracks_lost_arrivals():
    srv = make_server("klms", feature_map=_RFF, bank=3, chunk=4, mu=0.3,
                      probe=True)
    for t, x, y in _traffic(7, 20):
        srv.submit(t, x, y)
    srv.drain()
    assert srv._slot_lags() == [0, 0, 0]
    # Silently lose a backlog (bypassing the facade's accounting).
    srv.submit(1, np.zeros(3, np.float32), 1.0)
    srv.queue.drop_pending(1)
    srv.submit(0, np.zeros(3, np.float32), 0.0)  # drive a real flush
    srv.flush()
    assert srv._slot_lags()[1] == 1
    assert srv.probe.total_events >= 1
    assert any(
        ev.probe == "ticks_lag" for ev in srv.probe.events
    )


def test_probe_monitor_subscribers_receive_every_event():
    mon = ProbeMonitor()
    seen = []
    mon.subscribe(seen.append)
    mon.update({"finite": 0.0})
    mon.update({"finite": 1.0})
    mon.update({"finite": 0.0, "theta.norm_max": 1e9})
    assert [(ev.probe) for ev in seen] == [
        "finite", "finite", "theta.norm_max",
    ]


# -- the recovery ladder, driven directly ------------------------------------


def _degraded_server(**kw):
    """A policy-mode server with tenant 1 trained then NaN-poisoned."""
    srv = make_server(
        "klms", feature_map=_RFF, bank=4, chunk=4, mu=0.3,
        policy="lru", log_capacity=kw.pop("log_capacity", 64),
        recovery=kw.pop("recovery", True), **kw,
    )
    for t, x, y in _traffic(8, 30):
        srv.submit(t, x, y)
    srv.drain()
    slot = srv.resident[1]
    srv.queue.state = srv.queue.state._replace(
        theta=srv.queue.state.theta.at[slot].set(jnp.nan)
    )
    return srv, slot


def test_nan_poison_quarantines_then_rebuilds():
    srv, slot = _degraded_server()
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()  # fold fires finite, recovery rebuilds in the same call
    rec = srv.recovery
    assert rec.history == [
        {"tenant": 1, "action": "rebuild", "verified": True}
    ]
    assert rec.quarantined == frozenset()
    counters = srv.metrics.snapshot()["counters"]
    assert counters["recovery.quarantines"] == 1
    assert counters["recovery.repairs{action=rebuild}"] == 1
    assert counters["recovery.releases"] == 1
    assert np.isfinite(np.asarray(srv.queue.state.theta)).all()


def test_overflowed_log_fails_complete_and_falls_through_to_reset():
    # The satellite: rebuild from a windowed ring must NOT install partial
    # state as full history — complete()==False surfaces through the
    # RecoveryPolicy pre-check and the ladder falls through to reset.
    srv, slot = _degraded_server(log_capacity=4)
    assert not srv.log.complete(1)
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()
    rec = srv.recovery
    assert rec.history[0] == {
        "tenant": 1, "action": "rebuild",
        "outcome": "fallthrough", "reason": "incomplete_log",
    }
    assert rec.history[1] == {
        "tenant": 1, "action": "reset", "verified": True,
    }
    assert rec.quarantined == frozenset()
    # Reset forgot the (windowed) history along with the state.
    assert srv.log.size(1) == 0
    assert np.isfinite(np.asarray(srv.queue.state.theta)).all()
    row = np.asarray(srv.queue.state.theta[slot])
    assert np.array_equal(row, np.zeros_like(row))


def test_repeated_failures_escalate_backoff_then_give_up(monkeypatch):
    fake = [0.0]
    srv, slot = _degraded_server(
        recovery={"max_retries": 2, "backoff_base": 10.0,
                  "clock": lambda: fake[0]},
    )
    rec = srv.recovery
    monkeypatch.setattr(rec, "_verify", lambda ep: False)
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()
    ep = rec._episodes[1]
    assert ep.attempts == 1 and ep.backoff_until == 10.0 * 2.0
    n_attempts = len(rec.history)
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()  # still inside backoff: no new attempt
    assert len(rec.history) == n_attempts
    fake[0] = 100.0
    rec.process()  # attempt 2 (reset rung), fails, exceeds max_retries...
    fake[0] = 1000.0
    rec.process()
    assert ep.gave_up
    assert 1 in rec.quarantined  # kept for the operator
    counters = srv.metrics.snapshot()["counters"]
    assert counters["recovery.gave_up"] == 1
    assert "recovery.releases" not in counters
    # The parked slot is healthy, so bank-global probes stay quiet.
    assert np.isfinite(np.asarray(srv.queue.state.theta)).all()
    before = srv.probe.total_events
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()
    assert srv.probe.total_events == before


def test_quarantined_tenant_reads_healthy_writes_deferred(monkeypatch):
    srv, slot = _degraded_server()
    rec = srv.recovery
    healthy_theta = np.asarray(rec._last_healthy[0].theta[slot]).copy()
    # Freeze the episode open so the quarantine behavior is observable.
    monkeypatch.setattr(rec, "_repair_due", lambda: None)
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()
    assert 1 in rec.quarantined
    xq = np.ones(3, np.float32)
    pred = float(srv.predict(1, xq))
    from repro.serve.snapshot import predict_row

    expect = float(predict_row(healthy_theta, xq[None], _RFF)[0])
    assert pred == expect  # served from the captured healthy row
    assert np.isfinite(pred)
    n_before = srv.log.size(1)
    lag_before = srv._slot_lags()[slot]
    srv.submit(1, xq, 1.0)  # deferred: logged, not queued
    assert srv.log.size(1) == n_before + 1
    assert srv.queue.backlog()[slot] == 0
    assert srv._slot_lags()[slot] == lag_before
    counters = srv.metrics.snapshot()["counters"]
    assert counters["recovery.deferred"] == 1
    assert counters["read.quarantined"] == 1


def test_recovery_requires_probe_and_single_bind():
    with pytest.raises(ValueError, match="probe"):
        RecoveryPolicy().bind(
            make_server("klms", feature_map=_RFF, bank=2, chunk=4, mu=0.3)
        )
    srv = make_server("klms", feature_map=_RFF, bank=2, chunk=4, mu=0.3,
                      recovery=True)
    assert srv.probe is not None  # recovery implies probe
    with pytest.raises(RuntimeError, match="bound"):
        srv.recovery.bind(srv)


def test_process_drains_events_across_repeated_folds():
    # Regression: the monitor's subscriber holds a reference to the
    # pending-events list; process() must drain it in place, or every
    # event after the first fold is appended to an orphaned list.
    srv, slot = _degraded_server()
    rec = srv.recovery
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()
    assert rec.history  # first fold acted
    # Poison again: the second episode must be seen too.
    slot2 = srv.resident[2]
    srv.queue.state = srv.queue.state._replace(
        theta=srv.queue.state.theta.at[slot2].set(jnp.nan)
    )
    srv.submit(0, np.zeros(3, np.float32), 0.0)
    srv.drain()
    assert len(rec.history) >= 2
    assert rec.quarantined == frozenset()
    assert np.isfinite(np.asarray(srv.queue.state.theta)).all()
