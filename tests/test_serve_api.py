"""Facade / shim / policy-tier contracts (serve/api.py, serve/policy.py).

Three contract families:

* equivalence — the deprecated per-family entry points and the
  learner-parameterized facade run the SAME jitted programs, so identical
  input streams must produce bitwise-identical states (all five learner
  families; the three non-fused families are pinned against the core
  ``bank_run`` reference, which the generic masked chunk path must match
  exactly on lockstep traffic);
* deprecation — every old name still imports and emits exactly one
  ``DeprecationWarning`` per process (latch re-armed per test via the
  testing hook);
* policy — eviction-order determinism (score, then recency, then tenant
  id), the admission floor (reject when no incumbent scores strictly
  below the candidate), and pow2 resize compaction preserving resident
  rows bitwise.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import bank_init, bank_run, bank_size, resize_bank, tenant_row
from repro.core.rff import sample_rff
from repro.serve import api
from repro.serve.policy import SlotPolicy

D_IN, D_FEAT = 3, 16
RFF = sample_rff(jax.random.PRNGKey(0), D_IN, D_FEAT, 1.0)


def lockstep_stream(bank=4, n=12, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(bank, n, D_IN)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(bank, n)), jnp.float32)
    return xs, ys


def ragged_traffic(tenants=4, n=40, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            int(rng.integers(0, tenants)),
            rng.normal(size=D_IN).astype(np.float32),
            float(rng.normal()),
        )
        for _ in range(n)
    ]


def assert_trees_bitwise(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol
        )


# ---------------------------------------------------------------------------
# Facade vs deprecated entry points: bitwise equivalence, five families
# ---------------------------------------------------------------------------


def test_run_stream_matches_old_serve_bank_stream():
    from repro.serve.bank_loop import serve_bank_stream

    xs, ys = lockstep_stream()
    st_old, out_old = serve_bank_stream(RFF, xs, ys, 0.3)
    st_new, out_new = api.run_stream("klms", RFF, xs, ys, mu=0.3)
    assert_trees_bitwise(st_old, st_new)
    np.testing.assert_array_equal(
        np.asarray(out_old.prediction), np.asarray(out_new.prediction)
    )


def test_run_stream_matches_old_krls_stream():
    from repro.serve.bank_loop import serve_krls_bank_stream

    xs, ys = lockstep_stream(seed=1)
    st_old, _ = serve_krls_bank_stream(RFF, xs, ys, lam=1e-2, beta=0.999)
    st_new, _ = api.run_stream("krls", RFF, xs, ys, lam=1e-2, beta=0.999)
    assert_trees_bitwise(st_old, st_new)


@pytest.mark.parametrize("learner", ["nklms", "qklms", "ald"])
def test_run_stream_matches_core_bank_run(learner):
    """The families with no fused path ride the generic scan — which must
    be the exact program ``core.bank.bank_run`` runs."""
    xs, ys = lockstep_stream(seed=2)
    if learner == "nklms":
        fm, hp = RFF, dict(mu=0.3)
    else:
        fm, hp = None, dict(sigma=1.0, capacity=8)
    lrn = api.build_learner(learner, fm, input_dim=D_IN, **hp)
    ref_state, ref_out = jax.jit(lambda s: bank_run(lrn, s, xs, ys))(
        bank_init(lrn, 4)
    )
    st, out = api.run_stream(learner, fm, xs, ys, input_dim=D_IN, **hp)
    assert_trees_bitwise(ref_state, st)
    np.testing.assert_array_equal(
        np.asarray(ref_out.prediction), np.asarray(out.prediction)
    )


def test_make_queue_matches_old_micro_batch_queues():
    from repro.serve.queue import (
        klms_micro_batch_queue,
        krls_micro_batch_queue,
    )

    traffic = ragged_traffic(n=30)
    for old_factory, learner, hp in [
        (klms_micro_batch_queue, "klms", dict(mu=0.3)),
        (krls_micro_batch_queue, "krls", dict(lam=1e-2, beta=0.999)),
    ]:
        q_old = old_factory(RFF, 4, chunk=4, **hp)
        q_new = api.make_queue(learner, RFF, 4, chunk=4, **hp)
        for t, x, y in traffic:
            q_old.submit(t, x, y)
            q_new.submit(t, x, y)
        q_old.drain()
        q_new.drain()
        assert_trees_bitwise(q_old.state, q_new.state)


@pytest.mark.parametrize(
    "learner,kw",
    [
        ("klms", dict(feature_map=RFF, mu=0.3)),
        ("nklms", dict(feature_map=RFF, mu=0.3)),
        ("krls", dict(feature_map=RFF, lam=1e-2, beta=0.999)),
        ("qklms", dict(input_dim=D_IN, sigma=1.0, capacity=8)),
        ("ald", dict(input_dim=D_IN, sigma=1.0, capacity=8)),
    ],
)
def test_server_chunked_matches_lockstep_reference(learner, kw):
    """Full facade write path (queue + snapshot) on lockstep traffic ==
    the one-shot stream drive, for every family. KLMS and the generic
    families are bitwise; KRLS compares the one-launch stream kernel
    against per-chunk launches — different GEMM groupings for the P
    update — so it gets a tight f32 tolerance instead."""
    xs, ys = lockstep_stream(bank=3, n=8, seed=3)
    ref_state, _ = api.run_stream(
        learner, kw.get("feature_map"), xs, ys, chunk=4,
        **{k: v for k, v in kw.items() if k != "feature_map"},
    )
    srv = api.make_server(learner, bank=3, chunk=4, **kw)
    for t in range(xs.shape[1]):
        for b in range(3):
            srv.submit(b, np.asarray(xs[b, t]), float(ys[b, t]))
    srv.drain()
    if learner == "krls":
        assert_trees_close(ref_state, srv.queue.state, rtol=1e-4, atol=1e-5)
    else:
        assert_trees_bitwise(ref_state, srv.queue.state)


def test_reset_slots_matches_old_resets():
    from repro.serve.bank_loop import reset_krls_tenants, reset_tenants

    xs, ys = lockstep_stream()
    st, _ = api.run_stream("klms", RFF, xs, ys, mu=0.3)
    slots = jnp.array([0, 2])
    assert_trees_bitwise(
        reset_tenants(st, slots), api.reset_slots(st, slots)
    )
    kst, _ = api.run_stream("krls", RFF, xs, ys, lam=1e-2)
    assert_trees_bitwise(
        reset_krls_tenants(kst, slots, lam=1e-2),
        api.reset_slots(kst, slots, learner="krls", lam=1e-2),
    )


def test_facade_rejects_unknown_learner_and_hp():
    with pytest.raises(ValueError, match="unknown learner"):
        api.make_server("svm", feature_map=RFF)
    with pytest.raises(TypeError, match="unknown hyperparameters"):
        api.make_server("klms", feature_map=RFF, learning_rate=0.1)


# ---------------------------------------------------------------------------
# Deprecation shims: every old name importable, exactly one warning each
# ---------------------------------------------------------------------------

OLD_NAMES = [
    "make_bank_server",
    "serve_bank_stream",
    "reset_tenants",
    "make_krls_bank_server",
    "serve_krls_bank_stream",
    "reset_krls_tenants",
    "make_chunked_bank_server",
    "make_chunked_krls_bank_server",
    "klms_micro_batch_queue",
    "krls_micro_batch_queue",
]


def test_all_old_names_importable_from_serve():
    import repro.serve as serve

    for name in OLD_NAMES:
        assert callable(getattr(serve, name))
        assert name in serve.__all__


def test_deprecation_warning_fires_exactly_once_per_name():
    import repro.serve as serve

    api._reset_deprecation_state()
    xs, ys = lockstep_stream(bank=2, n=4)
    st, _ = api.run_stream("klms", RFF, xs, ys, mu=0.3)
    kst, _ = api.run_stream("krls", RFF, xs, ys)
    calls = {
        "make_bank_server": lambda: serve.make_bank_server(RFF, 0.3),
        "serve_bank_stream": lambda: serve.serve_bank_stream(
            RFF, xs, ys, 0.3
        ),
        "reset_tenants": lambda: serve.reset_tenants(st, jnp.array([0])),
        "make_krls_bank_server": lambda: serve.make_krls_bank_server(RFF),
        "serve_krls_bank_stream": lambda: serve.serve_krls_bank_stream(
            RFF, xs, ys
        ),
        "reset_krls_tenants": lambda: serve.reset_krls_tenants(
            kst, jnp.array([0])
        ),
        "make_chunked_bank_server": lambda: serve.make_chunked_bank_server(
            RFF, 0.3
        ),
        "make_chunked_krls_bank_server": (
            lambda: serve.make_chunked_krls_bank_server(RFF)
        ),
        "klms_micro_batch_queue": lambda: serve.klms_micro_batch_queue(
            RFF, 2
        ),
        "krls_micro_batch_queue": lambda: serve.krls_micro_batch_queue(
            RFF, 2
        ),
    }
    assert set(calls) == set(OLD_NAMES)
    for name, call in calls.items():
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            call()
            call()  # second call: latched, no second warning
        dep = [
            w for w in caught if issubclass(w.category, DeprecationWarning)
            and name in str(w.message)
        ]
        assert len(dep) == 1, f"{name}: {len(dep)} warnings"
        assert "deprecated" in str(dep[0].message)
    api._reset_deprecation_state()


# ---------------------------------------------------------------------------
# Policy unit tests
# ---------------------------------------------------------------------------


def drive(policy, events):
    """Replay (kind, tenant) events; return the decision/victim trace."""
    trace = []
    for kind, tenant in events:
        if kind == "touch":
            policy.touch(tenant)
        else:
            d = policy.admit(tenant)
            trace.append((tenant, d.action, d.slot, d.victim))
    return trace


def test_eviction_order_deterministic():
    rng = np.random.default_rng(7)
    events = []
    for _ in range(200):
        t = int(rng.integers(0, 12))
        events.append(("touch", t))
        events.append(("admit", t))
    for scorer in ("lru", "lfu", "cost"):
        a = SlotPolicy(3, scorer=scorer, cost_fn=lambda t: 1.0 + t % 3)
        b = SlotPolicy(3, scorer=scorer, cost_fn=lambda t: 1.0 + t % 3)
        assert drive(a, events) == drive(b, events)
        assert a.resident == b.resident


def test_victim_tie_break_is_recency_then_id():
    pol = SlotPolicy(3, scorer="lfu")
    for t in (0, 1, 2):
        pol.touch(t)
        pol.admit(t)
    # All scores tie at 1 touch; 0 was touched longest ago.
    assert pol.victim() == 0
    pol.touch(0)  # 0 now outranks on lfu score
    assert pol.victim() == 1


def test_admission_floor_rejects_cold_candidates():
    pol = SlotPolicy(2, scorer="lfu")
    for t in (0, 1):
        for _ in range(3):
            pol.touch(t)
        pol.admit(t)
    pol.touch(9)  # one-hit wonder: score 1 vs incumbents' 3
    d = pol.admit(9)
    assert d.action == "reject"
    assert pol.lookup(9) is None
    assert pol.rejects_since_resize == 1
    # force (operator readmit) bypasses the floor
    d = pol.admit(9, force=True)
    assert d.action == "evict" and d.victim == 0
    # LRU always admits: a fresh touch outranks any incumbent
    lru = SlotPolicy(1, scorer="lru")
    lru.touch(0)
    lru.admit(0)
    lru.touch(5)
    assert lru.admit(5).action == "evict"


def test_suggest_size_grow_and_shrink():
    pol = SlotPolicy(2, scorer="lfu", grow_rejects=2, min_slots=1)
    for t in (0, 1):
        for _ in range(3):
            pol.touch(t)
        pol.admit(t)
    assert pol.suggest_size() == 2
    for _ in range(2):
        pol.touch(7)
        pol.admit(7)
    assert pol.suggest_size() == 4
    pol.set_slots(4)
    assert pol.rejects_since_resize == 0
    pol.release(0)
    pol.release(1)
    pol.release(7)
    assert pol.suggest_size() == 2


def test_bank_resize_grow_preserves_rows_bitwise():
    xs, ys = lockstep_stream(bank=4, n=8)
    st, _ = api.run_stream("klms", RFF, xs, ys, mu=0.3)
    grown = resize_bank(st, 8)
    assert bank_size(grown) == 8
    for b in range(4):
        assert_trees_bitwise(tenant_row(st, b), tenant_row(grown, b))
    assert not np.asarray(tenant_row(grown, 6).theta).any()
    shrunk = resize_bank(grown, 2)
    for b in range(2):
        assert_trees_bitwise(tenant_row(st, b), tenant_row(shrunk, b))


def test_server_resize_compaction_preserves_resident_rows_bitwise():
    srv = api.make_server(
        "klms", feature_map=RFF, bank=4, chunk=4, mu=0.3,
        policy="lfu", log_capacity=16,
    )
    for t, x, y in ragged_traffic(tenants=4, n=40, seed=5):
        srv.submit(t, x, y)
    srv.drain()
    before = {
        t: tenant_row(srv.queue.state, s)
        for t, s in srv.policy.resident.items()
    }
    srv.resize(8)
    assert srv.slots == 8 and srv.queue.num_tenants == 8
    for t, s in srv.policy.resident.items():
        assert_trees_bitwise(before[t], tenant_row(srv.queue.state, s))
    # Shrink below occupancy: coldest evicted, survivors compacted bitwise
    srv.resize(2)
    assert srv.slots == 2 and srv.policy.occupancy <= 2
    for t, s in srv.policy.resident.items():
        assert s < 2
        assert_trees_bitwise(before[t], tenant_row(srv.queue.state, s))
    with pytest.raises(ValueError, match="power of two"):
        srv.resize(3)


# ---------------------------------------------------------------------------
# Policy-mode server integration
# ---------------------------------------------------------------------------


def test_policy_server_admits_evicts_and_rebuilds():
    srv = api.make_server(
        "klms", feature_map=RFF, bank=2, chunk=4, mu=0.3,
        policy="lru", log_capacity=32,
    )
    rng = np.random.default_rng(6)
    obs = {t: [] for t in range(3)}
    for _ in range(30):
        t = int(rng.integers(0, 3))
        x = rng.normal(size=D_IN).astype(np.float32)
        y = float(rng.normal())
        obs[t].append((x, y))
        srv.submit(t, x, y)
    srv.drain()
    m = srv.metrics
    assert m.count("evictions") > 0
    assert m.count("readmissions") > 0
    assert srv.policy.occupancy == 2
    # Every resident tenant's row must equal a from-scratch replay of its
    # full logged history (log_capacity was never exceeded). The live row
    # is mid-history rebuilds plus chunked online updates, so chunk
    # boundaries differ from the one-shot replay — tight f32 tolerance,
    # not bitwise (observed drift is ~1 ulp).
    for t, slot in srv.policy.resident.items():
        assert srv.log.complete(t)
        xs = jnp.asarray(np.stack([x for x, _ in obs[t]]))
        ys = jnp.asarray(np.asarray([y for _, y in obs[t]], np.float32))
        ref = srv._lrn.rebuild(xs, ys, mode="scan")
        assert_trees_close(ref, tenant_row(srv.queue.state, slot))


def test_policy_server_cold_read_returns_zeros_without_admitting():
    srv = api.make_server(
        "klms", feature_map=RFF, bank=2, chunk=4, mu=0.3, policy="lru",
    )
    q = np.ones(D_IN, np.float32)
    assert float(srv.predict(17, q)) == 0.0
    assert np.asarray(srv.predict(17, np.ones((5, D_IN), np.float32))).shape == (5,)
    assert srv.policy.lookup(17) is None
    assert srv.metrics.count("read.cold") == 2


def test_policy_server_rejection_logs_but_does_not_train():
    srv = api.make_server(
        "klms", feature_map=RFF, bank=1, chunk=4, mu=0.3,
        policy="lfu", log_capacity=8,
    )
    x = np.ones(D_IN, np.float32)
    for _ in range(3):
        srv.submit(0, x, 1.0)
    srv.drain()
    theta_before = np.asarray(srv.queue.state.theta).copy()
    srv.submit(42, x, 1.0)  # floor: 1 touch vs incumbent's 3 -> reject
    srv.drain()
    assert srv.metrics.count("admission.rejects") == 1
    assert srv.log.size(42) == 1
    np.testing.assert_array_equal(
        theta_before, np.asarray(srv.queue.state.theta)
    )
