"""Bank-slot eviction / rebuild lifecycle (core/bank.py + serve/snapshot.py).

The property under test (hypothesis, all five learners): evicting a
learner and rebuilding it from its replay log reproduces the
never-evicted state — bitwise through the sequential replay path, within
the pinned replay tolerances through the scan/blocked engine, at
arbitrary (mid-chunk) eviction boundaries. The f64 variant rides in a
subprocess (conftest pins x64 off) and shows drift shrinking with
precision, i.e. the lifecycle is exact algebra, not a lucky f32 artifact.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Only the two property tests need hypothesis (optional dep, installed in
# CI) — the bank/server/f64 lifecycle tests below must run without it.
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in minimal containers
    HAVE_HYPOTHESIS = False

from repro.core.bank import (
    evict_tenant,
    klms_bank_init,
    krls_bank_init,
    rebuild_tenant,
    set_tenant_row,
    tenant_row,
)
from repro.core.klms import rff_klms_run
from repro.core.krls import rff_krls_run
from repro.core.learner import (
    ald_krls_learner,
    klms_learner,
    krls_learner,
    nklms_learner,
    qklms_learner,
)
from repro.core.rff import sample_rff
from repro.serve import make_server
from repro.serve.snapshot import ReplayLog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RFF = sample_rff(jax.random.PRNGKey(0), 3, 32, 1.0)

FAMILIES = ["klms", "nklms", "krls", "qklms", "ald"]


def _learner(family):
    return {
        "klms": lambda: klms_learner(_RFF, 0.3),
        "nklms": lambda: nklms_learner(_RFF, 0.3),
        "krls": lambda: krls_learner(_RFF, lam=0.1, beta=0.99),
        "qklms": lambda: qklms_learner(3, 1.0, 0.3, 0.1, capacity=32),
        "ald": lambda: ald_krls_learner(3, 1.0, nu=5e-4, capacity=32),
    }[family]()


def _stream(seed, n, d=3):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return (
        jax.random.normal(kx, (n, d)),
        jax.random.normal(ky, (n,)),
    )


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert bool(jnp.array_equal(la, lb)), (la, lb)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# -- the satellite property: evict -> rebuild(log) == never evicted ---------

if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("family", FAMILIES)
    @given(seed=st.integers(0, 2**16), cut=st.integers(1, 47))
    @settings(max_examples=8, deadline=None)
    def test_evict_rebuild_from_log_matches_never_evicted(family, seed, cut):
        """Sequential rebuild of the full log is BITWISE the never-evicted
        state for every learner — the state at the (arbitrary, mid-chunk)
        eviction tick is discarded and never consulted."""
        lrn = _learner(family)
        xs, ys = _stream(seed, 48)
        never, _ = lrn.run(None, xs, ys)
        # Evict at `cut`: whatever state existed there is dropped on the
        # floor; the rebuild sees only the log.
        _discarded, _ = lrn.run(None, xs[:cut], ys[:cut])
        rebuilt = lrn.rebuild(xs, ys, mode="sequential")
        _assert_trees_equal(never, rebuilt)

    @pytest.mark.parametrize("family", ["klms", "nklms", "krls"])
    @given(seed=st.integers(0, 2**16), cut=st.integers(1, 47))
    @settings(max_examples=8, deadline=None)
    def test_warm_rebuild_across_cut_matches_never_evicted(family, seed, cut):
        """Scan/blocked replay restarted from the state at an arbitrary
        cut (mid-chunk boundaries included: chunk=16, cut uniform in
        [1, 47]) lands on the never-evicted state within the replay
        tolerance."""
        lrn = _learner(family)
        xs, ys = _stream(seed, 48)
        never, _ = lrn.run(None, xs, ys)
        at_cut, _ = lrn.run(None, xs[:cut], ys[:cut])
        for mode in ("scan", "blocked"):
            rebuilt = lrn.rebuild(
                xs[cut:], ys[cut:], state=at_cut, mode=mode, chunk=16
            )
            # KRLS warm start round-trips Phi_0 = inv(P_0) at f32.
            tol = 5e-4 if family == "krls" else 5e-5
            assert _rel(rebuilt.theta, never.theta) < tol, (mode, cut)
            assert int(rebuilt.step) == 48


# -- bank-level lifecycle ----------------------------------------------------


def test_bank_evict_parks_fresh_row(key):
    lms = klms_bank_init(_RFF, 3)
    lms = jax.tree.map(lambda a: a + 1.0, lms)  # make rows non-trivial
    ev = evict_tenant(lms, 1)
    assert float(jnp.abs(ev.theta[1]).max()) == 0.0
    assert float(jnp.abs(ev.theta[0] - lms.theta[0]).max()) == 0.0

    rls = krls_bank_init(_RFF, 3, jnp.asarray([0.1, 0.2, 0.5]))
    ev = evict_tenant(rls, 2, lam=jnp.asarray([0.1, 0.2, 0.5]))
    # P_0 = I/lam with the TENANT'S lam from the (B,) sweep.
    np.testing.assert_allclose(
        np.asarray(ev.pmat[2]), np.eye(32, dtype=np.float32) / 0.5, atol=1e-6
    )


def test_bank_rebuild_tenant_sequential_is_bitwise(key):
    xs, ys = _stream(3, 50)
    state = klms_bank_init(_RFF, 3)
    state = rebuild_tenant(state, 1, _RFF, xs, ys, mu=0.3, mode="sequential")
    seq, _ = rff_klms_run(_RFF, xs, ys, 0.3)
    assert bool(jnp.array_equal(state.theta[1], seq.theta))

    rls = krls_bank_init(_RFF, 3, 0.1)
    rls = rebuild_tenant(
        rls, 2, _RFF, xs, ys, lam=0.1, beta=0.99, mode="sequential"
    )
    kseq, _ = rff_krls_run(_RFF, xs, ys, lam=0.1, beta=0.99)
    assert bool(jnp.array_equal(rls.theta[2], kseq.theta))
    assert bool(jnp.array_equal(rls.pmat[2], kseq.pmat))


def test_tenant_row_roundtrip(key):
    state = klms_bank_init(_RFF, 4)
    row = tenant_row(state, 2)
    bumped = jax.tree.map(lambda a: a + 3.0, row)
    state2 = set_tenant_row(state, 2, bumped)
    _assert_trees_equal(tenant_row(state2, 2), bumped)
    _assert_trees_equal(tenant_row(state2, 0), tenant_row(state, 0))


# -- replay log --------------------------------------------------------------


def test_replay_log_ring_semantics():
    log = ReplayLog(capacity=4)
    for i in range(6):
        log.append(0, np.full(3, i, np.float32), float(i))
    assert log.size(0) == 4
    assert log.dropped(0) == 2
    assert not log.complete(0)
    xs, ys = log.arrays(0)
    assert xs.shape == (4, 3)
    np.testing.assert_array_equal(ys, [2.0, 3.0, 4.0, 5.0])
    assert log.complete(1) and log.size(1) == 0
    log.clear(0)
    assert log.size(0) == 0 and log.complete(0)


# -- snapshot-server integration --------------------------------------------


def _drive(server, obs):
    for t, x, y in obs:
        server.submit(t, x, y)
    server.drain()


def _obs(seed, n, tenants=3):
    rng = np.random.default_rng(seed)
    return [
        (int(rng.integers(0, tenants)), rng.normal(size=3).astype(np.float32),
         float(rng.normal()))
        for _ in range(n)
    ]


@pytest.mark.parametrize("policy", [None, "lru"])
@pytest.mark.parametrize("family", ["klms", "krls"])
def test_server_evict_readmit_matches_never_evicted(family, policy):
    """Without a policy an evicted tenant's arrivals only log until
    ``readmit``; under LRU with a slot per tenant its next arrival
    readmits it. Either way the rebuilt row matches the never-evicted one
    and every other row is untouched."""

    def make():
        return make_server(
            family, feature_map=_RFF, bank=3, chunk=8, log_capacity=512,
            policy=policy, **_SERVER_HP[family],
        )

    srv, ctl = make(), make()
    obs = _obs(7, 240)
    _drive(ctl, obs)

    _drive(srv, obs[:100])
    slot = srv.resident[1]
    srv.evict(1)
    assert 1 not in srv.resident
    # While evicted: reads serve the parked fresh row.
    if family == "klms":
        assert float(jnp.abs(srv.snapshot.state.theta[slot]).max()) == 0.0
    n1 = sum(1 for t, _, _ in obs if t == 1)
    if policy is None:
        assert 1 in srv.evicted
        _drive(srv, obs[100:])
        assert srv.queue.backlog()[1] == 0  # nothing queued while evicted
        assert srv.readmit(1) == n1
        assert 1 not in srv.evicted
    else:
        assert srv.evicted == frozenset()
        _drive(srv, obs[100:])
        assert srv.metrics.count("readmissions") == 1
        assert srv.readmit(1) == 0  # already back
    assert srv.resident == ctl.resident
    assert _rel(
        srv.snapshot.state.theta[slot], ctl.snapshot.state.theta[slot]
    ) < 5e-5
    # Untouched tenants are bit-identical to the control server.
    for b in set(range(3)) - {slot}:
        _assert_trees_equal(
            tenant_row(srv.snapshot.state, b), tenant_row(ctl.snapshot.state, b)
        )


@pytest.mark.parametrize("family", ["klms", "krls"])
def test_identity_and_lru_servers_share_one_lifecycle(family):
    """A server without a policy and an LRU server that never has to evict
    run one tenant lifecycle: the same traffic with ``evict``, ``readmit``
    and ``reset_tenant`` of one tenant ends in bitwise-equal live state,
    replica and flush priors."""
    servers = [
        make_server(
            family, feature_map=_RFF, bank=3, chunk=8, log_capacity=64,
            policy=policy, rebuild_mode="sequential", **_SERVER_HP[family],
        )
        for policy in (None, "lru")
    ]
    # First arrivals in tenant order, so LRU places tenant t in slot t.
    head = [(t, np.full(3, 0.1 * t, np.float32), 1.0) for t in range(3)]
    obs = head + _obs(17, 90)
    others = [a for a in _obs(19, 60) if a[0] != 1]

    def run(srv):
        priors = []
        for part, op in ((obs[:60], srv.evict), (others, srv.readmit),
                         (obs[60:], srv.reset_tenant), (others, None)):
            for t, x, y in part:
                srv.submit(t, x, y)
            priors.append(srv.drain())
            if op is not None:
                op(1)
        return priors

    ident, lru = servers
    p_ident, p_lru = run(ident), run(lru)
    assert ident.policy is None and lru.policy is not None
    assert ident.resident == lru.resident == {0: 0, 1: 1, 2: 2}
    assert ident.metrics.count("readmissions") == 1
    assert p_ident == p_lru
    _assert_trees_equal(ident.queue.state, lru.queue.state)
    _assert_trees_equal(ident.snapshot.state, lru.snapshot.state)


def test_server_sequential_readmit_is_bitwise():
    srv = make_server(
        "klms", feature_map=_RFF, bank=3, mu=0.3, chunk=8, log_capacity=512,
        rebuild_mode="sequential",
    )
    obs = _obs(11, 200)
    _drive(srv, obs)
    srv.evict(2)
    srv.readmit(2)
    x2 = np.stack([x for t, x, _ in obs if t == 2])
    y2 = np.asarray([y for t, _, y in obs if t == 2], np.float32)
    seq, _ = rff_klms_run(_RFF, jnp.asarray(x2), jnp.asarray(y2), 0.3)
    assert bool(jnp.array_equal(srv.snapshot.state.theta[2], seq.theta))


def test_server_evict_drops_pending_and_publishes():
    srv = make_server(
        "klms", feature_map=_RFF, bank=2,
        mu=0.3, chunk=16, log_capacity=64, publish_every=1000
    )
    rng = np.random.default_rng(0)
    for _ in range(5):
        srv.submit(0, rng.normal(size=3).astype(np.float32), 1.0)
    version_before = srv.snapshot.version
    assert srv.evict(0) == 5
    assert srv.queue.backlog() == [0, 0]
    assert srv.snapshot.version == version_before + 1  # eviction publishes
    assert srv.log.size(0) == 5  # the log keeps what the queue dropped
    assert srv.readmit(0) == 5


def test_server_readmit_overflowed_log_is_windowed():
    """Ring overflow -> readmission rebuilds fresh-init + last `capacity`
    ticks, and the log flags the truncation."""
    srv = make_server("klms", feature_map=_RFF, bank=2, mu=0.3, chunk=8,
                      log_capacity=16)
    obs = [(0, x, y) for _, x, y in _obs(13, 40)]
    _drive(srv, obs)
    srv.evict(0)
    assert not srv.log.complete(0)
    assert srv.readmit(0) == 16
    xs = np.stack([x for _, x, _ in obs[-16:]])
    ys = np.asarray([y for _, _, y in obs[-16:]], np.float32)
    win, _ = rff_klms_run(_RFF, jnp.asarray(xs), jnp.asarray(ys), 0.3)
    assert _rel(srv.snapshot.state.theta[0], win.theta) < 5e-5


def test_server_reset_clears_lifecycle_state():
    srv = make_server("klms", feature_map=_RFF, bank=2, mu=0.3,
                      log_capacity=8)
    srv.submit(0, np.zeros(3, np.float32), 1.0)
    srv.drain()
    srv.evict(0)
    from repro.core.bank import klms_bank_init

    srv.reset(klms_bank_init(_RFF, 2))
    assert srv.evicted == frozenset()
    assert srv.log.size(0) == 0 and srv.log.complete(0)


def test_reset_tenant_clears_stale_log_truncation_flags():
    """Regression: a per-tenant reset while the ring has overflowed must
    clear the dropped-entry counter along with the history. Before the
    fix, the next occupant of the slot inherited ``complete() == False``
    from the previous tenant and a later evict/rebuild silently replayed
    a truncated (empty) history as if it were the full stream."""
    srv = make_server("klms", feature_map=_RFF, bank=3, mu=0.3, chunk=8,
                      log_capacity=16)
    obs = _obs(11, 120)
    _drive(srv, obs)
    assert srv.log.dropped(1) > 0 and not srv.log.complete(1)

    srv.evict(1)
    dropped = srv.reset_tenant(1)
    assert dropped == 0  # drain()ed above, nothing pending
    # Log state fully cleared: no history AND no stale truncation flag.
    assert srv.log.size(1) == 0
    assert srv.log.dropped(1) == 0
    assert srv.log.complete(1)
    # The slot left the evicted set and serves the parked fresh row.
    assert 1 not in srv.evicted
    assert float(jnp.abs(srv.snapshot.state.theta[1]).max()) == 0.0

    # The slot trains normally again, identical to a fresh server fed the
    # same post-reset stream.
    post = [(t, x, y) for (t, x, y) in _obs(13, 80) if t == 1]
    ctl = make_server("klms", feature_map=_RFF, bank=3, mu=0.3, chunk=8,
                      log_capacity=16)
    _drive(srv, post)
    _drive(ctl, post)
    assert bool(
        jnp.array_equal(
            srv.snapshot.state.theta[1], ctl.snapshot.state.theta[1]
        )
    )
    assert srv.log.complete(1) == ctl.log.complete(1)


# -- make_server's eviction: one compiled row write ------------------------

_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_SERVER_HP = {
    "klms": dict(mu=0.3),
    "nklms": dict(mu=0.3),
    "krls": dict(lam=0.1, beta=0.99),
    "qklms": dict(sigma=1.0, mu=0.3, quant_eps=0.1, capacity=32),
    "ald": dict(sigma=1.0, nu=5e-4, capacity=32),
}


def _old_evict(family, state, slot):
    """The row write each family's eviction made before it was compiled."""
    if family in ("qklms", "ald"):
        zeros = jax.tree.map(jnp.zeros_like, tenant_row(state, slot))
        return set_tenant_row(state, slot, zeros)
    return evict_tenant(state, slot, lam=_SERVER_HP[family].get("lam", 1e-4))


@pytest.mark.parametrize("family", FAMILIES)
def test_make_server_eviction_is_one_program_bitwise_the_eager_write(family):
    """The server's eviction parks bitwise the row ``core.bank.evict_tenant``
    (zeros for the dictionary families) parks, at the first, second and
    last slot, through one program lowered once for every slot."""
    bank = 6
    srv = make_server(family, feature_map=_RFF, bank=bank, chunk=4,
                      **_SERVER_HP[family])
    _drive(srv, _obs(3, 60, tenants=bank))
    state = srv.queue.state
    evict_fn = srv._inner._evict_fn
    jax.clear_caches()  # families of one state type share the program
    lowerings = []

    def on(name, _secs, **_kw):
        if name == _LOWERING:
            lowerings.append(name)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        got = {slot: evict_fn(state, slot) for slot in (0, 1, bank - 1)}
        jax.block_until_ready(got)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert len(lowerings) == 1
    for slot, new in got.items():
        assert not all(
            bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree.leaves(tenant_row(state, slot)),
                jax.tree.leaves(tenant_row(new, slot)),
            )
        ), slot  # the slot was trained, so the write is seen
        _assert_trees_equal(new, _old_evict(family, state, slot))


@pytest.mark.parametrize("policy", [None, "lru"])
def test_snapshot_taken_before_an_eviction_keeps_its_row(policy):
    """The eviction writes a new bank and leaves the old one alone (no
    donation): a reader holding the snapshot published before it still
    reads the evicted tenant's trained row."""
    srv = make_server("krls", feature_map=_RFF, bank=4, chunk=4,
                      policy=policy, **_SERVER_HP["krls"])
    _drive(srv, _obs(5, 40, tenants=3))
    slot = 1 if policy is None else srv.policy.lookup(1)
    held = srv.snapshot
    before = jax.tree.map(np.array, tenant_row(held.state, slot))
    assert srv.evict(1) == 0
    assert srv.snapshot.version > held.version
    _assert_trees_equal(srv.snapshot.state,
                        _old_evict("krls", held.state, slot))
    after = jax.tree.map(np.asarray, tenant_row(held.state, slot))
    _assert_trees_equal(after, before)
    assert int(before.step) > 0


# -- f64 (subprocess: conftest pins x64 off) --------------------------------

_F64_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core.learner import klms_learner, krls_learner
from repro.core.rff import sample_rff

rff = sample_rff(jax.random.PRNGKey(0), 3, 32, 1.0, dtype=jnp.float64)
kx, ky = jax.random.split(jax.random.PRNGKey(9))
xs = jax.random.normal(kx, (48, 3), jnp.float64)
ys = jax.random.normal(ky, (48,), jnp.float64)
res = {}
for name, lrn in (
    ("klms", klms_learner(rff, 0.3)),
    ("krls", krls_learner(rff, lam=0.1, beta=0.99)),
):
    never, _ = lrn.run(None, xs, ys)
    seq = lrn.rebuild(xs, ys, mode="sequential")
    res[f"{name}_seq_bitwise"] = bool(jnp.array_equal(seq.theta, never.theta))
    for cut in (7, 23):
        at_cut, _ = lrn.run(None, xs[:cut], ys[:cut])
        rb = lrn.rebuild(xs[cut:], ys[cut:], state=at_cut, mode="scan",
                         chunk=16)
        res[f"{name}_scan_cut{cut}"] = float(
            jnp.linalg.norm(rb.theta - never.theta)
            / jnp.linalg.norm(never.theta)
        )
print(json.dumps(res))
"""


@pytest.mark.slow
def test_evict_rebuild_f64_drift_shrinks():
    """At f64 the scan rebuild across arbitrary cuts lands within 1e-10
    of the never-evicted state (measured ~1e-13) — the f32 tolerances
    above are working-precision rounding, not algebra error."""
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_ENABLE_X64="1",
    )
    out = subprocess.run(
        [sys.executable, "-c", _F64_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["klms_seq_bitwise"] and res["krls_seq_bitwise"], res
    for k, v in res.items():
        if not k.endswith("bitwise"):
            assert v < 1e-10, res
