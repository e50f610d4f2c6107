"""A paged KRLS fleet: more tenants than bank slots, evicted tenants rebuilt
from their replay logs.

* The fleet served through ``make_server(policy="lru")`` against a plain
  float64 sequential KRLS per tenant over its whole history: final theta,
  P and step of every tenant, and every prior a flush returned.
* Padded replays (``core.scan.pad_log``) against unpadded ones, for every
  log length up to the ring's capacity, in ``"scan"`` and ``"sequential"``
  modes, for KRLS and KLMS; the rebuilt ``step`` counts real ticks only.
* A policy server compiles every rebuild and the eviction's row write
  while ``make_server`` runs: readmissions at many log lengths and the
  evictions before them compile nothing afterwards.
* The lifecycle's spans and counters: ``policy.admit`` and ``serve.evict``
  nest under ``serve.submit``, ``serve.install`` carries ``ticks`` and
  ``bucket``, and ``replay.ticks`` + ``replay.pad_ticks`` is the buckets'
  sum.
"""
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import tenant_row
from repro.core.rff import sample_rff
from repro.core.scan import (
    pad_log,
    replay_bucket,
    replay_buckets,
    replay_klms,
    replay_krls,
)
from repro.obs.trace import Tracer
from repro.serve import make_server

D_IN, D_FEAT = 5, 32
SLOTS, TENANTS = 8, 80
LAM, BETA = 1e-4, 0.9995
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_RFF = sample_rff(jax.random.PRNGKey(7), D_IN, D_FEAT, 5.0)


def _keys(rng, n, every=40, by=3):
    """Zipf 0.99 tenant keys whose hot set moves ``by`` ranks every
    ``every`` requests."""
    pmf = np.arange(1, TENANTS + 1, dtype=np.float64) ** -0.99
    rank = rng.choice(TENANTS, size=n, p=pmf / pmf.sum())
    rank = (rank + by * (np.arange(n) // every)) % TENANTS
    return rng.permutation(TENANTS)[rank]


def _stream(seed, n):
    rng = np.random.default_rng(seed)
    keys = _keys(rng, n)
    xs = rng.standard_normal((n, D_IN)).astype(np.float32)
    ys = (np.sin(xs.sum(1)) + 0.05 * rng.standard_normal(n)).astype(np.float32)
    return keys, xs, ys


def _server(size_watermark=16, rebuild_mode="sequential", **kw):
    """A paged KRLS server; ``"sequential"`` rebuilds, as the chip cell's."""
    return make_server(
        "krls", feature_map=_RFF, bank=SLOTS, chunk=16,
        size_watermark=size_watermark, lam=LAM, beta=BETA, policy="lru",
        rebuild_mode=rebuild_mode, **kw,
    )


def _krls_f64(xs, ys):
    """Plain float64 EW-RLS from theta 0, P = I/lam: the final theta and P
    and the prior prediction of every write."""
    w = np.asarray(_RFF.omega, np.float64)
    b = np.asarray(_RFF.bias, np.float64)
    z = np.sqrt(2.0 / D_FEAT) * np.cos(np.asarray(xs, np.float64) @ w + b)
    theta, p = np.zeros(D_FEAT), np.eye(D_FEAT) / LAM
    priors = []
    for zn, yn in zip(z, np.asarray(ys, np.float64)):
        pz = p @ zn
        g = pz / (BETA + zn @ pz)
        priors.append(theta @ zn)
        theta = theta + g * (yn - priors[-1])
        p = (p - np.outer(g, pz)) / BETA
    return theta, p, np.asarray(priors)


def _rel(got, want):
    """Norm-relative gap (0 when both are 0)."""
    num = np.linalg.norm(np.asarray(got, np.float64) - want)
    den = np.linalg.norm(want)
    return float(num / den) if den else float(num) * np.inf if num else 0.0


@pytest.mark.parametrize("rebuild_mode", ["scan", "sequential"])
def test_paged_fleet_matches_float64_sequential_reference(rebuild_mode):
    srv = _server(size_watermark=4, log_capacity=512,
                  rebuild_mode=rebuild_mode)
    keys, xs, ys = _stream(11, 1500)
    written = defaultdict(int)  # writes submitted so far, per tenant
    flushed = []  # (tenant, index of the write, prior)
    flush = srv.queue.flush

    def recorded():
        # A flush happens before any further admission, so the residents
        # map each slot to its tenant. Backlogs never pass the chunk (size
        # watermark 4, chunk 16): a slot's outputs are its tenant's last
        # writes, oldest first.
        owner = {s: t for t, s in srv.policy.resident.items()}
        res = flush()
        for slot, outs in res.items():
            t = owner[slot]
            for j, (pred, _) in enumerate(outs):
                flushed.append((t, written[t] - len(outs) + j, float(pred)))
        return res

    srv.queue.flush = recorded
    for k, x, y in zip(keys.tolist(), xs, ys):
        written[k] += 1
        srv.submit(k, x, y)
    srv.drain()
    assert srv.metrics.count("evictions") > 100
    assert srv.metrics.count("readmissions") > 100

    ref = {}
    for t in written:
        mine = keys == t
        ref[t] = _krls_f64(xs[mine], ys[mine])
    gaps = defaultdict(float)
    for t in written:
        assert srv.log.complete(t)
        srv.readmit(t)
        row = tenant_row(srv.queue.state, srv.policy.lookup(t))
        theta, p, _ = ref[t]
        assert int(row.step) == written[t]  # every write trained exactly once
        gaps["theta"] = max(gaps["theta"], _rel(row.theta, theta))
        gaps["pmat"] = max(gaps["pmat"], _rel(row.pmat, p))
    by_tenant = defaultdict(list)
    for t, i, pred in flushed:
        by_tenant[t].append((i, pred))
    for t, pairs in by_tenant.items():
        idx, pred = np.asarray(pairs).T
        gaps["prior"] = max(gaps["prior"],
                            _rel(pred, ref[t][2][idx.astype(int)]))
    assert len(flushed) > 500
    # float32 against float64: at lam=1e-4 P's eigenvalues span ~1e4, so
    # rounding grows through the recursion. The scan rebuild's one solve of
    # the information form adds the most: its worst tenant reads ~2.2e-3
    # for theta, P and priors on this stream; the sequential rebuild (the
    # training path's own recursion) reads 3.4e-4, 8.3e-5 and 1.8e-4.
    # 1e-2 (the krls_fleet cell's P and prior limits) leaves room for both
    # and sits far below what a lost or doubled write gives (~1).
    assert gaps["theta"] < 1e-2, gaps
    assert gaps["pmat"] < 1e-2, gaps
    assert gaps["prior"] < 1e-2, gaps


@pytest.mark.parametrize("mode", ["scan", "sequential"])
@pytest.mark.parametrize("family", ["krls", "klms"])
def test_padded_replay_equals_unpadded(family, mode):
    cap = 12  # not a power of two: the last bucket is the ring itself
    rng = np.random.default_rng(3)
    xs_all = rng.standard_normal((cap, D_IN)).astype(np.float32)
    ys_all = rng.standard_normal(cap).astype(np.float32)

    @jax.jit
    def replay(xs, ys, mask=None):
        if family == "krls":
            return replay_krls(_RFF, xs, ys, lam=0.1, beta=0.99, mode=mode,
                               mask=mask)
        return replay_klms(_RFF, xs, ys, 0.3, mode=mode, mask=mask)

    for n in range(1, cap + 1):
        xs, ys = xs_all[:n], ys_all[:n]
        bucket = replay_bucket(n, cap)
        assert bucket in replay_buckets(cap) and bucket >= n
        want = replay(jnp.asarray(xs), jnp.asarray(ys))
        got = replay(*map(jnp.asarray, pad_log(xs, ys, bucket)))
        assert int(got.step) == int(want.step) == n
        for leaf in ("theta", "pmat"):
            if not hasattr(want, leaf):
                continue
            a, b = getattr(got, leaf), getattr(want, leaf)
            if mode == "sequential":
                # A padded tick is gated off; the real ticks run the same
                # per-tick program as the unpadded scan.
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:
                # The associative scan groups the real ticks differently
                # at another length; identity elements add exact zeros,
                # so the gap is reassociation rounding.
                assert _rel(a, np.asarray(b, np.float64)) < 2e-5, (n, leaf)


def test_rebuilt_step_counts_real_ticks_only():
    rng = np.random.default_rng(5)
    xs = rng.standard_normal((5, D_IN)).astype(np.float32)
    ys = rng.standard_normal(5).astype(np.float32)
    xs, ys, mask = map(jnp.asarray, pad_log(xs, ys, 8))
    krls = jax.jit(lambda state, mode: replay_krls(
        _RFF, xs, ys, lam=0.1, beta=0.99, mode=mode, state=state, mask=mask,
    ), static_argnames="mode")
    klms = jax.jit(lambda state, mode: replay_klms(
        _RFF, xs, ys, 0.3, mode=mode, state=state, mask=mask,
    ), static_argnames="mode")
    for mode in ("scan", "sequential"):
        for replay in (krls, klms):
            fresh = replay(None, mode)
            assert int(fresh.step) == 5
            assert int(replay(fresh, mode).step) == 10
    srv = _server(log_capacity=64)
    keys, xs, ys = _stream(2, 300)
    for k, x, y in zip(keys.tolist(), xs, ys):
        srv.submit(k, x, y)
    srv.drain()
    for t, slot in srv.policy.resident.items():
        assert int(srv.queue.state.step[slot]) == srv.log.size(t)


def test_policy_server_compiles_nothing_after_make_server():
    tracer = Tracer(capacity=1 << 16)
    srv = _server(log_capacity=64, trace=tracer)
    keys, xs, ys = _stream(4, 600)
    srv.submit(int(keys[0]), xs[0], ys[0])
    srv.drain()  # the flush program compiles at its first launch
    lowerings = []

    def on(name, _secs, **_kw):
        if name == LOWERING:
            lowerings.append(name)

    evictions = srv.metrics.count("evictions")
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        for k, x, y in zip(keys[1:].tolist(), xs[1:], ys[1:]):
            srv.submit(k, x, y)
        srv.drain()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    lengths = {s.attrs["ticks"] for s in tracer.spans()
               if s.name == "serve.install"}
    assert len(lengths) >= 10, sorted(lengths)
    # Evictions ran in the loop, so their row write is covered too.
    assert srv.metrics.count("evictions") > evictions
    assert not lowerings


def _traced_run(seed=9, n=400):
    tracer = Tracer(capacity=1 << 16)
    srv = _server(log_capacity=64, trace=tracer)
    keys, xs, ys = _stream(seed, n)
    for k, x, y in zip(keys.tolist(), xs, ys):
        srv.submit(k, x, y)
    srv.drain()
    spans = [s for s in tracer.spans() if s.kind == "span"]
    return srv, spans, {s.span_id: s for s in spans}


def test_admit_and_evict_spans_nest_under_submit():
    srv, spans, by_id = _traced_run()
    admits = [s for s in spans if s.name == "policy.admit"]
    evicts = [s for s in spans if s.name == "serve.evict"]
    assert admits and evicts
    assert srv.metrics.count("bank.misses") == len(admits)
    assert srv.metrics.count("evictions") == len(evicts)
    for s in admits + evicts:
        assert by_id[s.parent_id].name == "serve.submit"
    actions = [s.attrs["action"] for s in admits]
    assert actions.count("evict") == len(evicts) and "admit" in actions
    for s in admits:
        assert (s.attrs["victim"] is None) == (s.attrs["action"] != "evict")
    # An operator readmit releases its victim under its own span.
    out = next(t for t in range(TENANTS) if srv.policy.lookup(t) is None
               and srv.log.size(t))
    srv.readmit(out)
    assert srv.metrics.count("evictions") == len(evicts) + 1


def test_install_spans_carry_ticks_and_bucket_and_counters_add_up():
    srv, spans, by_id = _traced_run()
    installs = [s for s in spans if s.name == "serve.install"]
    assert len(installs) == srv.metrics.count("readmissions") > 0
    for s in installs:
        assert s.attrs["bucket"] == replay_bucket(s.attrs["ticks"], 64)
        assert by_id[s.parent_id].name == "serve.submit"
    ticks = srv.metrics.count("replay.ticks")
    pad = srv.metrics.count("replay.pad_ticks")
    assert ticks == sum(s.attrs["ticks"] for s in installs)
    assert ticks + pad == sum(s.attrs["bucket"] for s in installs)
    assert pad > 0
