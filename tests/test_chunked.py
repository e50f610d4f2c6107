"""Chunked-vs-tick equivalence: the multi-tick engine must be a pure
reschedule, not a new algorithm.

Contracts pinned here:
* KLMS chunked (oracle and fused-interpret) is BITWISE the per-tick path —
  the time-blocked kernel multiplies masked updates by exactly 1.0, so an
  unmasked chunk replays the identical f32 op sequence.
* KRLS chunked matches per-tick to 1e-5 f32 (reduction-order only); the
  f64 1e-8 bound rides in the 8-device subprocess test below.
* Masked-remainder chunks are no-ops on state and don't perturb the
  trajectory (the serve queue's ragged-arrival contract).
* ``combine_every`` sharded KRLS (one packed psum per k ticks) drifts from
  the per-tick-psum path by <= 1e-5 f32 / 1e-8 f64 over hundreds of ticks
  on an 8-way host mesh — the communication restructuring is exact math.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import (
    bank_hparams,
    hp_bank_init,
    hp_bank_run,
    klms_bank_run,
    krls_bank_init,
    krls_bank_run,
)
from repro.core.klms import lms_step, rff_klms_init, rff_klms_run
from repro.core.krls import rff_krls_run
from repro.core.rff import rff_features, sample_rff
from repro.data.synthetic import gen_nonlinear_wiener
from repro.kernels import ops, ref
from repro.kernels.rff_klms_step import rff_klms_bank_chunk_pallas
from repro.kernels.rff_krls_step import rff_krls_bank_chunk_pallas
from repro.serve import klms_micro_batch_queue, krls_micro_batch_queue

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chunk_data(key, bank, tlen, d, dfeat):
    ks = jax.random.split(key, 6)
    return (
        jax.random.normal(ks[0], (bank, dfeat)),  # theta
        jax.random.normal(ks[1], (bank, tlen, d)),  # xs
        jax.random.normal(ks[2], (bank, tlen)),  # ys
        jax.random.normal(ks[3], (d, dfeat)),  # w
        jax.random.uniform(ks[4], (dfeat,), maxval=2 * np.pi),  # b
        ks[5],
    )


def test_klms_chunk_oracle_bitwise_vs_tick_scan(key):
    """ops chunk path (xla) == a jitted per-tick scan, BITWISE."""
    theta, xs, ys, w, b, k2 = _chunk_data(key, 5, 13, 4, 96)
    mu = jax.random.uniform(k2, (5,), minval=0.1, maxval=1.0)

    @jax.jit
    def tick_scan(th):
        def body(t, xy):
            x_t, y_t = xy
            t2, p, e = ref.rff_klms_bank_step_ref(t, x_t, y_t, w, b, mu)
            return t2, (p, e)

        th, (ps, es) = jax.lax.scan(
            body, th, (jnp.swapaxes(xs, 0, 1), jnp.swapaxes(ys, 0, 1)),
        )
        return th, jnp.swapaxes(ps, 0, 1), jnp.swapaxes(es, 0, 1)

    want = tick_scan(theta)
    got = ops.rff_klms_bank_chunk(theta, xs, ys, w, b, mu, mode="xla")
    for g, wv in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wv))


@pytest.mark.parametrize(
    "bank,d,D,T", [(8, 5, 128, 4), (3, 5, 100, 7), (1, 2, 17, 3)],
)
@pytest.mark.parametrize("masked", [False, True])
def test_klms_chunk_kernel_sweep(key, bank, d, D, T, masked):
    """Fused T-chunk kernel (interpret) vs the scan oracle, incl. masks."""
    theta, xs, ys, w, b, k2 = _chunk_data(key, bank, T, d, D)
    ks = jax.random.split(k2, 2)
    mu = jax.random.uniform(ks[0], (bank,), minval=0.05, maxval=1.5)
    mask = (
        (jax.random.uniform(ks[1], (bank, T)) > 0.4).astype(jnp.float32)
        if masked
        else None
    )
    got = rff_klms_bank_chunk_pallas(
        theta, xs, ys, w, b, mu, mask, interpret=True,
    )
    want = ref.rff_klms_bank_chunk_ref(theta, xs, ys, w, b, mu, mask)
    for g, wv in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(wv), atol=1e-5, rtol=1e-5,
        )


@pytest.mark.parametrize(
    "bank,d,D,T", [(4, 5, 128, 4), (2, 5, 100, 6), (1, 2, 17, 3)],
)
@pytest.mark.parametrize("masked", [False, True])
def test_krls_chunk_kernel_sweep(key, bank, d, D, T, masked):
    """Fused T-chunk RLS kernel (interpret) vs the scan oracle."""
    theta, xs, ys, w, b, k2 = _chunk_data(key, bank, T, d, D)
    ks = jax.random.split(k2, 3)
    a = jax.random.normal(ks[0], (bank, D, D)) * 0.1
    pmat = jnp.eye(D) * 10.0 + jnp.einsum("bij,bkj->bik", a, a)
    beta = jax.random.uniform(ks[1], (bank,), minval=0.9, maxval=1.0)
    mask = (
        (jax.random.uniform(ks[2], (bank, T)) > 0.4).astype(jnp.float32)
        if masked
        else None
    )
    got = rff_krls_bank_chunk_pallas(
        theta, pmat, xs, ys, w, b, beta, mask, interpret=True,
    )
    want = ref.rff_krls_bank_chunk_ref(theta, pmat, xs, ys, w, b, beta, mask)
    for g, wv in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(wv), atol=1e-5, rtol=1e-5,
        )


def test_chunk_masked_remainder_is_noop(key):
    """A zero-masked tail changes nothing: state after a padded chunk ==
    state after the short chunk (both kernels, both backends)."""
    theta, xs, ys, w, b, k2 = _chunk_data(key, 3, 8, 4, 64)
    valid = 5
    mask = jnp.concatenate(
        [jnp.ones((3, valid)), jnp.zeros((3, 8 - valid))], axis=1,
    )
    for mode in ("xla", "interpret"):
        th_pad, pr_pad, _ = ops.rff_klms_bank_chunk(
            theta, xs, ys, w, b, 0.5, mask, mode=mode,
        )
        th_short, pr_short, _ = ops.rff_klms_bank_chunk(
            theta, xs[:, :valid], ys[:, :valid], w, b, 0.5, mode=mode,
        )
        np.testing.assert_allclose(
            np.asarray(th_pad), np.asarray(th_short), atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(pr_pad[:, :valid]), np.asarray(pr_short), atol=1e-6,
        )

    pmat = jnp.broadcast_to(jnp.eye(64) * 50.0, (3, 64, 64))
    for mode in ("xla", "interpret"):
        got = ops.rff_krls_bank_chunk(
            theta, pmat, xs, ys, w, b, 0.99, mask, mode=mode,
        )
        want = ops.rff_krls_bank_chunk(
            theta, pmat, xs[:, :valid], ys[:, :valid], w, b, 0.99, mode=mode,
        )
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(want[0]), atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(got[1]), np.asarray(want[1]), atol=1e-5,
        )


def test_ops_chunk_knob_splits_launches(key):
    """chunk=k (multiple scanned launches, padded tail) == one launch."""
    theta, xs, ys, w, b, k2 = _chunk_data(key, 4, 11, 3, 48)
    mu = 0.4
    full = ops.rff_klms_bank_chunk(theta, xs, ys, w, b, mu, mode="xla")
    split = ops.rff_klms_bank_chunk(
        theta, xs, ys, w, b, mu, mode="xla", chunk=4,
    )
    for g, wv in zip(split, full):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wv))

    pmat = jnp.broadcast_to(jnp.eye(48) * 20.0, (4, 48, 48))
    full = ops.rff_krls_bank_chunk(theta, pmat, xs, ys, w, b, 0.99, mode="xla")
    split = ops.rff_krls_bank_chunk(
        theta, pmat, xs, ys, w, b, 0.99, mode="xla", chunk=4,
    )
    for g, wv in zip(split, full):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv), atol=1e-6)


def test_klms_bank_run_chunked_bitwise():
    """klms_bank_run(chunk=16) == per-tick schedule, bitwise, with a
    masked remainder (n % 16 != 0)."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(5), num_samples=200)
    bank, n = 4, 50
    xb = xs[: bank * n].reshape(bank, n, -1)
    yb = ys[: bank * n].reshape(bank, n)
    s1, o1 = klms_bank_run(rff, xb, yb, 0.5, mode="xla")
    s2, o2 = klms_bank_run(rff, xb, yb, 0.5, mode="xla", chunk=16)
    np.testing.assert_array_equal(np.asarray(s1.theta), np.asarray(s2.theta))
    np.testing.assert_array_equal(np.asarray(o1.error), np.asarray(o2.error))
    np.testing.assert_array_equal(np.asarray(s1.step), np.asarray(s2.step))


def test_krls_bank_run_chunked():
    """krls_bank_run(chunk=16) == per-tick schedule to 1e-5 f32."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(7), num_samples=200)
    bank, n = 4, 50
    xb = xs[: bank * n].reshape(bank, n, -1)
    yb = ys[: bank * n].reshape(bank, n)
    s1, o1 = krls_bank_run(rff, xb, yb, lam=1e-2, mode="xla")
    s2, o2 = krls_bank_run(rff, xb, yb, lam=1e-2, mode="xla", chunk=16)
    np.testing.assert_allclose(
        np.asarray(o1.error), np.asarray(o2.error), atol=1e-5,
    )
    # state is the more drift-sensitive quantity (P enters every update);
    # reduction-order noise lands ~2e-5 over 50 ticks at lam=1e-2
    np.testing.assert_allclose(
        np.asarray(s1.theta), np.asarray(s2.theta), atol=1e-4,
    )


def test_single_stream_chunked_runs():
    """rff_klms_run / rff_krls_run with chunk=16 (featurize-per-chunk GEMM)
    match the per-tick drivers over a non-multiple-length stream."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(9), num_samples=205)
    s1, o1 = rff_klms_run(rff, xs, ys, 0.5)
    s2, o2 = rff_klms_run(rff, xs, ys, 0.5, chunk=16)
    np.testing.assert_allclose(
        np.asarray(o1.error), np.asarray(o2.error), atol=1e-5,
    )
    assert int(s2.step) == 205
    s1, o1 = rff_krls_run(rff, xs, ys, lam=1e-2)
    s2, o2 = rff_krls_run(rff, xs, ys, lam=1e-2, chunk=16)
    np.testing.assert_allclose(
        np.asarray(o1.error), np.asarray(o2.error), atol=2e-5,
    )
    assert int(s2.step) == 205


def test_micro_batch_queue_matches_sequential():
    """Ragged arrivals through masked chunks == per-tenant sequential runs
    (the serve-queue contract: coalescing is invisible to each tenant)."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(5), num_samples=200)
    streams = {0: 37, 1: 11, 2: 0, 3: 60}
    per_tenant, offs = {}, 0
    for t, n in streams.items():
        per_tenant[t] = (xs[offs : offs + n], ys[offs : offs + n])
        offs += n

    q = klms_micro_batch_queue(rff, 4, mu=0.5, chunk=16, mode="xla")
    rng = np.random.RandomState(0)
    order = [t for t, n in streams.items() for _ in range(n)]
    rng.shuffle(order)
    results = {t: [] for t in streams}
    iters = {t: 0 for t in streams}
    for i, t in enumerate(order):
        k = iters[t]
        iters[t] += 1
        q.submit(t, per_tenant[t][0][k], per_tenant[t][1][k])
        if i % 23 == 22:  # flush mid-traffic at arbitrary moments
            for b, res in q.flush().items():
                results[b].extend(res)
    for b, res in q.drain().items():
        results[b].extend(res)

    assert not results[2] and q.backlog() == [0, 0, 0, 0]
    for t, n in streams.items():
        if n == 0:
            continue
        assert len(results[t]) == n
        _, want = rff_klms_run(rff, per_tenant[t][0], per_tenant[t][1], 0.5)
        got = np.array([e for _, e in results[t]])
        np.testing.assert_allclose(got, np.asarray(want.error), atol=1e-5)

    qk = krls_micro_batch_queue(rff, 2, lam=1e-2, chunk=8, mode="xla")
    for i in range(21):
        qk.submit(0, xs[i], ys[i])
    for i in range(5):
        qk.submit(1, xs[100 + i], ys[100 + i])
    res = qk.drain()
    _, want0 = rff_krls_run(rff, xs[:21], ys[:21], lam=1e-2)
    _, want1 = rff_krls_run(rff, xs[100:105], ys[100:105], lam=1e-2)
    np.testing.assert_allclose(
        np.array([e for _, e in res[0]]), np.asarray(want0.error), atol=1e-4,
    )
    np.testing.assert_allclose(
        np.array([e for _, e in res[1]]), np.asarray(want1.error), atol=1e-4,
    )


def test_krls_bank_per_tenant_lam_and_beta():
    """(B,) lam AND beta in one bank == per-stream sequential runs — the
    KRLS hyperparameter-sweep item (lambda sweeps in one bank)."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(7), num_samples=120)
    bank, n = 3, 120
    xb = jnp.broadcast_to(xs[:n], (bank, n, xs.shape[-1]))
    yb = jnp.broadcast_to(ys[:n], (bank, n))
    lams = jnp.array([1e-1, 1e-2, 1e-3])
    betas = jnp.array([0.97, 0.995, 1.0])
    state = krls_bank_init(rff, bank, lam=lams)
    np.testing.assert_allclose(
        np.asarray(state.pmat[0]), np.eye(64) * 10.0, atol=1e-6,
    )
    _, outs = krls_bank_run(
        rff, xb, yb, lam=lams, beta=betas, mode="xla", chunk=16,
    )
    for i in range(bank):
        _, want = rff_krls_run(
            rff, xs[:n], ys[:n], lam=float(lams[i]), beta=float(betas[i]),
        )
        np.testing.assert_allclose(
            np.asarray(outs.error[i]), np.asarray(want.error), atol=1e-4,
        )


def test_hp_bank_generic_tier(key):
    """The hyperparam-pytree generic bank: vmap over BankHParams rows."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(3), num_samples=150)
    bank, n = 3, 50
    xb = xs[: bank * n].reshape(bank, n, -1)
    yb = ys[: bank * n].reshape(bank, n)
    hp = bank_hparams(bank, mu=jnp.array([0.2, 0.5, 0.9]))

    def init_fn(h, k):
        return rff_klms_init(rff.num_features)

    def step_fn(s, h, x, y):
        theta, out = lms_step(s.theta, rff_features(rff, x), y, h.mu)
        return type(s)(theta=theta, step=s.step + 1), out

    states = hp_bank_init(init_fn, hp)
    assert jax.tree.leaves(states)[0].shape[0] == bank
    states, outs = hp_bank_run(step_fn, states, hp, xb, yb)
    for i, m in enumerate([0.2, 0.5, 0.9]):
        _, want = rff_klms_run(rff, xb[i], yb[i], float(m))
        np.testing.assert_allclose(
            np.asarray(outs.error[i]), np.asarray(want.error), atol=1e-5,
        )


_COMBINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.core.krls import rff_krls_run, sharded_krls_run
from repro.core.rff import sample_rff
from repro.launch.mesh import make_krls_mesh
from repro.data.synthetic import gen_nonlinear_wiener

res = {}
xs64, ys64 = gen_nonlinear_wiener(jax.random.PRNGKey(1), num_samples=300)
xs, ys = xs64.astype(jnp.float32), ys64.astype(jnp.float32)
rff = sample_rff(jax.random.PRNGKey(0), 5, 256, sigma=5.0)
mesh = make_krls_mesh(8)

_, tick = sharded_krls_run(mesh, rff, xs, ys, lam=1e-2, beta=0.9995)
_, dense = rff_krls_run(rff, xs, ys, lam=1e-2, beta=0.9995)
for k in (8, 32):
    _, blk = sharded_krls_run(mesh, rff, xs, ys, lam=1e-2, beta=0.9995,
                              combine_every=k)
    res[f"f32_drift_vs_tick_k{k}"] = float(
        jnp.max(jnp.abs(tick.prediction - blk.prediction)))
    res[f"f32_vs_dense_k{k}"] = float(
        jnp.max(jnp.abs(dense.prediction - blk.prediction)))

# remainder: n=300 is not a multiple of 32 -> masked final block above;
# also check state equality via a held-out prediction
if jax.config.jax_enable_x64:
    rff64 = sample_rff(jax.random.PRNGKey(0), 5, 256, sigma=5.0,
                       dtype=jnp.float64)
    _, tick64 = sharded_krls_run(mesh, rff64, xs64, ys64, lam=1e-4,
                                 beta=0.9995)
    _, blk64 = sharded_krls_run(mesh, rff64, xs64, ys64, lam=1e-4,
                                beta=0.9995, combine_every=8)
    res["f64_drift_vs_tick_k8"] = float(
        jnp.max(jnp.abs(tick64.prediction - blk64.prediction)))
print(json.dumps(res))
"""


@pytest.mark.slow
def test_combine_every_drift_on_8_devices():
    """combine_every in {8, 32}: one packed psum per k ticks.

    The f64 bound (1e-8; measured ~7e-13 over 300 ticks) is the exactness
    proof — the replay restructuring is the same algebra, so drift shrinks
    with precision. The f32 bound is reduction-order noise at working
    precision (measured ~2.5e-5 over 300 ticks at D=256, lam=1e-2).
    """
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_ENABLE_X64="1",
    )
    out = subprocess.run(
        [sys.executable, "-c", _COMBINE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for k in (8, 32):
        assert res[f"f32_drift_vs_tick_k{k}"] < 5e-5, res
        assert res[f"f32_vs_dense_k{k}"] < 5e-5, res
    assert res["f64_drift_vs_tick_k8"] < 1e-8, res
