"""Sharded-KRLS tests: dense-vs-sharded equivalence on a forced 8-device
host mesh (subprocess — the device count locks at backend init, same
pattern as tests/test_distributed.py) and fused RLS bank kernel parity
against its pure-JAX oracle in interpret mode.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krls import rff_krls_run, rff_krls_step
from repro.core.bank import krls_bank_init, krls_bank_run
from repro.core.rff import sample_rff
from repro.data.synthetic import gen_nonlinear_wiener
from repro.kernels import ops, ref
from repro.kernels.rff_krls_step import rff_krls_bank_step_pallas
from repro.launch.sharding import krls_shard_bytes
from repro.serve import reset_krls_tenants

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.core.krls import rff_krls_run, sharded_krls_run
from repro.core.learner import krls_learner, sharded_krls_learner
from repro.launch.mesh import make_krls_mesh
from repro.data.synthetic import gen_nonlinear_wiener
from repro.core.rff import sample_rff

res = {}
xs64, ys64 = gen_nonlinear_wiener(jax.random.PRNGKey(1), num_samples=600)
# under JAX_ENABLE_X64 the generator emits f64; the f32 sections cast down
xs, ys = xs64.astype(jnp.float32), ys64.astype(jnp.float32)
rff = sample_rff(jax.random.PRNGKey(0), 5, 256, sigma=5.0)

# f32, well-conditioned regularizer, 600 ticks, every shard count that
# divides the 8 host devices.
for n in (2, 4, 8):
    mesh = make_krls_mesh(n)
    _, dense = rff_krls_run(rff, xs, ys, lam=1e-2, beta=0.9995)
    _, shard = sharded_krls_run(mesh, rff, xs, ys, lam=1e-2, beta=0.9995)
    res[f"f32_pred_maxdiff_n{n}"] = float(
        jnp.max(jnp.abs(dense.prediction - shard.prediction)))

# f64 at the paper's hyperparams (lam=1e-4, beta=0.9995): the sharded
# restructuring is exact math, so the gap is pure reduction-order noise.
if jax.config.jax_enable_x64:
    mesh = make_krls_mesh(8)
    rff64 = sample_rff(jax.random.PRNGKey(0), 5, 256, sigma=5.0,
                       dtype=jnp.float64)
    _, dense = rff_krls_run(rff64, xs64, ys64, lam=1e-4, beta=0.9995)
    _, shard = sharded_krls_run(mesh, rff64, xs64, ys64, lam=1e-4,
                                beta=0.9995)
    res["f64_pred_maxdiff"] = float(
        jnp.max(jnp.abs(dense.prediction - shard.prediction)))

# the OnlineLearner adapter: per-tick step fn + predict fn
mesh = make_krls_mesh(8)
ls = sharded_krls_learner(mesh, rff, lam=1e-2, beta=0.9995)
ld = krls_learner(rff, lam=1e-2, beta=0.9995)
ss, sd = ls.init(), ld.init()
dmax = 0.0
for i in range(32):
    ss, outs = ls.step(ss, xs[i], ys[i])
    sd, outd = ld.step(sd, xs[i], ys[i])
    dmax = max(dmax, float(jnp.abs(outs.prediction - outd.prediction)))
res["adapter_step_maxdiff"] = dmax
res["adapter_predict_diff"] = float(
    jnp.abs(ls.predict(ss, xs[40]) - ld.predict(sd, xs[40])))
res["theta_is_sharded"] = len(ss.theta.sharding.device_set) == 8
res["pmat_is_sharded"] = len(ss.pmat.sharding.device_set) == 8
print(json.dumps(res))
"""


@pytest.mark.slow
def test_sharded_krls_matches_dense_on_8_devices():
    """Acceptance: sharded == dense to 1e-5 over >=500 ticks, 8-way mesh."""
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_ENABLE_X64="1",
    )
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for n in (2, 4, 8):
        assert res[f"f32_pred_maxdiff_n{n}"] < 1e-5, res
    assert res["f64_pred_maxdiff"] < 1e-8, res
    assert res["adapter_step_maxdiff"] < 1e-5, res
    assert res["adapter_predict_diff"] < 1e-4, res
    assert res["theta_is_sharded"] and res["pmat_is_sharded"], res


def test_krls_shard_bytes_memory_model():
    """Per-shard P block is the dense bytes / n_shards; D must divide."""
    m = krls_shard_bytes(4096, 8, input_dim=16)
    assert m["p_block_bytes"] == 4096 * 512 * 4
    assert m["dense_p_bytes"] == 8 * m["p_block_bytes"]
    assert m["tick_payload_bytes"] == (2 * 4096 + 1) * 4
    with pytest.raises(ValueError):
        krls_shard_bytes(100, 8)


@pytest.mark.parametrize("bank,d,D", [(4, 5, 128), (3, 5, 100), (1, 2, 17)])
@pytest.mark.parametrize("per_tenant_beta", [False, True])
def test_rff_krls_step_kernel_sweep(key, bank, d, D, per_tenant_beta):
    """Fused featurize+predict+downdate step vs the two-pass oracle."""
    ks = jax.random.split(key, 7)
    theta = jax.random.normal(ks[0], (bank, D))
    a = jax.random.normal(ks[1], (bank, D, D)) * 0.1
    pmat = jnp.eye(D) * 10.0 + jnp.einsum("bij,bkj->bik", a, a)
    x = jax.random.normal(ks[2], (bank, d))
    y = jax.random.normal(ks[3], (bank,))
    w = jax.random.normal(ks[4], (d, D))
    b = jax.random.uniform(ks[5], (D,), maxval=2 * np.pi)
    if per_tenant_beta:
        beta = jax.random.uniform(ks[6], (bank,), minval=0.9, maxval=1.0)
    else:
        beta = jnp.asarray(0.9995)
    got = rff_krls_bank_step_pallas(
        theta,
        pmat,
        x,
        y,
        w,
        b,
        beta,
        interpret=True,
    )
    want = ref.rff_krls_bank_step_ref(theta, pmat, x, y, w, b, beta)
    for g, expect in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g),
            np.asarray(expect),
            atol=1e-5,
            rtol=1e-5,
        )


def test_rff_krls_step_ops_dispatch(key):
    """mode='interpret' (Pallas) and mode='xla' (oracle) agree through ops."""
    ks = jax.random.split(key, 4)
    bank, d, D = 6, 4, 96
    theta = jax.random.normal(ks[0], (bank, D))
    pmat = jnp.broadcast_to(jnp.eye(D) * 50.0, (bank, D, D))
    x = jax.random.normal(ks[1], (bank, d))
    y = jax.random.normal(ks[2], (bank,))
    w = jax.random.normal(ks[3], (d, D))
    b = jnp.zeros((D,))
    got = ops.rff_krls_bank_step(theta, pmat, x, y, w, b, 0.99, mode="interpret")
    want = ops.rff_krls_bank_step(theta, pmat, x, y, w, b, 0.99, mode="xla")
    for g, expect in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(expect), atol=1e-5)


def test_fused_krls_bank_matches_sequential():
    """Fused-step KRLS bank == B sequential rff_krls_run streams."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 100, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(5), num_samples=400)
    bank, n = 4, 100
    xb = xs[: bank * n].reshape(bank, n, -1)
    yb = ys[: bank * n].reshape(bank, n)
    run = jax.jit(
        lambda: krls_bank_run(rff, xb, yb, lam=1e-2, beta=0.9995, mode="xla")
    )
    _, outs = run()
    for i in range(bank):
        _, want = rff_krls_run(rff, xb[i], yb[i], lam=1e-2, beta=0.9995)
        np.testing.assert_allclose(
            np.asarray(outs.error[i]),
            np.asarray(want.error),
            atol=1e-4,
        )


def test_fused_krls_bank_per_tenant_beta():
    """(B,) beta vector == per-stream sequential runs with scalar betas."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(7), num_samples=120)
    bank, n = 3, 120
    xb = jnp.broadcast_to(xs[:n], (bank, n, xs.shape[-1]))
    yb = jnp.broadcast_to(ys[:n], (bank, n))
    betas = jnp.array([0.97, 0.99, 1.0])
    _, outs = krls_bank_run(rff, xb, yb, lam=1e-2, beta=betas, mode="xla")
    for i in range(bank):
        _, want = rff_krls_run(rff, xs[:n], ys[:n], lam=1e-2, beta=float(betas[i]))
        np.testing.assert_allclose(
            np.asarray(outs.error[i]),
            np.asarray(want.error),
            atol=1e-4,
        )


def test_krls_bank_vs_vmapped_dense_step(key):
    """One fused tick == vmapped core rls_step over the bank."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    bank = 5
    state = krls_bank_init(rff, bank, lam=1e-2)
    x = jax.random.normal(key, (bank, 5))
    y = jax.random.normal(jax.random.PRNGKey(3), (bank,))
    got = ops.rff_krls_bank_step(
        state.theta,
        state.pmat,
        x,
        y,
        rff.omega,
        rff.bias,
        0.9995,
        mode="xla",
    )
    vstep = jax.vmap(lambda s, xx, yy: rff_krls_step(s, (xx, yy), rff, 0.9995))
    want_state, want_out = vstep(state, x, y)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want_state.theta), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(got[1]), np.asarray(want_state.pmat), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(got[2]), np.asarray(want_out.prediction), atol=1e-5
    )


def test_reset_krls_tenants():
    rff = sample_rff(jax.random.PRNGKey(0), 5, 32, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(jax.random.PRNGKey(9), num_samples=64)
    xb = xs[:64].reshape(4, 16, -1)
    yb = ys[:64].reshape(4, 16)
    state, _ = krls_bank_run(rff, xb, yb, lam=1e-2, mode="xla")
    state = reset_krls_tenants(state, jnp.array([1, 3]), lam=1e-2)
    assert float(jnp.max(jnp.abs(state.theta[1]))) == 0.0
    np.testing.assert_allclose(
        np.asarray(state.pmat[3]), np.eye(32) * 100.0, atol=1e-6
    )
    assert int(state.step[1]) == 0
    assert float(jnp.max(jnp.abs(state.theta[0]))) > 0.0
