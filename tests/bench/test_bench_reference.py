"""The benchmark's plain float64 references agree with the library's own
recursions (``core.klms.rff_klms_run``, ``core.krls.rff_krls_run``) run in
float64 on the CPU, tenant by tenant, reads included."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.configs import klms_fleet, krls_fleet
from bench.harness import RunView
from repro.core.klms import rff_klms_run
from repro.core.krls import rff_krls_run
from repro.core.rff import RFF

D_IN, D_FEAT, TENANTS = 5, 48, 3


def _view(cfg, seed=3, writes=40, reads=12):
    rng = np.random.default_rng(seed)
    wk = rng.integers(TENANTS, size=writes)
    rk = rng.integers(TENANTS, size=reads)
    counts = np.bincount(wk, minlength=TENANTS)
    return RunView(
        cfg=cfg,
        seed=seed,
        w=(rng.standard_normal((D_IN, D_FEAT)) / 5.0).astype(np.float32),
        b=rng.uniform(0, 2 * np.pi, D_FEAT).astype(np.float32),
        write_key=wk,
        write_x=rng.standard_normal((writes, D_IN)).astype(np.float32),
        write_y=rng.standard_normal(writes).astype(np.float32),
        read_key=rk,
        read_x=rng.standard_normal((reads, D_IN)).astype(np.float32),
        read_pub=np.array([rng.integers(counts[k] + 1) for k in rk]),
    )


def _library(view, run_fn, t):
    """The library's float64 run of tenant ``t``: final state, priors, and
    the value of each of its reads after its published prefix."""
    mine = view.write_key == t
    rff = RFF(omega=jnp.asarray(view.w, jnp.float64),
              bias=jnp.asarray(view.b, jnp.float64))
    xs = jnp.asarray(view.write_x[mine], jnp.float64)
    ys = jnp.asarray(view.write_y[mine], jnp.float64)
    state, out = run_fn(rff, xs, ys)
    reads = []
    for x, n in zip(view.read_x[view.read_key == t], view.read_pub[view.read_key == t]):
        theta = run_fn(rff, xs[:n], ys[:n])[0].theta
        z = np.sqrt(2.0 / D_FEAT) * np.cos(np.asarray(x, np.float64) @ view.w + view.b)
        reads.append(float(np.asarray(theta) @ z))
    return state, np.asarray(out.prediction), np.asarray(reads)


def _check(view, ref_mod, run_fn, leaves):
    ids = np.arange(TENANTS)
    ref = ref_mod.replay(view, ids, "f64")
    with jax.enable_x64(True):
        lib = [_library(view, run_fn, t) for t in ids]
    wk = view.write_key[np.isin(view.write_key, ids)]
    rk = view.read_key[np.isin(view.read_key, ids)]
    for t in ids:
        state, priors, reads = lib[t]
        for leaf in leaves:
            np.testing.assert_allclose(
                ref[leaf][t], np.asarray(getattr(state, leaf)), rtol=1e-9, atol=1e-9
            )
        np.testing.assert_allclose(ref["prior"][wk == t], priors, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(ref["read"][rk == t], reads, rtol=1e-9, atol=1e-9)


def test_klms_reference_matches_core_klms():
    view = _view({"tenants": TENANTS, "hp": {"mu": 0.5}})
    _check(view, klms_fleet,
           lambda rff, xs, ys: rff_klms_run(rff, xs, ys, 0.5), ["theta"])


def test_krls_reference_matches_core_krls():
    view = _view({"tenants": TENANTS, "hp": {"lam": 1e-2, "beta": 0.999}})
    _check(view, krls_fleet,
           lambda rff, xs, ys: rff_krls_run(rff, xs, ys, lam=1e-2, beta=0.999),
           ["theta", "pmat"])


def test_krls_sample_holds_the_hottest_tenant():
    view = _view({"tenants": TENANTS, "hp": {}, "check_tenants": 2}, writes=60)
    ids = krls_fleet.tenants(view)
    hot = np.argmax(np.bincount(view.write_key, minlength=TENANTS))
    assert len(ids) == 2 and hot in ids
    assert np.array_equal(ids, krls_fleet.tenants(view))  # drawn from the seed
