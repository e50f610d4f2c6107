#!/usr/bin/env python3
"""Bring-up smoke run of the tenant-fleet server on TPU.

One chip (the default) drives the main path through its public entry
point, ``repro.serve.make_server``, at the paper's widths:

* KLMS fleet: 1024 slots and 1024 tenants, paper §5 example 1 (D=1000,
  sigma=5, mu=1, d=5), chunk 16, 32 arrivals per tenant; then a Q=64
  ``predict_block`` over the bank and ``predict(tenant, xs)`` reads.
* KRLS fleet: 256 slots at D=1000, lam=1e-4, beta=0.9995 (paper §6), 16
  arrivals per tenant (P is about 1 GiB on the device), then the same reads.

Each phase runs the same streams through ``core.bank.klms_bank_run`` /
``krls_bank_run`` with ``mode="xla"`` (the ``kernels/ref.py`` oracles, at
f32 matmul precision) and checks the reads against the XLA predict oracle
on the served replica. KLMS state and prior predictions must match the
oracle at the tier-1 tolerance. KRLS at lam=1e-4 is too ill-conditioned
for that in f32 (the f32 oracle itself is up to ~5e-5 norm-relative off
the exact solution), so every tenant's state and prior predictions are
held to the oracle within 1e-3, and a sample of tenants to a float64
numpy run of the same recursion within 1e-4, norm-relative per tenant.
It also
asserts that the compiled flush and predict programs contain
``tpu_custom_call``: the Pallas kernels ran, not the XLA reference.

``--chips 4`` runs only the four-chip phase: ``core.krls.sharded_krls_run``
on a 4-way mesh at D=8192 (256 MiB of P, 64 MiB per chip), 256 ticks with
``combine_every`` 1 and 8, against dense ``rff_krls_run`` on one chip, and
both against a float64 run of the first ticks.

Usage (from the checkout root; needs a TPU):

    python chip_smoke.py [--chips 4] [--seed N]

Each phase prints one JSON line (device kind, compile and wall seconds,
peak device bytes, max errors). The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or run from a directory without the repo's ``src/``, it
exits non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# Tier-1 tolerances (rtol, atol) for each comparison made here.
KLMS_TOL = (1e-5, 1e-5)  # tests/test_chunked.py: chunk kernel vs oracle
READ_TOL = (1e-5, 1e-5)  # kernel-vs-oracle bound of the chunk kernels
# KRLS state and prior predictions, norm-relative per tenant. At P0 = 1e4 I
# two f32 implementations of the recursion do not agree to the tier-1 1e-5
# (pinned at P0 ~ 10 I): on a v5e kernel and oracle each land 5e-6 to 3e-5
# from float64 on a sample of tenants, and the two differ by up to 9.3e-5
# on the worst of 256. A bf16 matmul lands ~1e-2, a wrong update ~1.
KRLS_REL_TOL = 1e-4  # against a float64 run, on a sample of tenants
KRLS_ORACLE_TOL = 1e-3  # against the f32 oracle, on every tenant
SHARDED_TOL = 1e-5  # tests/test_krls_sharded.py: sharded vs dense preds
COMBINE_TOL = 5e-5  # tests/test_chunked.py: combine_every vs dense preds
F64_TICKS = 32  # ticks of the four-chip phase rerun in float64 on the host


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _assert_kernel(compiled, what: str) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the program")


def _max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def _check(name: str, got, want, tol) -> float:
    """Max abs error; raises past ``tol = (rtol, atol)``, elementwise."""
    rtol, atol = tol
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=rtol, atol=atol, err_msg=name
    )
    return _max_err(got, want)


def _rel_rows(got, want) -> np.ndarray:
    """Norm-relative error of each entry of the leading (tenant) axis."""
    errs = []
    for g, w in zip(np.asarray(got), np.asarray(want)):
        g, w = g.astype(np.float64), w.astype(np.float64)
        errs.append(np.linalg.norm(g - w) / np.linalg.norm(w))
    return np.asarray(errs)


def _krls_f64(rff, xs, ys, lam, beta):
    """Plain float64 EW-RLS (paper §6) over one tenant's stream: the
    yardstick for both f32 implementations. Returns (theta, P, priors)."""
    w = np.asarray(rff.omega, np.float64)
    b = np.asarray(rff.bias, np.float64)
    dfeat = w.shape[1]
    theta, p, priors = np.zeros(dfeat), np.eye(dfeat) / lam, []
    for x, y in zip(np.asarray(xs, np.float64), np.asarray(ys, np.float64)):
        z = np.sqrt(2.0 / dfeat) * np.cos(x @ w + b)
        pz = p @ z
        gain = pz / (beta + z @ pz)
        priors.append(theta @ z)
        theta = theta + gain * (y - priors[-1])
        p = (p - np.outer(gain, pz)) / beta
    return theta, p, np.asarray(priors)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _fleet(name, server, rff, xs, ys, xq, reference, check, reads=4):
    """Submit every arrival, drain, read. ``check(state, priors, ref_state,
    ref_out)`` judges the write path against the ``reference`` run; the
    reads are judged against the predict oracle on the served replica."""
    from repro.core.bank import bank_predict_block

    dev = jax.devices()[0]
    bank, ticks, d = xs.shape
    chunk = server.queue.chunk
    predict = jax.jit(bank_predict_block, static_argnames=("mode", "precision"))
    state = server.queue.state
    row = jax.tree.map(lambda a: a[:1], state)

    t0 = time.perf_counter()
    flush_c = server.queue._chunk_step.lower(
        state,
        np.zeros((bank, chunk, d), np.float32),
        np.zeros((bank, chunk), np.float32),
        np.zeros((bank, chunk), np.float32),
    ).compile()
    block_c = predict.lower(state, xq, rff, mode="auto").compile()
    row_c = predict.lower(row, xq[:1], rff, mode="auto").compile()
    compile_s = time.perf_counter() - t0
    _assert_kernel(flush_c, f"{name} flush")
    _assert_kernel(block_c, f"{name} predict_block")
    _assert_kernel(row_c, f"{name} predict")

    xs_h, ys_h, xq_h = np.asarray(xs), np.asarray(ys), np.asarray(xq)
    t0 = time.perf_counter()
    for t in range(ticks):
        for b in range(bank):
            server.submit(b, xs_h[b, t], float(ys_h[b, t]))
    results = server.drain()
    block = server.predict_block(xq_h)
    tenants = sorted({(i * (bank - 1)) // max(1, reads - 1) for i in range(reads)})
    rows = [server.predict(b, xq_h[b]) for b in tenants]
    jax.block_until_ready((server.queue.state, block, rows))
    wall_s = time.perf_counter() - t0

    served = np.asarray([[p for p, _ in results[b]] for b in range(bank)])
    state = server.queue.state
    snap = server.snapshot.state
    with jax.default_matmul_precision("float32"):
        ref_state, ref_out = jax.jit(reference)()
        block_ref = predict(snap, xq, rff, mode="xla")
    errs = check(state, served, ref_state, ref_out)
    errs["predict_block"] = _check(
        f"{name} predict_block", block, block_ref, READ_TOL
    )
    errs["predict"] = max(
        _check(f"{name} predict[{b}]", r, block_ref[b], READ_TOL)
        for b, r in zip(tenants, rows)
    )
    _emit(
        phase=name,
        device_kind=dev.device_kind,
        compile_s=compile_s,
        wall_s=wall_s,
        peak_bytes_in_use=_peak_bytes(dev),
        max_err=errs,
        tenants=bank,
        arrivals=bank * ticks,
    )


def klms_fleet(seed: int, bank: int = 1024, ticks: int = 32) -> None:

    from repro.configs.paper_rff import EXPERIMENTS
    from repro.core.bank import klms_bank_run
    from repro.core.rff import sample_rff
    from repro.data.synthetic import gen_kernel_expansion
    from repro.serve import make_server

    ex, d, chunk = EXPERIMENTS["example1"], 5, 16
    k_fm, k_data, k_q = jax.random.split(jax.random.PRNGKey(seed), 3)
    rff = sample_rff(k_fm, d, ex.rff_dim, sigma=ex.sigma)
    data = jax.vmap(
        lambda k: gen_kernel_expansion(k, ticks, d, sigma=ex.sigma)
    )(jax.random.split(k_data, bank))
    xs, ys = data.xs, data.ys
    xq = jax.random.normal(k_q, (bank, 64, d))
    server = make_server(
        "klms", feature_map=rff, bank=bank, chunk=chunk, mu=ex.mu
    )

    def check(state, priors, ref_state, ref_out):
        return {
            "theta": _check(
                "klms theta", state.theta, ref_state.theta, KLMS_TOL
            ),
            "prior_pred": _check(
                "klms prior predictions", priors, ref_out.prediction, KLMS_TOL
            ),
        }

    _fleet(
        "klms_fleet", server, rff, xs, ys, xq,
        lambda: klms_bank_run(rff, xs, ys, ex.mu, mode="xla", chunk=chunk),
        check,
    )


def krls_fleet(seed: int, bank: int = 256, ticks: int = 16) -> None:

    from repro.configs.paper_rff import EXPERIMENTS
    from repro.core.bank import krls_bank_run
    from repro.core.rff import sample_rff
    from repro.data.synthetic import gen_nonlinear_wiener
    from repro.serve import make_server

    ex, d, dfeat, chunk = EXPERIMENTS["example2"], 5, 1000, 16
    lam, beta = ex.krls_lambda, ex.krls_beta
    k_fm, k_data, k_q = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    rff = sample_rff(k_fm, d, dfeat, sigma=ex.sigma)
    xs, ys = jax.vmap(lambda k: gen_nonlinear_wiener(k, ticks, d))(
        jax.random.split(k_data, bank)
    )
    xq = jax.random.normal(k_q, (bank, 64, d))
    server = make_server(
        "krls", feature_map=rff, bank=bank, chunk=chunk, lam=lam, beta=beta
    )

    def check(state, priors, ref_state, ref_out):
        """Norm-relative per tenant: the kernel against the f32 oracle on
        every tenant (``KRLS_ORACLE_TOL``); kernel (and, for the record,
        the oracle) against the f64 recursion on 8 evenly spaced tenants
        (``KRLS_REL_TOL``) and on the tenant where kernel and oracle differ
        most in theta (``KRLS_ORACLE_TOL``)."""
        sample = np.linspace(0, bank - 1, 8).astype(int)
        worst = int(np.argmax(_rel_rows(state.theta, ref_state.theta)))
        exact = [
            _krls_f64(rff, xs[i], ys[i], lam, beta) for i in [*sample, worst]
        ]
        errs = {"worst_tenant": worst}
        for i, (k, got, ora) in enumerate((
            ("theta", state.theta, ref_state.theta),
            ("pmat", state.pmat, ref_state.pmat),
            ("prior_pred", priors, ref_out.prediction),
        )):
            got, ora = np.asarray(got), np.asarray(ora)
            want = np.stack([e[i] for e in exact])
            rows = [*sample, worst]
            kernel, oracle = _rel_rows(got[rows], want), _rel_rows(ora[rows], want)
            vs_oracle = float(_rel_rows(got, ora).max())
            for what, err, bound in (
                ("the f32 oracle", vs_oracle, KRLS_ORACLE_TOL),
                ("the f64 run", kernel[:-1].max(), KRLS_REL_TOL),
                ("the f64 run at the worst tenant", kernel[-1], KRLS_ORACLE_TOL),
            ):
                if not err < bound:
                    raise AssertionError(
                        f"krls {k}: {err} from {what} (bound {bound}; the "
                        f"f32 oracle is {oracle.max()} from the f64 run)"
                    )
            errs[k] = _max_err(got, ora)
            errs[f"{k}_rel_oracle"] = vs_oracle
            errs[f"{k}_rel_f64"] = float(kernel[:-1].max())
            errs[f"{k}_oracle_rel_f64"] = float(oracle[:-1].max())
            errs[f"{k}_worst_rel_f64"] = float(kernel[-1])
            errs[f"{k}_worst_oracle_rel_f64"] = float(oracle[-1])
        return errs

    _fleet(
        "krls_fleet", server, rff, xs, ys, xq,
        lambda: krls_bank_run(
            rff, xs, ys, lam=lam, beta=beta, mode="xla", chunk=chunk
        ),
        check,
    )


def sharded_krls(seed: int, n_shards: int = 4, dfeat: int = 8192,
                 ticks: int = 256) -> None:
    """Sharded KRLS over ``n_shards`` chips vs dense KRLS on one chip."""
    from repro.core.krls import rff_krls_run, sharded_krls_run
    from repro.core.rff import sample_rff
    from repro.data.synthetic import gen_nonlinear_wiener
    from repro.launch.mesh import make_krls_mesh

    devs = jax.devices()
    if len(devs) < n_shards:
        raise AssertionError(f"{n_shards} chips asked for, {len(devs)} found")
    # lam=1e-2 / beta=0.9995: the configuration the tier-1 sharded tests pin.
    lam, beta = 1e-2, 0.9995
    k_fm, k_data = jax.random.split(jax.random.PRNGKey(seed + 2))
    rff = sample_rff(k_fm, 5, dfeat, sigma=5.0)
    xs, ys = gen_nonlinear_wiener(k_data, ticks, 5)
    mesh = make_krls_mesh(n_shards)

    dense_run = jax.jit(lambda: rff_krls_run(rff, xs, ys, lam=lam, beta=beta))

    def run_all():
        _, dense = dense_run()
        runs = {
            k: sharded_krls_run(
                mesh, rff, xs, ys, lam=lam, beta=beta, combine_every=k
            )
            for k in (1, 8)
        }
        return jax.block_until_ready((dense, runs))

    # The first pass compiles the three programs (their jit caches keep
    # them), the second is timed; compile time is the difference.
    t0 = time.perf_counter()
    run_all()
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dense, runs = run_all()
    wall_s = time.perf_counter() - t0

    # Dense and sharded round the same inputs, so they would agree even if
    # both ran a bf16 pass; a float64 run of the first ticks is the
    # yardstick for the precision of each (a bf16 pass lands ~1e-2 off).
    _, _, exact = _krls_f64(rff, xs[:F64_TICKS], ys[:F64_TICKS], lam, beta)
    rel_f64 = {
        name: float(_rel_rows([out.prediction[:F64_TICKS]], [exact])[0])
        for name, out in [("dense", dense)]
        + [(f"combine_every_{k}", out) for k, (_, out) in runs.items()]
    }
    for name, err in rel_f64.items():
        if not err < KRLS_REL_TOL:
            raise AssertionError(
                f"{name}: predictions {err} from the f64 run (bound "
                f"{KRLS_REL_TOL})"
            )
    errs = {}
    for k, (state, out) in runs.items():
        shards = state.pmat.addressable_shards
        placed = {s.device for s in shards}
        shapes = {tuple(s.data.shape) for s in shards}
        want = (dfeat // n_shards, dfeat)
        if len(placed) != n_shards or shapes != {want}:
            raise AssertionError(
                f"combine_every={k}: pmat on {len(placed)} devices with "
                f"shard shapes {sorted(shapes)}, want {n_shards} x {want}"
            )
        err = _max_err(out.prediction, dense.prediction)
        bound = SHARDED_TOL if k == 1 else COMBINE_TOL
        if not err < bound:
            raise AssertionError(
                f"combine_every={k}: sharded vs dense {err} >= {bound}"
            )
        errs[f"combine_every_{k}"] = err
    _emit(
        phase="sharded_krls",
        device_kind=devs[0].device_kind,
        chips=n_shards,
        dfeat=dfeat,
        ticks=ticks,
        pmat_shard_shape=[dfeat // n_shards, dfeat],
        compile_s=cold_s - wall_s,
        wall_s=wall_s,
        peak_bytes_in_use=[_peak_bytes(d) for d in devs[:n_shards]],
        max_err_vs_dense=errs,
        pred_rel_f64=rel_f64,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX platform {devs[0].platform!r})",
            file=sys.stderr,
        )
        return 1

    from repro.compile_cache import enable_compile_cache

    _emit(compile_cache=enable_compile_cache())
    if args.chips == 4:
        sharded_krls(args.seed, n_shards=4)
    else:
        klms_fleet(args.seed)
        krls_fleet(args.seed)
    _emit(
        ok=True,
        device={
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
