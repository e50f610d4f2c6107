"""Feature-quality harness: families x D sweep for the pluggable feature
subsystem (repro.features).

Two measurements per (family, D) cell:

* Kernel-approximation error against the exact Gaussian kernel on sampled
  input pairs — sup and MSE of ``z(x).z(y) - kappa(x, y)``. Monte-Carlo
  families are additionally averaged over seeds with the across-seed spread
  recorded (deterministic families have zero spread by construction).
* Steady-state MSE of RFF-KLMS on the paper's chaotic-series task (§5.3),
  averaged over the final quarter of the stream — the end-to-end quantity
  the accuracy-vs-D trade actually buys.

The sweep is the evidence for the No-Trick claim: deterministic GQ (and
QMC) reach the Monte-Carlo error floor at equal or smaller D with zero seed
variance. ``derived`` per record = the smallest swept D at which each
family's kernel RMSE beats iid RFF at the largest swept D.

Run as a script to emit ``BENCH_features.json``:

    PYTHONPATH=src python benchmarks/features_bench.py --out BENCH_features.json
    PYTHONPATH=src python benchmarks/features_bench.py --tiny   # CI smoke

Without an explicit ``--out``, a ``--tiny`` run writes to /tmp so tiny
shapes can never overwrite the committed full-shape baseline at the repo
root.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


FAMILY_ORDER = ("rff", "orf", "qmc", "gq", "taylor")


def _build(family, d, dfeat, sigma, seed=0):
    import jax

    from repro.features import make_feature_map

    return make_feature_map(
        family, d, dfeat, sigma, key=jax.random.PRNGKey(seed)
    )


def kernel_error_cell(
    family: str,
    d: int,
    dfeat: int,
    sigma: float,
    num_pairs: int = 512,
    num_seeds: int = 4,
) -> dict:
    """Sup/MSE of the kernel estimate vs the exact Gaussian kernel.

    Monte-Carlo families average over ``num_seeds`` independent maps and
    record the across-seed RMSE spread; deterministic families run once
    (their spread is identically zero — that IS the point).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.rff import gaussian_kernel
    from repro.features import featurize

    kx, ky = jax.random.split(jax.random.PRNGKey(1234))
    x = jax.random.normal(kx, (num_pairs, d))
    y = jax.random.normal(ky, (num_pairs, d))
    exact = gaussian_kernel(x, y, sigma)

    fm0 = _build(family, d, dfeat, sigma, seed=0)
    seeds = range(num_seeds) if not fm0.deterministic else range(1)
    rmses, sups = [], []
    for seed in seeds:
        fm = _build(family, d, dfeat, sigma, seed=seed)
        est = jnp.sum(featurize(fm, x) * featurize(fm, y), axis=-1)
        err = est - exact
        rmses.append(float(jnp.sqrt(jnp.mean(err**2))))
        sups.append(float(jnp.max(jnp.abs(err))))
    mean_rmse = sum(rmses) / len(rmses)
    spread = (
        max(rmses) - min(rmses) if len(rmses) > 1 else 0.0
    )
    return {
        "kernel_rmse": mean_rmse,
        "kernel_sup": sum(sups) / len(sups),
        "kernel_rmse_seed_spread": spread,
        "actual_num_features": fm0.num_features,
        "deterministic": bool(fm0.deterministic),
    }


def steady_state_cell(
    family: str,
    dfeat: int,
    sigma: float,
    num_samples: int,
    mu: float = 0.5,
) -> dict:
    """Steady-state KLMS MSE on the chaotic-series task (paper §5.3).

    The task fixes the input dimension at 2 (the ``(u_{n-1}, d_{n-1})``
    regressor), so this cell builds ITS OWN map at d=2 — a different map
    from the kernel-error cell's swept-d one. Its identity is recorded in
    ``steady_input_dim`` / ``steady_actual_num_features`` so a record never
    reads as one map's quality profile when two maps were measured.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.klms import rff_klms_run
    from repro.data.synthetic import gen_chaotic1
    from repro.features import make_feature_map

    xs, ys = gen_chaotic1(jax.random.PRNGKey(42), num_samples=num_samples)
    fm = make_feature_map(
        family, 2, dfeat, sigma, key=jax.random.PRNGKey(7)
    )
    t0 = time.perf_counter()
    _, out = jax.jit(
        lambda a, b: rff_klms_run(fm, a, b, mu)
    )(xs, ys)
    err = jax.block_until_ready(out.error)
    wall = time.perf_counter() - t0
    tail = err[-num_samples // 4 :]
    return {
        "steady_state_mse": float(jnp.mean(tail**2)),
        "steady_input_dim": fm.input_dim,
        "steady_actual_num_features": fm.num_features,
        "run_wall_s": wall,
    }


def bench_feature_quality(
    d: int = 3,
    sigma: float = 1.5,
    d_sweep=(64, 128, 256, 512),
    num_pairs: int = 512,
    num_samples: int = 2000,
) -> list[dict]:
    """The families x D sweep; one record per (family, D) cell."""
    records = []
    for family in FAMILY_ORDER:
        for dfeat in d_sweep:
            cell = {"family": family, "num_features": dfeat}
            cell.update(
                kernel_error_cell(family, d, dfeat, sigma, num_pairs)
            )
            cell.update(
                steady_state_cell(family, dfeat, sigma, num_samples)
            )
            records.append(cell)
            print(
                f"# {family:7s} D={dfeat:5d} (actual {cell['actual_num_features']:5d}) "
                f"kernel_rmse={cell['kernel_rmse']:.5f} "
                f"sup={cell['kernel_sup']:.5f} "
                f"spread={cell['kernel_rmse_seed_spread']:.5f} "
                f"klms_mse={cell['steady_state_mse']:.5f}",
                file=sys.stderr,
            )
    # derived summary: smallest D per family beating iid RFF at max D.
    rff_floor = min(
        r["kernel_rmse"] for r in records if r["family"] == "rff"
    )
    for family in FAMILY_ORDER:
        cells = [r for r in records if r["family"] == family]
        beating = [
            c["num_features"] for c in cells if c["kernel_rmse"] <= rff_floor
        ]
        for c in cells:
            c["d_matching_rff_floor"] = min(beating) if beating else None
            c["rff_floor_rmse"] = rff_floor
    return records


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.out is None:
        args.out = (
            "/tmp/BENCH_features.json" if args.tiny else "BENCH_features.json"
        )

    import jax

    if args.tiny:
        records = bench_feature_quality(
            d=2, d_sweep=(32, 64), num_pairs=128, num_samples=400
        )
    else:
        records = bench_feature_quality()

    payload = {
        "suite": "run_features",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "full": not args.tiny,
        "records": [
            {
                "bench": f"features_{r['family']}_D{r['num_features']}",
                "us_per_call": r["run_wall_s"] * 1e6,
                "derived": r["kernel_rmse"],
                "detail": r,
            }
            for r in records
        ],
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    json.dump(payload, sys.stdout, indent=2)
    print()


if __name__ == "__main__":
    main()
