"""Sharded-KRLS benchmarks: dense vs sharded tick across D, fused vs
two-pass KRLS bank tick. Emits ``BENCH_krls.json`` (the CI bench-smoke
artifact recording the perf trajectory per PR).

Run as a script. It runs on the default JAX backend; for a CPU run set
``JAX_PLATFORMS=cpu``, and the script forces a multi-device host platform
*before* first jax use, so the sharded path actually distributes:

    JAX_PLATFORMS=cpu python benchmarks/krls_shard_bench.py --shards 8 --out BENCH_krls.json
    python benchmarks/krls_shard_bench.py --tiny   # CI smoke shapes

On CPU the sharded tick is expected to LOSE to dense (host "devices" share
the same cores and the psum is pure overhead) — the number that matters is
the per-shard memory column: the (D/n, D) P block is what fits under a
single-chip VMEM/HBM budget when the dense (D, D) no longer does. Treat the
CPU timing as the baseline for real-ICI runs (ROADMAP).

All jax imports are deferred so ``main()`` can set XLA_FLAGS first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _time(fn, iters: int = 10) -> float:
    import jax

    jax.block_until_ready(fn())  # compile
    jax.block_until_ready(fn())  # warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_dense_vs_sharded_tick(dfeats, n_shards: int, iters: int = 10):
    """Per-tick latency + per-shard memory, dense vs sharded, across D."""
    import jax
    import jax.numpy as jnp

    from repro.core.krls import (
        make_sharded_krls_step,
        rff_krls_init,
        rff_krls_step,
        sharded_krls_init,
    )
    from repro.core.rff import sample_rff
    from repro.launch.mesh import make_krls_mesh
    from repro.launch.sharding import krls_shard_bytes

    mesh = make_krls_mesh(n_shards)
    d_in = 8
    records = []
    for dfeat in dfeats:
        rff = sample_rff(jax.random.PRNGKey(0), d_in, dfeat, sigma=2.0)
        x = jax.random.normal(jax.random.PRNGKey(1), (d_in,))
        y = jnp.asarray(0.5)

        dense_state = rff_krls_init(dfeat, 1e-2)
        dense_step = jax.jit(
            lambda s, xx, yy: rff_krls_step(s, (xx, yy), rff, 0.9995)
        )
        dt_dense = _time(lambda: dense_step(dense_state, x, y), iters)

        sh_state = sharded_krls_init(mesh, dfeat, 1e-2)
        sh_step = make_sharded_krls_step(mesh, rff, 0.9995)
        dt_sh = _time(lambda: sh_step(sh_state, x, y), iters)

        mem = krls_shard_bytes(dfeat, n_shards, input_dim=d_in)
        records.append({
            "bench": "dense_vs_sharded_tick",
            "dfeat": dfeat,
            "n_shards": n_shards,
            "dense_us": dt_dense * 1e6,
            "sharded_us": dt_sh * 1e6,
            "sharded_speedup": dt_dense / dt_sh,
            "p_block_bytes_per_shard": mem["p_block_bytes"],
            "dense_p_bytes": mem["dense_p_bytes"],
        })
    return records


def bench_krls_bank_fused_vs_twopass(
    bank: int = 16, d: int = 8, dfeat: int = 256, iters: int = 10
):
    """One KRLS bank tick: fused single program vs two-pass (standalone
    feature jit, then the batched RLS update jit — z, pz and P make extra
    HBM round-trips between the calls)."""
    import jax
    import jax.numpy as jnp

    from repro.core.rff import sample_rff
    from repro.kernels import ops, ref

    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    theta = jax.random.normal(ks[0], (bank, dfeat))
    pmat = jnp.broadcast_to(jnp.eye(dfeat) * 100.0, (bank, dfeat, dfeat))
    x = jax.random.normal(ks[1], (bank, d))
    y = jax.random.normal(ks[2], (bank,))

    fused = jax.jit(
        lambda t, p, xx, yy: ops.rff_krls_bank_step(
            t, p, xx, yy, rff.omega, rff.bias, 0.9995, mode="auto"
        )
    )
    features = jax.jit(
        lambda xx: ref.rff_features_ref(xx, rff.omega, rff.bias)
    )

    @jax.jit
    def update(t, p, z, yy):
        pred = jnp.sum(t * z, axis=-1)
        err = yy - pred
        pz = jnp.einsum("bij,bj->bi", p, z)
        denom = 0.9995 + jnp.sum(z * pz, axis=-1)
        gain = pz / denom[:, None]
        t = t + gain * err[:, None]
        p = (p - gain[:, :, None] * pz[:, None, :]) / 0.9995
        p = 0.5 * (p + jnp.swapaxes(p, -1, -2))
        return t, p, pred, err

    def twopass():
        z = features(x)
        return update(theta, pmat, z, y)

    dt_fused = _time(lambda: fused(theta, pmat, x, y), iters)
    dt_two = _time(twopass, iters)
    return [{
        "bench": "krls_bank_fused_vs_twopass",
        "bank": bank,
        "dfeat": dfeat,
        "fused_us": dt_fused * 1e6,
        "twopass_us": dt_two * 1e6,
        "fused_speedup": dt_two / dt_fused,
    }]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--out", default="BENCH_krls.json")
    args = ap.parse_args(argv)

    # Must precede first jax use: the host platform locks its device count
    # at backend init.
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.shards}",
    )
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.tiny:
        dfeats, bank, dfeat_bank, iters = [64, 128], 4, 64, 3
    else:
        dfeats, bank, dfeat_bank, iters = [256, 512, 1024], 16, 256, 10

    records = []
    records += bench_dense_vs_sharded_tick(dfeats, args.shards, iters)
    records += bench_krls_bank_fused_vs_twopass(
        bank=bank, dfeat=dfeat_bank, iters=iters
    )

    import jax

    payload = {
        "suite": "krls_shard_bench",
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "tiny": args.tiny,
        "records": records,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    json.dump(payload, sys.stdout, indent=2)
    print()


if __name__ == "__main__":
    main()
