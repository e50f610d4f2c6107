"""Chunked multi-tick engine benchmarks: T sweep + combine_every sweep.

Three measurements, each paired with the analytic bytes/collectives model so
the JSON artifact records prediction AND observation:

* ``bench_chunk_dispatch`` (KLMS and KRLS) — the scan-driver dispatch loop:
  a per-tick jitted server called n times from Python vs the chunked server
  called n/T times (T in {1, 4, 16, 64}). On CPU the win is pure dispatch
  amortization (one Python->XLA round-trip per T ticks); on TPU the same
  schedule additionally keeps theta/P VMEM-resident per chunk (bytes model
  below). derived = chunked-vs-per-tick ticks/sec speedup at each T.
* ``bench_combine_every`` — sharded KRLS over a forced multi-device host
  mesh with k in {1, 8, 32} ticks per psum. On host devices the collective
  is cheap so CPU numbers are a baseline; the model column (collectives per
  tick, payload bytes per collective) is what transfers to ICI/DCN.

Run as a script to emit ``BENCH_chunk.json``. It runs on the default JAX
backend; for a CPU run set ``JAX_PLATFORMS=cpu``, and the script forces
``--shards`` host devices (XLA_FLAGS, before first jax use) so the sharded
sweep actually distributes:

    JAX_PLATFORMS=cpu python benchmarks/chunk_bench.py --shards 8 --out BENCH_chunk.json
    python benchmarks/chunk_bench.py --tiny   # CI smoke -> /tmp by default

Without an explicit ``--out``, a ``--tiny`` run writes to /tmp so tiny
shapes can never overwrite the committed full-shape baseline at the repo
root.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _time(fn, iters: int = 5) -> float:
    import jax

    jax.block_until_ready(fn())  # compile
    jax.block_until_ready(fn())  # warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


# Bytes models live in repro.obs.telemetry — the same closed forms feed the
# live kernel.bytes_moved gauges, so the bench columns cannot drift from
# what serving reports.
def klms_chunk_bytes_per_tick(
    bank: int, d: int, dfeat: int, tchunk: int,
) -> dict:
    from repro.obs.telemetry import klms_chunk_bytes

    return klms_chunk_bytes(bank, d, dfeat, tchunk)


def krls_chunk_bytes_per_tick(
    bank: int, d: int, dfeat: int, tchunk: int,
) -> dict:
    from repro.obs.telemetry import krls_chunk_bytes

    return krls_chunk_bytes(bank, d, dfeat, tchunk)


def bench_chunk_dispatch(
    algo: str = "klms",
    bank: int = 16,
    d: int = 8,
    dfeat: int = 128,
    n_ticks: int = 256,
    tees: tuple = (1, 4, 16, 64),
    iters: int = 5,
):
    """Per-tick server loop vs chunked server loop, ticks/sec at each T."""
    import jax
    import jax.numpy as jnp

    from repro.core.bank import klms_bank_init, krls_bank_init
    from repro.core.rff import sample_rff
    from repro.serve.api import make_chunk_step, make_tick

    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    xs = jax.random.normal(ks[0], (bank, n_ticks, d))
    ys = jax.random.normal(ks[1], (bank, n_ticks))
    if algo == "klms":
        state = klms_bank_init(rff, bank)
        tick = make_tick("klms", rff, mode="auto", mu=0.5)
        chunk_srv = make_chunk_step("klms", rff, mode="auto", mu=0.5)
        model = klms_chunk_bytes_per_tick
    else:
        state = krls_bank_init(rff, bank, lam=1e-2)
        tick = make_tick("krls", rff, mode="auto", beta=0.9995)
        chunk_srv = make_chunk_step("krls", rff, mode="auto", beta=0.9995)
        model = krls_chunk_bytes_per_tick

    # Host-side pre-split so each timed call is pure dispatch + compute
    # (arrivals in a real serving loop come from the host anyway).
    tick_args = [
        (jnp.asarray(xs[:, t]), jnp.asarray(ys[:, t]))
        for t in range(n_ticks)
    ]

    def run_per_tick():
        s = state
        for x_t, y_t in tick_args:
            s, _ = tick(s, x_t, y_t)
        return s

    dt_tick = _time(run_per_tick, iters)
    base_tps = n_ticks / dt_tick
    records = [{
        "bench": f"{algo}_chunk_dispatch",
        "schedule": "per_tick_server",
        "bank": bank,
        "dfeat": dfeat,
        "n_ticks": n_ticks,
        "ticks_per_s": base_tps,
        "us_per_tick": dt_tick / n_ticks * 1e6,
        **model(bank, d, dfeat, 1),
    }]

    for tchunk in tees:
        nb = n_ticks // tchunk
        chunk_args = [
            (
                jnp.asarray(xs[:, i * tchunk : (i + 1) * tchunk]),
                jnp.asarray(ys[:, i * tchunk : (i + 1) * tchunk]),
                jnp.ones((bank, tchunk)),
            )
            for i in range(nb)
        ]

        def run_chunked():
            s = state
            for xc, yc, mc in chunk_args:
                s, _ = chunk_srv(s, xc, yc, mc)
            return s

        dt = _time(run_chunked, iters)
        tps = nb * tchunk / dt
        records.append({
            "bench": f"{algo}_chunk_dispatch",
            "schedule": f"chunked_T{tchunk}",
            "bank": bank,
            "dfeat": dfeat,
            "n_ticks": nb * tchunk,
            "chunk_T": tchunk,
            "ticks_per_s": tps,
            "us_per_tick": dt / (nb * tchunk) * 1e6,
            "speedup_vs_per_tick": tps / base_tps,
            **model(bank, d, dfeat, tchunk),
        })
    return records


def bench_combine_every(
    n_shards: int,
    dfeat: int = 256,
    n_ticks: int = 128,
    ks_sweep: tuple = (1, 8, 32),
    iters: int = 5,
):
    """Sharded-KRLS stream with k ticks per psum; model = collectives/tick."""
    import jax

    from repro.core.krls import sharded_krls_run
    from repro.core.rff import sample_rff
    from repro.launch.mesh import make_krls_mesh

    mesh = make_krls_mesh(n_shards)
    d_in = 8
    rff = sample_rff(jax.random.PRNGKey(0), d_in, dfeat, sigma=2.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    xs = jax.random.normal(ks[0], (n_ticks, d_in))
    ys = jax.random.normal(ks[1], (n_ticks,))

    records = []
    base_tps = None
    for k in ks_sweep:
        def run():
            return sharded_krls_run(
                mesh, rff, xs, ys, lam=1e-2, beta=0.9995, combine_every=k,
            )

        dt = _time(run, iters)
        tps = n_ticks / dt
        if base_tps is None:
            base_tps = tps
        records.append({
            "bench": "krls_combine_every",
            "combine_every": k,
            "n_shards": n_shards,
            "dfeat": dfeat,
            "n_ticks": n_ticks,
            "ticks_per_s": tps,
            "us_per_tick": dt / n_ticks * 1e6,
            "speedup_vs_k1": tps / base_tps,
            "collectives_per_tick_model": 1.0 / k,
            "payload_bytes_per_collective": 4 * k * (2 * dfeat + 1),
        })
    return records


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.out is None:
        # Tiny runs must not clobber the committed full-shape baseline.
        args.out = "/tmp/BENCH_chunk.json" if args.tiny else "BENCH_chunk.json"

    # Must precede first jax use: the host platform locks its device count
    # at backend init.
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.shards}",
    )
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.tiny:
        disp_kw = dict(bank=4, d=4, dfeat=64, n_ticks=64, iters=2)
        krls_kw = dict(bank=2, d=4, dfeat=64, n_ticks=64, iters=2)
        comb_kw = dict(dfeat=64, n_ticks=64, iters=2)
    else:
        # Serving-shaped banks: small enough that per-launch dispatch is a
        # real fraction of the tick (the quantity chunking amortizes).
        disp_kw = dict(bank=16, d=8, dfeat=128, n_ticks=256, iters=5)
        krls_kw = dict(bank=8, d=8, dfeat=128, n_ticks=256, iters=5)
        comb_kw = dict(dfeat=256, n_ticks=128, iters=5)

    records = []
    records += bench_chunk_dispatch("klms", **disp_kw)
    records += bench_chunk_dispatch("krls", **krls_kw)
    records += bench_combine_every(args.shards, **comb_kw)

    import jax

    payload = {
        "suite": "chunk_bench",
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
