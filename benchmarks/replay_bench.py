"""Parallel-in-time replay benchmarks: scan vs blocked vs per-tick rebuild.

One record per (family, T): the same T-tick replay log rebuilt three ways —

* ``sequential`` — the per-tick training scan (bitwise the train path;
  critical path T combine steps);
* ``scan`` — per-tick associative elements + ``lax.associative_scan``
  (critical path ceil(log2 T), but T (D, D) element materializations);
* ``blocked`` — the chunk-element kernels (kernels/rff_scan.py) compose Tc
  ticks per launch in VMEM at O(D^2)/tick rank-1 cost, then a short
  cross-chunk scan (critical path Tc + ceil(log2 nc), only nc (D, D)
  elements ever hit HBM).

Each mode column carries both the measurement (``us_per_rebuild``,
``ticks_per_s``) and the analytic model (``depth`` = critical-path combine
steps, ``element_bytes`` = f32 bytes of materialized elements) so the JSON
artifact records prediction AND observation: on CPU the depth model is a
proxy (no real parallel combine tree), on TPU/GPU it is the quantity the
schedule buys. The committed ``BENCH_replay.json`` is the CPU baseline —
regenerate with::

    PYTHONPATH=src python benchmarks/replay_bench.py --out BENCH_replay.json
    PYTHONPATH=src python benchmarks/replay_bench.py --tiny   # CI smoke

Without an explicit ``--out``, a ``--tiny`` run writes to /tmp so tiny
shapes can never overwrite the committed full-shape baseline.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

MODES = ("sequential", "scan", "blocked")


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


def replay_models(tlen: int, chunk: int, dfeat: int) -> dict:
    """Analytic depth/traffic columns for the three replay schedules.

    ``depth`` counts combine steps on the critical path; ``element_bytes``
    counts f32 bytes of (D, D)+(D,) elements materialized outside VMEM
    (sequential materializes none — its state stays a (D,) / (D, D)
    running value; scan materializes one element per tick; blocked only
    one per chunk)."""
    nc = -(-tlen // chunk)
    ebytes = 4 * (dfeat * dfeat + dfeat)
    return {
        "sequential_depth": tlen,
        "scan_depth": max(1, math.ceil(math.log2(tlen))),
        "blocked_depth": chunk + max(1, math.ceil(math.log2(max(nc, 2)))),
        "sequential_element_bytes": 0,
        "scan_element_bytes": tlen * ebytes,
        "blocked_element_bytes": nc * ebytes,
    }


def bench_replay(
    ts=(64, 256, 1024, 4096),
    d: int = 4,
    dfeat: int = 64,
    iters: int = 5,
) -> list:
    """Rebuild-latency sweep over log length T for both replayable
    families. KLMS pure-scan at T=4096, D=64 materializes a 64 MiB
    (T, D, D) element buffer — the point of the blocked schedule."""
    import jax
    import jax.numpy as jnp

    from repro.core.learner import klms_learner, krls_learner
    from repro.core.rff import sample_rff
    from repro.kernels.chunking import default_chunk_t

    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, 1.0)
    learners = {
        "klms": klms_learner(rff, 0.2),
        "krls": krls_learner(rff, lam=0.1, beta=0.9995),
    }
    records = []
    for family, lrn in learners.items():
        for tlen in ts:
            kx, ky = jax.random.split(jax.random.PRNGKey(tlen))
            xs = jax.random.normal(kx, (tlen, d))
            ys = jax.random.normal(ky, (tlen,))
            chunk = min(
                tlen,
                default_chunk_t(1, dfeat, xs.dtype, input_dim=d,
                                elements=True),
            )
            rec = {
                "bench": f"replay_{family}",
                "family": family,
                "tlen": tlen,
                "d": d,
                "dfeat": dfeat,
                "chunk": chunk,
                **replay_models(tlen, chunk, dfeat),
            }
            for mode in MODES:
                fn = jax.jit(
                    lambda a, b, m=mode: lrn.rebuild(a, b, mode=m,
                                                     chunk=chunk)
                )
                us = _time(lambda: fn(xs, ys), iters) * 1e6
                rec[f"{mode}_us_per_rebuild"] = us
                rec[f"{mode}_ticks_per_s"] = tlen / (us / 1e6)
            rec["scan_speedup_vs_sequential"] = (
                rec["sequential_us_per_rebuild"] / rec["scan_us_per_rebuild"]
            )
            rec["blocked_speedup_vs_sequential"] = (
                rec["sequential_us_per_rebuild"]
                / rec["blocked_us_per_rebuild"]
            )
            records.append(rec)
            print(f"# {json.dumps(rec)}", flush=True)
    return records


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.out is None:
        # Tiny runs must not clobber the committed full-shape baseline.
        args.out = (
            "/tmp/BENCH_replay.json" if args.tiny else "BENCH_replay.json"
        )

    # Tiny keeps the full-shape dfeat so the tlen=64 record joins exactly
    # against the committed baseline in scripts/check_bench_regress.py.
    kw = (
        dict(ts=(16, 64), dfeat=64, iters=2)
        if args.tiny
        else dict(ts=(64, 256, 1024, 4096), dfeat=64, iters=5)
    )
    records = bench_replay(**kw)

    import jax

    payload = {
        "suite": "replay_bench",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "tiny": args.tiny,
        "records": records,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    json.dump(payload, sys.stdout, indent=2)
    print()


if __name__ == "__main__":
    main()
