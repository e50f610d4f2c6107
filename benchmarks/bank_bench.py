"""Filter-bank benchmarks: fused step vs two-pass, bank scaling.

Two comparisons:

* ``bench_bank_fused_vs_twopass`` — the per-tick hot path as one fused
  program (featurize+predict+update in a single jit; on TPU the Pallas
  kernel, on CPU one XLA fusion) vs the two-pass form (feature kernel and
  update as *separate* jitted calls, forcing the ``(B, D)`` feature block
  through HBM between them). derived = fused speedup (x). NOTE: on CPU
  XLA the two-pass form often *wins* (observed 0.5-1.0x fused speedup at
  the default sizes) — XLA-CPU parallelizes the standalone feature fusion
  better than the combined program, and a CPU cache hides the round-trip.
  The number this tracks is the memory-traffic argument for the TPU Pallas
  kernel, whose VMEM-resident ``z`` interpret mode cannot time; treat the
  CPU figure as a baseline to beat when real-TPU numbers land (ROADMAP).
* ``bench_bank_streams`` — B >= 64 concurrent streams of length n served by
  ONE jitted call (the acceptance-criteria path). derived = stream-steps/s.

Run as a script to emit the CI bench-smoke artifact ``BENCH_bank.json``:

    python -m benchmarks.bank_bench --tiny --out BENCH_bank.json
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from benchmarks.kernels_bench import _time
from repro.core.bank import klms_bank_init, klms_bank_run
from repro.core.rff import sample_rff
from repro.kernels import ops, ref

__all__ = [
    "bench_bank_fused_vs_twopass",
    "bench_bank_streams",
    "bench_bank_chunked_streams",
    "main",
]


def bench_bank_fused_vs_twopass(
    bank: int = 64, d: int = 8, dfeat: int = 512
):
    """One bank tick, fused vs two-pass. derived = fused speedup (x)."""
    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    theta = jax.random.normal(ks[0], (bank, dfeat))
    x = jax.random.normal(ks[1], (bank, d))
    y = jax.random.normal(ks[2], (bank,))

    # All arrays enter as jit *arguments* (closed-over values become
    # compile-time constants and XLA folds the whole computation away).
    # mode="auto": the Pallas kernel on TPU, the XLA ref path elsewhere.
    fused = jax.jit(
        lambda t, xx, yy: ops.rff_klms_bank_step(
            t, xx, yy, rff.omega, rff.bias, 0.5, mode="auto"
        )
    )

    # Two-pass: feature map and LMS update in separate jits — z and theta
    # make an extra HBM round-trip between the calls.
    features = jax.jit(
        lambda xx: ref.rff_features_ref(xx, rff.omega, rff.bias)
    )

    @jax.jit
    def update(t, z, yy):
        pred = jnp.sum(t * z, axis=-1)
        err = yy - pred
        return t + (0.5 * err)[:, None] * z, pred, err

    def twopass():
        z = features(x)
        return update(theta, z, y)

    dt_fused = _time(lambda: fused(theta, x, y), iters=10)
    dt_two = _time(twopass, iters=10)
    return dt_fused * 1e6, dt_two / dt_fused, {
        "fused_us": dt_fused * 1e6,
        "twopass_us": dt_two * 1e6,
        "bank": bank,
        "dfeat": dfeat,
    }


def bench_bank_streams(
    bank: int = 64, n: int = 256, d: int = 8, dfeat: int = 256
):
    """B concurrent streams, one jitted call. derived = stream-steps/s."""
    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    xs = jax.random.normal(ks[0], (bank, n, d))
    ys = jax.random.normal(ks[1], (bank, n))
    state = klms_bank_init(rff, bank)

    fn = jax.jit(
        lambda s, xx, yy: klms_bank_run(rff, xx, yy, 0.5, state=s, mode="auto")
    )
    dt = _time(lambda: fn(state, xs, ys), iters=5)
    return dt / (bank * n) * 1e6, bank * n / dt, {
        "seconds": dt,
        "bank": bank,
        "steps": n,
    }


def bench_bank_chunked_streams(
    bank: int = 64, n: int = 256, d: int = 8, dfeat: int = 256,
    chunk: int = 16,
):
    """The streams bench on the chunked schedule (one launch per T ticks
    inside the jit instead of a per-tick scan). derived = stream-steps/s;
    compare against ``bench_bank_streams`` for the in-jit chunking effect
    (the out-of-jit dispatch-amortization story lives in chunk_bench.py).
    """
    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    xs = jax.random.normal(ks[0], (bank, n, d))
    ys = jax.random.normal(ks[1], (bank, n))
    state = klms_bank_init(rff, bank)

    fn = jax.jit(
        lambda s, xx, yy: klms_bank_run(
            rff, xx, yy, 0.5, state=s, mode="auto", chunk=chunk
        )
    )
    dt = _time(lambda: fn(state, xs, ys), iters=5)
    return dt / (bank * n) * 1e6, bank * n / dt, {
        "seconds": dt,
        "bank": bank,
        "steps": n,
        "chunk": chunk,
    }


def main(argv=None) -> None:
    """Emit the KLMS bank benchmarks as a ``BENCH_bank.json`` artifact."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--out", default="BENCH_bank.json")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.tiny:
        fused_kw = dict(bank=8, d=4, dfeat=64)
        stream_kw = dict(bank=8, n=32, d=4, dfeat=64)
        chunk_kw = dict(bank=8, n=32, d=4, dfeat=64, chunk=8)
    else:
        fused_kw = dict(bank=64, d=8, dfeat=512)
        stream_kw = dict(bank=64, n=256, d=8, dfeat=256)
        chunk_kw = dict(bank=64, n=256, d=8, dfeat=256, chunk=16)

    records = []
    us, derived, detail = bench_bank_fused_vs_twopass(**fused_kw)
    records.append({
        "bench": "bank_fused_vs_twopass",
        "us_per_call": us,
        "fused_speedup": derived,
        **detail,
    })
    us, derived, detail = bench_bank_streams(**stream_kw)
    records.append({
        "bench": "bank_streams",
        "us_per_step": us,
        "stream_steps_per_s": derived,
        **detail,
    })
    us, derived, detail = bench_bank_chunked_streams(**chunk_kw)
    records.append({
        "bench": "bank_chunked_streams",
        "us_per_step": us,
        "stream_steps_per_s": derived,
        **detail,
    })

    payload = {
        "suite": "bank_bench",
        "backend": jax.default_backend(),
        "tiny": args.tiny,
        "records": records,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    json.dump(payload, sys.stdout, indent=2)
    print()


if __name__ == "__main__":
    main()
