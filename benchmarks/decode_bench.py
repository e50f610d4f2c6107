"""Decode-path benchmarks: fused block decode vs per-token dispatch vs flash.

Three measurements, together the perf story for the fused decode-block
kernel (kernels/rff_attention.py):

* ``bench_context_sweep`` — tokens/s decoding from the fixed-size RFF
  state vs from a growing softmax KV cache, across context lengths. The
  RFF state is (D, dv) regardless of how many tokens came before, so its
  tokens/s is FLAT in context; the flash/dense baseline re-reads a
  (context, dh) cache every token and degrades linearly. This is the
  paper's fixed-size-solution claim measured on the serving axis.
* ``bench_block_sweep`` — the same T decode ticks dispatched as T
  single-token launches (block_t=1, the pre-fused path) vs one fused
  launch per block_t ticks. On CPU the win is dispatch amortization; on
  TPU the same schedule additionally keeps the (D, dv) S tile and z row
  VMEM-resident across the block (one state read/write per block_t ticks
  instead of block_t).
* ``bench_bf16_error`` — bf16 read-path decode (features + numerator
  GEMMs in bf16, state f32) vs the f32 oracle: the error floor the
  mixed-precision contract promises (<= 2e-2 scale-relative).

Record schema (guarded by scripts/check_bench_schema.py)::

    {"suite": "decode", "backend": ..., "jax": ..., "tiny": bool,
     "records": [
       {"bench": "decode_context_sweep", "attn": "rff_block"|"flash",
        "context_len": int, "tokens_per_s": float, "us_per_token": float},
       {"bench": "decode_block_sweep", "block_t": int,
        "tokens_per_s": float, "us_per_token": float,
        "speedup_vs_per_token": float},
       {"bench": "decode_bf16_error", "feature_kind": str,
        "rel_err_out": float, "rel_err_state": float}, ...]}

Run as a script to emit ``BENCH_decode.json``:

    PYTHONPATH=src python benchmarks/decode_bench.py --out BENCH_decode.json
    PYTHONPATH=src python benchmarks/decode_bench.py --tiny   # CI smoke

Without an explicit ``--out``, a ``--tiny`` run writes to /tmp so tiny
shapes can never overwrite the committed full-shape baseline at the repo
root.
"""
from __future__ import annotations

import argparse
import json
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


def _decode_inputs(bh, t, dh, dfeat, dv, seed=0):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (bh, t, dh)) * 0.1
    k = jax.random.normal(ks[1], (bh, t, dh)) * 0.1
    v = jax.random.normal(ks[2], (bh, t, dv))
    w = jax.random.normal(ks[3], (dh, dfeat)) * 0.3
    b = jax.random.uniform(ks[4], (dfeat,), maxval=6.283185)
    s_state = jax.random.normal(ks[5], (bh, dfeat, dv)) * 0.1
    z_state = jax.nn.relu(jax.random.normal(ks[6], (bh, dfeat))) + 0.5
    return q, k, v, w, b, s_state, z_state


def bench_context_sweep(bh=8, dh=64, dfeat=256, dv=64, t=32,
                        contexts=(512, 2048, 8192), iters=5) -> list[dict]:
    """tokens/s vs context length: fixed-size RFF state vs softmax cache.

    The RFF decode reads NOTHING that scales with context (same (D, dv)
    state whatever came before), so the context axis only changes the
    baseline: a per-token softmax step over a (context, dh) KV cache.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    q, k, v, w, b, s_state, z_state = _decode_inputs(bh, t, dh, dfeat, dv)
    records = []
    blocked = jax.jit(lambda s, z: ops.rff_attention_decode_block(
        s, z, q, k, v, w, b, mode="xla", block_t=t))

    def flash_step(q1, kc, vc):
        # one softmax decode tick over the cache — linear in context
        logits = jnp.einsum("bd,bsd->bs", q1, kc) / jnp.sqrt(
            jnp.float32(q1.shape[-1]))
        return jnp.einsum("bs,bsv->bv", jax.nn.softmax(logits, axis=-1), vc)

    flash = jax.jit(flash_step)
    for ctx in contexts:
        dt = _time(lambda: blocked(s_state, z_state), iters)
        records.append({
            "bench": "decode_context_sweep", "attn": "rff_block",
            "context_len": int(ctx), "block_t": int(t),
            "us_per_token": dt / (bh * t) * 1e6,
            "tokens_per_s": bh * t / dt,
        })
        kc = jax.random.normal(jax.random.PRNGKey(1), (bh, ctx, dh)) * 0.1
        vc = jax.random.normal(jax.random.PRNGKey(2), (bh, ctx, dv))
        q1 = q[:, 0]
        dtf = _time(lambda: flash(q1, kc, vc), iters)
        records.append({
            "bench": "decode_context_sweep", "attn": "flash",
            "context_len": int(ctx),
            "us_per_token": dtf / bh * 1e6,
            "tokens_per_s": bh / dtf,
        })
    return records


def bench_block_sweep(bh=8, dh=64, dfeat=256, dv=64, t=64,
                      block_ts=(1, 4, 16, 64), iters=5) -> list[dict]:
    """T decode ticks as T launches (per-token dispatch) vs fused blocks.

    block_t=1 is the honest per-token path — a Python loop of T jitted
    single-token calls threading the state, exactly what serving does
    without the fused kernel. Larger block_t amortizes launches (and, on
    TPU, state movement) over the block.
    """
    import jax

    from repro.kernels import ops

    q, k, v, w, b, s_state, z_state = _decode_inputs(bh, t, dh, dfeat, dv)
    step = jax.jit(lambda s, z, q1, k1, v1: ops.rff_attention_decode_block(
        s, z, q1, k1, v1, w, b, mode="xla", block_t=1))

    def per_token():
        s_st, z_st = s_state, z_state
        out = None
        for i in range(t):
            out, s_st, z_st = step(s_st, z_st, q[:, i:i + 1], k[:, i:i + 1],
                                   v[:, i:i + 1])
        return out, s_st, z_st

    base_dt = _time(per_token, iters)
    records = [{
        "bench": "decode_block_sweep", "block_t": 1,
        "us_per_token": base_dt / (bh * t) * 1e6,
        "tokens_per_s": bh * t / base_dt,
        "speedup_vs_per_token": 1.0,
    }]
    for bt in block_ts:
        if bt == 1:
            continue
        fn = jax.jit(lambda s, z, bt=bt: ops.rff_attention_decode_block(
            s, z, q, k, v, w, b, mode="xla", block_t=bt))
        dt = _time(lambda: fn(s_state, z_state), iters)
        records.append({
            "bench": "decode_block_sweep", "block_t": int(bt),
            "us_per_token": dt / (bh * t) * 1e6,
            "tokens_per_s": bh * t / dt,
            "speedup_vs_per_token": base_dt / dt,
        })
    return records


def bench_bf16_error(bh=4, t=32, dh=32, dfeat=256, dv=32) -> list[dict]:
    """bf16 read-path decode vs the f32 oracle: scale-relative max error."""
    import numpy as np

    from repro.kernels import ref

    records = []
    for kind in ("prf", "trig"):
        q, k, v, w, b, s_state, z_state = _decode_inputs(
            bh, t, dh, dfeat, dv, seed=3)
        normalize = kind == "prf"
        f32 = ref.rff_attention_decode_block_ref(
            s_state, z_state, q, k, v, w, b, feature_kind=kind,
            normalize=normalize)
        bf16 = ref.rff_attention_decode_block_ref(
            s_state, z_state, q, k, v, w, b, feature_kind=kind,
            normalize=normalize, precision="bf16")
        def rel(g, wv):
            g = np.asarray(g, np.float32)
            wv = np.asarray(wv, np.float32)
            return float(np.max(np.abs(g - wv)) / (np.max(np.abs(wv)) + 1e-6))
        records.append({
            "bench": "decode_bf16_error", "feature_kind": kind,
            "rel_err_out": rel(bf16[0], f32[0]),
            "rel_err_state": max(rel(bf16[1], f32[1]), rel(bf16[2], f32[2])),
        })
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
        args.out = "/tmp/BENCH_decode.json" if args.tiny else "BENCH_decode.json"

    if args.tiny:
        ctx_kw = dict(bh=2, dh=16, dfeat=64, dv=16, t=8,
                      contexts=(64, 256), iters=2)
        blk_kw = dict(bh=2, dh=16, dfeat=64, dv=16, t=16,
                      block_ts=(1, 4, 16), iters=2)
        err_kw = dict(bh=2, t=8, dh=16, dfeat=64, dv=16)
    else:
        ctx_kw = dict(contexts=(512, 2048, 8192, 32768), iters=5)
        blk_kw = dict(block_ts=(1, 4, 16, 64), iters=5)
        err_kw = {}

    records = (
        bench_context_sweep(**ctx_kw)
        + bench_block_sweep(**blk_kw)
        + bench_bf16_error(**err_kw)
    )

    import jax

    payload = {
        "suite": "decode",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "tiny": bool(args.tiny),
        "records": records,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    for rec in records:
        print(json.dumps(rec), file=sys.stderr)
    print(f"wrote {args.out} ({len(records)} records)")


if __name__ == "__main__":
    main()
