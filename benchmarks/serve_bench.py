"""Read-path benchmarks: fused query-block predict vs the vmapped adapter.

Two measurements, each paired with the analytic bytes-moved model so the
JSON artifact records prediction AND observation:

* ``bench_read_block`` — Q queries per tenant served as Q separate
  ``core.bank.bank_predict`` calls (the PR-1 adapter: one vmapped
  featurize+matvec per query, theta and W re-fetched every call) vs ONE
  ``ops.rff_bank_predict`` launch over the ``(B, Q, d)`` block, at f32 and
  bf16 read precision. On CPU the fused win is batching + dispatch
  amortization; on TPU the same schedule additionally keeps theta and W
  VMEM-resident across the block (the bytes model below).
* ``bench_read_write_mix`` — a read:write ratio sweep (1:1 -> 1000:1) of
  the train-coupled baseline (per-tick train server + per-query adapter
  reads against the live state) vs the snapshot-decoupled server
  (chunked micro-batch flushes + fused block reads from the frozen
  replica). Queries dominate real serving traffic, so this is the
  end-to-end quantity the read-path overhaul buys.

Plus ``bench_bf16_read_error`` — the per-family bf16-vs-f32 prediction
error floor (the README "Read path and serving precision" table).

Run as a script to emit ``BENCH_serve.json``:

    PYTHONPATH=src python benchmarks/serve_bench.py --out BENCH_serve.json
    PYTHONPATH=src python benchmarks/serve_bench.py --tiny   # CI smoke

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


def read_bytes_model(bank: int, d: int, dfeat: int, q: int) -> dict:
    """f32 HBM bytes moved to serve Q queries per tenant, both schedules.

    Adapter (Q separate bank_predict calls): every call re-reads W (d*D)
    and the whole theta (B*D), streams x (B*d) in and predictions (B) out.
    Fused block (one launch): W and theta are fetched ONCE — the
    VMEM-resident theta tile of kernels/rff_predict.py — and only the
    query/prediction streams scale with Q. The crossover is entirely the
    amortized (d*D + B*D) term, which is why the fused path pulls away as
    the read:write ratio (and hence Q per flush interval) grows.

    The closed form lives in repro.obs.telemetry — the same model feeds
    the live kernel.bytes_moved gauge, so bench and serving cannot drift.
    """
    from repro.obs.telemetry import predict_read_bytes

    return predict_read_bytes(bank, d, dfeat, q)


def bench_read_block(
    bank: int = 16,
    d: int = 8,
    dfeat: int = 256,
    qs: tuple = (1, 4, 16, 64, 256),
    iters: int = 5,
):
    """Q-per-query adapter loop vs one fused (B, Q, d) launch, f32 + bf16."""
    import jax
    import jax.numpy as jnp

    from repro.core.bank import bank_predict, klms_bank_init
    from repro.core.learner import klms_learner
    from repro.core.rff import sample_rff
    from repro.features.base import as_trig
    from repro.kernels import ops

    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    tf = as_trig(rff)
    learner = klms_learner(rff, 0.5)
    state = klms_bank_init(rff, bank)
    adapter = jax.jit(lambda s, x: bank_predict(learner, s, x))

    records = []
    for q in qs:
        xq = jax.random.normal(jax.random.PRNGKey(q), (bank, q, d))
        per_query = [jnp.asarray(xq[:, i]) for i in range(q)]

        def run_adapter():
            out = None
            for x in per_query:
                out = adapter(state, x)
            return out

        def run_fused(precision=None):
            return ops.rff_bank_predict(
                state.theta,
                xq,
                tf.omega,
                tf.bias,
                tf.scale,
                mode="auto",
                precision=precision,
            )

        dt_adapter = _time(run_adapter, iters)
        dt_fused = _time(run_fused, iters)
        dt_bf16 = _time(lambda: run_fused("bf16"), iters)
        qps = bank * q / dt_fused
        records.append({
            "bench": "read_block",
            "bank": bank,
            "dfeat": dfeat,
            "q": q,
            "adapter_us": dt_adapter * 1e6,
            "fused_us": dt_fused * 1e6,
            "fused_bf16_us": dt_bf16 * 1e6,
            "fused_qps": qps,
            "fused_speedup": dt_adapter / dt_fused,
            "bf16_speedup_vs_f32": dt_fused / dt_bf16,
            **read_bytes_model(bank, d, dfeat, q),
        })
    return records


def bench_read_write_mix(
    bank: int = 8,
    d: int = 8,
    dfeat: int = 128,
    n_writes: int = 16,
    q: int = 32,
    chunk: int = 16,
    ratios: tuple = (1, 10, 100, 1000),
    iters: int = 3,
):
    """Train-coupled adapter serving vs snapshot-decoupled fused serving.

    One round = one write tick per tenant + ``ratio`` bank-wide reads.
    The baseline trains per tick and answers every read with the per-query
    adapter against the live state; the snapshot path batches writes
    through the micro-batch queue (chunk=T flushes) and answers reads in
    ``q``-query fused blocks from the frozen replica.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.bank import bank_predict, klms_bank_init
    from repro.core.learner import klms_learner
    from repro.core.rff import sample_rff
    from repro.serve.api import make_server, make_tick

    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, sigma=2.0)
    learner = klms_learner(rff, 0.5)
    adapter = jax.jit(lambda s, x: bank_predict(learner, s, x))
    tick = make_tick("klms", rff, mode="auto", mu=0.5)

    rng = np.random.RandomState(0)
    xs = rng.randn(n_writes, bank, d).astype(np.float32)
    ys = rng.randn(n_writes, bank).astype(np.float32)
    init_state = klms_bank_init(rff, bank)
    # One server for the whole sweep (its jitted chunk/predict programs
    # trace once); each timed run restarts it on the fresh init state.
    srv = make_server(
        "klms", feature_map=rff, bank=bank, mu=0.5, chunk=chunk,
        publish_every=chunk, mode="auto",
    ).snapshot_server

    records = []
    for ratio in ratios:
        reads_per_round = ratio
        blocks_per_round = -(-reads_per_round // q)
        xq_block = jnp.asarray(rng.randn(bank, q, d).astype(np.float32))
        x_read = jnp.asarray(xs[0])

        def run_baseline():
            s = init_state
            out = None
            for w in range(n_writes):
                s, _ = tick(s, jnp.asarray(xs[w]), jnp.asarray(ys[w]))
                for _ in range(reads_per_round):
                    out = adapter(s, x_read)
            return out

        def run_snapshot():
            srv.reset(init_state)
            out = None
            for w in range(n_writes):
                for t in range(bank):
                    srv.submit(t, xs[w, t], ys[w, t])
                if (w + 1) % chunk == 0:
                    srv.flush()
                for _ in range(blocks_per_round):
                    out = srv.predict_block(xq_block)
            srv.drain()
            return out

        dt_base = _time(run_baseline, iters)
        dt_snap = _time(run_snapshot, iters)
        total_reads = n_writes * reads_per_round * bank
        records.append({
            "bench": "read_write_mix",
            "bank": bank,
            "dfeat": dfeat,
            "ratio": ratio,
            "q": q,
            "chunk": chunk,
            "n_writes": n_writes,
            "baseline_us": dt_base * 1e6,
            "snapshot_us": dt_snap * 1e6,
            "snapshot_speedup": dt_base / dt_snap,
            "snapshot_reads_per_s": total_reads / dt_snap,
            **read_bytes_model(bank, d, dfeat, reads_per_round * n_writes),
        })
    return records


def bench_bf16_read_error(
    families: tuple = ("rff", "orf", "qmc", "gq", "taylor"),
    d: int = 4,
    dfeat: int = 256,
    bank: int = 8,
    q: int = 256,
):
    """Per-family bf16-vs-f32 prediction error floor at serving shapes.

    The quantity the mixed-precision read contract trades away: max/RMS
    absolute prediction error of the bf16 read path against the f32
    reference, on unit-scale theta. This is the README error-floor table;
    tests/test_read_path.py pins the same bound per family.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.bank import bank_predict_block
    from repro.core.klms import LMSState
    from repro.features import make_feature_map

    records = []
    for family in families:
        fm = make_feature_map(
            family, d, dfeat, 2.0, key=jax.random.PRNGKey(0)
        )
        nfeat = fm.num_features
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        theta = 0.3 * jax.random.normal(ks[0], (bank, nfeat))
        xq = jax.random.normal(ks[1], (bank, q, d))
        state = LMSState(theta=theta, step=jnp.zeros((bank,), jnp.int32))
        f32 = bank_predict_block(state, xq, fm, mode="auto")
        bf16 = bank_predict_block(
            state, xq, fm, mode="auto", precision="bf16"
        )
        err = jnp.abs(f32 - bf16)
        records.append({
            "bench": "bf16_read_error",
            "family": family,
            "dfeat": nfeat,
            "bank": bank,
            "q": q,
            "max_abs_err": float(jnp.max(err)),
            "rms_err": float(jnp.sqrt(jnp.mean(err**2))),
            "pred_rms": float(jnp.sqrt(jnp.mean(f32**2))),
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
        args.out = "/tmp/BENCH_serve.json" if args.tiny else "BENCH_serve.json"

    if args.tiny:
        block_kw = dict(bank=4, d=4, dfeat=64, qs=(1, 8, 32), iters=2)
        mix_kw = dict(
            bank=2,
            d=4,
            dfeat=64,
            n_writes=8,
            q=8,
            chunk=8,
            ratios=(1, 10, 100),
            iters=2,
        )
    else:
        block_kw = dict(bank=16, d=8, dfeat=256, qs=(1, 4, 16, 64, 256),
                        iters=5)
        mix_kw = dict(bank=8, d=8, dfeat=128, n_writes=16, q=32, chunk=16,
                      ratios=(1, 10, 100, 1000), iters=3)

    err_kw = dict(dfeat=64, q=32) if args.tiny else {}
    records = (
        bench_read_block(**block_kw)
        + bench_read_write_mix(**mix_kw)
        + bench_bf16_read_error(**err_kw)
    )

    import jax

    payload = {
        "suite": "serve_bench",
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
