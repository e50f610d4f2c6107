"""Zipf tail-latency harness: the bank as a cache under skewed tenant load.

The tentpole question for the policy tier (serve/policy.py): when tenant
ids outnumber bank slots, what do eviction scoring and admission control
buy? This bench drives ``serve.make_server(policy=...)`` with a Zipf(α)
tenant arrival stream — pmf ∝ 1/rank^α over a fixed tenant universe — at
several bank:tenant ratios, interleaving reads (1 per ``read_every``
writes, tenants drawn from the same distribution), and reports per config:

* ``hit_rate`` — fraction of requests whose tenant was already resident;
* ``write_us`` / ``read_us`` — p50/p95/p99 request latency from the
  server's own metrics registry (serve/metrics.py), measured around the
  full submit/predict call: queue work, watermark flushes, eviction
  parks, and replay rebuilds all land in the write tail;
* the lifecycle counters (evictions / readmissions / admission rejects).

Policies compared: ``lru`` (always-admit, classic), ``lfu`` and ``cost``
(admission floor — a candidate must outscore the coldest incumbent, so
one-hit Zipf-tail tenants stop flushing the hot set; ``cost`` weights
recency by the family's rebuild cost). The payload's ``notes`` record
which skewed configs had ``cost`` beating plain ``lru`` on hit-rate or
p99 write latency.

Caveats recorded in the payload: replay rebuilds jit-compile once per
distinct log length, so the first pass over a config pays compile time
inside the write tail — a real cold-start cost, but one that amortizes
away in long-running servers; and latency percentiles come from
one-octave geometric buckets (serve/metrics.py), so read them as
trajectory signals, not microsecond forensics.

Run as a script to emit ``BENCH_zipf.json``:

    PYTHONPATH=src python benchmarks/zipf_bench.py --out BENCH_zipf.json
    PYTHONPATH=src python benchmarks/zipf_bench.py --tiny   # CI smoke

Without an explicit ``--out``, a ``--tiny`` run writes to /tmp so tiny
shapes can never overwrite the committed full-shape baseline.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

ALPHAS = (0.6, 0.9, 1.2)
RATIOS = ((16, 64), (16, 128))  # (bank slots, tenant universe)
POLICIES = ("lru", "lfu", "cost")


def zipf_stream(rng, tenants: int, alpha: float, n: int) -> np.ndarray:
    """n tenant ids with pmf ∝ 1/rank^alpha over [0, tenants)."""
    ranks = np.arange(1, tenants + 1, dtype=np.float64)
    probs = ranks**-alpha
    probs /= probs.sum()
    return rng.choice(tenants, size=n, p=probs)


def run_config(
    policy: str,
    alpha: float,
    bank: int,
    tenants: int,
    *,
    learner: str = "klms",
    requests: int = 4000,
    read_every: int = 4,
    chunk: int = 8,
    d: int = 8,
    dfeat: int = 64,
    log_capacity: int = 64,
    seed: int = 0,
    trace_out: str | None = None,
    ckpt: bool = False,
) -> dict:
    import jax

    from repro.core.rff import sample_rff
    from repro.serve import make_server

    rff = sample_rff(jax.random.PRNGKey(0), d, dfeat, 1.0)
    server_kw = dict(
        feature_map=rff,
        bank=bank,
        chunk=chunk,
        mu=0.3,
        policy=policy,
        log_capacity=log_capacity,
        size_watermark=chunk,
        probe=True,
    )
    srv = make_server(learner, trace=trace_out is not None, **server_kw)
    rng = np.random.default_rng(seed)
    ids = zipf_stream(rng, tenants, alpha, requests)
    xs = rng.standard_normal((requests, d)).astype(np.float32)
    ys = rng.standard_normal(requests).astype(np.float32)
    for i in range(requests):
        if read_every and i % read_every == read_every - 1:
            srv.predict(int(ids[i]), xs[i])
        else:
            srv.submit(int(ids[i]), xs[i], float(ys[i]))
    srv.drain()
    # Numerics-health columns: the in-jit tap's last flush readout plus
    # one bf16-vs-f32 read-contract sample on a Zipf-shaped query block.
    bf16_err = srv.check_read_contract(
        xs[: bank * 4].reshape(bank, 4, d)
    )
    ckpt_bitwise = None
    if ckpt:
        # Durability smoke riding the Zipf drive: checkpoint the loaded
        # server, restore into a fresh one, and demand a bitwise match on
        # every state leaf (the chaos suite covers kill-mid-stream; this
        # keeps the round-trip contract exercised at serving shapes).
        import tempfile

        from repro.serve.recovery import restore_checkpoint

        with tempfile.TemporaryDirectory() as tmp:
            srv.checkpoint(tmp)
            fresh = make_server(learner, **server_kw)
            restore_checkpoint(fresh, tmp)
            ckpt_bitwise = all(
                bool(np.array_equal(np.asarray(a), np.asarray(b),
                                    equal_nan=True))
                for a, b in zip(jax.tree.leaves(srv.queue.state),
                                jax.tree.leaves(fresh.queue.state))
            )
            assert ckpt_bitwise, "checkpoint round-trip lost state"
    probe = srv.probe.state()
    snap = srv.metrics.snapshot()
    lat = snap["histograms"]
    if trace_out is not None:
        srv.tracer.to_chrome_trace(trace_out)

    def pct(name):
        h = lat.get(name, {})
        return {k: round(h.get(k, 0.0), 1) for k in ("p50", "p95", "p99")}

    rec = {
        "bench": "zipf_serve",
        "learner": learner,
        "policy": policy,
        "alpha": alpha,
        "bank": bank,
        "tenants": tenants,
        "ratio": f"{bank}:{tenants}",
        "requests": requests,
        "hit_rate": round(srv.hit_rate(), 4),
        "write_us": pct("latency.write_us"),
        "read_us": pct("latency.read_us"),
        "counters": snap["counters"],
        "probes": {
            "healthy": probe["healthy"],
            "finite": probe["last"].get("finite", 1.0),
            "theta_norm_max": round(
                probe["last"].get("theta.norm_max", 0.0), 4
            ),
            "bf16_read_error": round(bf16_err, 6),
            "degradation_events": probe["total_events"],
        },
    }
    if ckpt_bitwise is not None:
        rec["ckpt_bitwise"] = ckpt_bitwise
    return rec


def cost_vs_lru_notes(records: list[dict]) -> list[str]:
    """Configs where the cost-aware policy beat plain LRU (the acceptance
    question), on hit-rate or p99 write latency."""
    notes = []
    by_key = {(r["policy"], r["alpha"], r["ratio"]): r for r in records}
    for (policy, alpha, ratio), rec in sorted(
        by_key.items(), key=lambda kv: (kv[0][1], kv[0][2])
    ):
        if policy != "cost":
            continue
        lru = by_key.get(("lru", alpha, ratio))
        if lru is None:
            continue
        wins = []
        if rec["hit_rate"] > lru["hit_rate"]:
            wins.append(
                f"hit_rate {rec['hit_rate']:.3f} > {lru['hit_rate']:.3f}"
            )
        if rec["write_us"]["p99"] < lru["write_us"]["p99"]:
            wins.append(
                f"p99 write {rec['write_us']['p99']} < "
                f"{lru['write_us']['p99']} us"
            )
        verdict = "; ".join(wins) if wins else "no win (LRU held)"
        notes.append(f"alpha={alpha} {ratio}: cost vs lru — {verdict}")
    return notes


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default=None)
    parser.add_argument("--tiny", action="store_true",
                        help="CI smoke shapes (never the committed baseline)")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--trace", default=None, metavar="PATH",
                        help="run the first recorded config traced and "
                             "write its Chrome trace-event JSON here")
    parser.add_argument("--ckpt", action="store_true",
                        help="checkpoint/restore round-trip on the first "
                             "recorded config (asserts a bitwise match)")
    args = parser.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    if args.tiny:
        alphas, ratios, policies = (0.9,), ((4, 16),), ("lru", "cost")
        requests = args.requests or 300
    else:
        alphas, ratios, policies = ALPHAS, RATIOS, POLICIES
        requests = args.requests or 4000

    # Warmup pass (discarded): populates the process-wide compile caches
    # (chunk scans, replay lengths, fused predict) so the recorded grid's
    # tails measure serving, not first-touch tracing. One jit per config
    # remains (each server owns its chunk-step closure) — the cold-start
    # caveat below.
    for policy in policies:
        run_config(
            policy, alphas[0], *ratios[0],
            requests=min(1500, requests), seed=99,
        )
        print(f"# warmup {policy} done", flush=True)

    records = []
    for alpha in alphas:
        for bank, tenants in ratios:
            for policy in policies:
                trace_out = args.trace if not records else None
                rec = run_config(
                    policy, alpha, bank, tenants, requests=requests,
                    trace_out=trace_out,
                    ckpt=args.ckpt and not records,
                )
                records.append(rec)
                print(
                    f"alpha={alpha} {rec['ratio']} {policy:>4}: "
                    f"hit={rec['hit_rate']:.3f} "
                    f"p99w={rec['write_us']['p99']}us "
                    f"p99r={rec['read_us']['p99']}us",
                    flush=True,
                )

    payload = {
        "suite": "zipf",
        "tiny": args.tiny,
        "backend": jax.default_backend(),
        "config": {
            "requests": requests,
            "read_every": 4,
            "chunk": 8,
            "dfeat": 64,
            "log_capacity": 64,
        },
        "notes": cost_vs_lru_notes(records),
        "caveats": [
            "write p99 includes one-time jit compiles (per distinct replay"
            " length) — cold-start cost, amortizes in long-running servers",
            "percentiles from one-octave geometric buckets (serve/metrics)",
        ],
        "records": records,
    }
    out = args.out or ("/tmp/BENCH_zipf.json" if args.tiny else "BENCH_zipf.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out} ({len(records)} records)")
    for note in payload["notes"]:
        print("  " + note)
    return 0


if __name__ == "__main__":
    sys.exit(main())
