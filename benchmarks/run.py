"""Benchmark harness: one function per paper table/figure + kernel/roofline.

Prints ``name,us_per_call,derived`` CSV (detail dicts go to stderr-style
comment lines prefixed with '#'). ``--full`` switches to paper-scale
Monte-Carlo run counts; default sizes keep the whole suite at CI scale.

Besides the CSV, the harness persists the results as ``BENCH_klms.json`` /
``BENCH_krls.json`` / ``BENCH_bank.json`` in ``--json-dir`` (default: repo
root, next to this package) with a stable schema::

    {"suite": "run_<family>", "backend": ..., "jax": ..., "full": bool,
     "records": [{"bench": ..., "us_per_call": ..., "derived": ...,
                  "detail": {...}}, ...]}

The committed copies at the repo root are the CPU baselines — re-run and
commit to track the perf trajectory across PRs instead of losing it with
CI artifacts. ``--no-json`` disables writing.

The feature-quality and serve-read-path suites keep their own record
schemas (they predate/outgrow the CSV contract); a clean full pass
delegates to their modules' writers so ``python -m benchmarks.run``
regenerates ``BENCH_features.json``, ``BENCH_serve.json``,
``BENCH_replay.json``, ``BENCH_decode.json`` and
``BENCH_recovery.json`` too, and ``--only features`` / ``--only serve``
/ ``--only replay`` / ``--only decode`` / ``--only recovery``
regenerates just that file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from benchmarks import (
    bank_bench,
    decode_bench,
    features_bench,
    kernels_bench,
    krls_shard_bench,
    paper,
    recovery_bench,
    replay_bench,
    roofline_report,
    serve_bench,
    zipf_bench,
)

# bench name -> which BENCH_<family>.json it persists to.
SUITE_OF = {
    "fig1_convergence": "klms",
    "fig2a_klms_vs_qklms": "klms",
    "fig3a_chaotic1": "klms",
    "fig3b_chaotic2": "klms",
    "table1_timing": "klms",
    "table1_highdim": "klms",
    "orf_vs_iid": "klms",
    "kernel_rff_features": "klms",
    "kernel_rff_attention": "klms",
    "kernel_rff_attention_decode": "klms",
    "roofline": "klms",
    "fig2b_krls": "krls",
    "krls_bank_fused_vs_twopass": "krls",
    "bank_fused_vs_twopass": "bank",
    "bank_streams": "bank",
    "bank_chunked_streams": "bank",
}

# Suites whose committed baseline has its own (richer) record schema and
# writer: run.py delegates to the module's main() so ONE entry point
# regenerates every committed BENCH_*.json. Each writes a *whole* file, so
# unlike the CSV suites a --only=<name> run may safely (re)write it.
# (BENCH_chunk.json stays manual: chunk_bench must set XLA_FLAGS device
# counts before the first jax import, which run.py has already done.)
DELEGATED = {
    "decode": decode_bench.main,
    "features": features_bench.main,
    "recovery": recovery_bench.main,
    "replay": replay_bench.main,
    "serve": serve_bench.main,
    "zipf": zipf_bench.main,
}


def _krls_bank_fused_vs_twopass():
    """Adapt krls_shard_bench's record format to the (us, derived, detail)
    CSV contract. derived = fused speedup (x)."""
    rec = krls_shard_bench.bench_krls_bank_fused_vs_twopass()[0]
    return rec["fused_us"], rec["fused_speedup"], rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale runs")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--json-dir",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="where BENCH_<family>.json files land (default: repo root)",
    )
    ap.add_argument(
        "--no-json", action="store_true", help="skip writing BENCH_*.json",
    )
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    scale = 4 if args.full else 1
    benches = {
        "fig1_convergence": lambda: paper.fig1_convergence(runs=25 * scale),
        "fig2a_klms_vs_qklms": lambda: paper.fig2a_klms_vs_qklms(runs=10 * scale),
        "fig2b_krls": lambda: paper.fig2b_krls(runs=5 * scale),
        "fig3a_chaotic1": lambda: paper.fig3a_chaotic1(runs=100 * scale),
        "fig3b_chaotic2": lambda: paper.fig3b_chaotic2(runs=100 * scale),
        "table1_timing": lambda: paper.table1_timing(runs=3 * scale),
        "table1_highdim": lambda: paper.table1_highdim(runs=3 * scale),
        "orf_vs_iid": lambda: paper.orf_vs_iid(num_seeds=8 * scale),
        "kernel_rff_features": kernels_bench.bench_rff_features,
        "kernel_rff_attention": kernels_bench.bench_rff_attention,
        "kernel_rff_attention_decode": kernels_bench.bench_rff_attention_decode,
        "bank_fused_vs_twopass": bank_bench.bench_bank_fused_vs_twopass,
        "bank_streams": bank_bench.bench_bank_streams,
        "bank_chunked_streams": bank_bench.bench_bank_chunked_streams,
        "krls_bank_fused_vs_twopass": _krls_bank_fused_vs_twopass,
        "roofline": roofline_report.roofline_table,
    }
    missing = set(benches) - set(SUITE_OF)
    assert not missing, f"benches missing a SUITE_OF entry: {sorted(missing)}"

    if args.only in DELEGATED:
        if args.no_json:
            print(f"# --only={args.only} is a delegated suite; nothing to do")
            return
        out = os.path.join(args.json_dir, f"BENCH_{args.only}.json")
        DELEGATED[args.only](["--out", out])
        print(f"# wrote {out}")
        return

    print("name,us_per_call,derived")
    failures = 0
    by_suite: dict[str, list] = {}
    for name, fn in benches.items():
        if args.only and args.only != name:
            continue
        try:
            us, derived, detail = fn()
            print(f"{name},{us:.3f},{derived:.4f}")
            print(f"# {name}: {json.dumps(detail)[:2000]}", flush=True)
            by_suite.setdefault(SUITE_OF[name], []).append({
                "bench": name,
                "us_per_call": us,
                "derived": derived,
                "detail": detail,
            })
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},nan,nan")
            print(f"# {name} FAILED: {e!r}", file=sys.stderr, flush=True)

    # Baselines are only trustworthy from a clean full pass: a --only run
    # or a failing bench would overwrite the committed multi-record files
    # with a partial record set.
    if args.only or failures:
        if not args.no_json:
            print(
                "# BENCH_*.json not written (partial/--only or failed run)",
                flush=True,
            )
    elif not args.no_json and by_suite:
        import jax

        for family, records in sorted(by_suite.items()):
            payload = {
                "suite": f"run_{family}",
                "backend": jax.default_backend(),
                "jax": jax.__version__,
                "full": args.full,
                "records": records,
            }
            path = os.path.join(args.json_dir, f"BENCH_{family}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"# wrote {path}", flush=True)
        # Full clean pass: also regenerate the delegated-suite baselines so
        # `python -m benchmarks.run` refreshes EVERY committed BENCH_*.json
        # (except BENCH_chunk.json — see the DELEGATED comment).
        for family, entry in sorted(DELEGATED.items()):
            path = os.path.join(args.json_dir, f"BENCH_{family}.json")
            entry(["--out", path])
            print(f"# wrote {path}", flush=True)

    if failures:
        raise SystemExit(f"{failures} benchmarks failed")


if __name__ == "__main__":
    main()
