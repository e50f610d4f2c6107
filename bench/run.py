"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program (``src/``), on a machine with the chips the cell asks for.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a traced run. Standard output: one JSON line of
details, then the result line (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit). The same numbers are the
last lines of standard error. Without a TPU, or with fewer chips than the
cell asks for, or without the program, it exits non-zero and prints no
result.

For measuring the benchmark itself: ``--rate`` overrides an open-loop
mix's rate (the knee sweep), ``--control 1`` also runs the bf16 control
against the reference, and ``--save-trace PATH`` keeps the profiler's
trace.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.serve  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not importable ({e})", file=sys.stderr)
        return 2
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import cells, harness

    cell = cells.resolve(args.workload, ROOT)
    try:
        result, details = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
            rate=args.rate, control=bool(args.control),
            save_trace=args.save_trace,
        )
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except harness.CompileInWindow as e:
        print(f"bench: compiled inside the measured window: {e}",
              file=sys.stderr)
        return 4
    print(json.dumps(details), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
