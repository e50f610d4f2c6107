#!/usr/bin/env python
"""Fail CI on new *internal* uses of the deprecated serving factory names.

PR 7 collapsed the per-family serving factories into the learner-
parameterized facade (``repro.serve.make_server`` and friends); the old
names live on only as DeprecationWarning shims. This grep keeps the
codebase honest: source, benchmarks, examples and scripts must call the
facade, while the shim modules themselves (where the old names are
*defined*) and the tests (which pin the shims' equivalence and warning
behavior) are exempt.

Usage::

    python scripts/check_deprecated_names.py

Exits 1 listing every offending ``path:line`` if a deprecated name is
referenced outside the exempt set.
"""
from __future__ import annotations

import os
import re
import sys

DEPRECATED = [
    "make_bank_server",
    "make_krls_bank_server",
    "serve_bank_stream",
    "serve_krls_bank_stream",
    "make_chunked_bank_server",
    "make_chunked_krls_bank_server",
    "klms_micro_batch_queue",
    "krls_micro_batch_queue",
    "reset_tenants",
    "reset_krls_tenants",
]

# Where the shims are defined / re-exported, plus the tests that pin them.
EXEMPT = (
    "src/repro/serve/bank_loop.py",
    "src/repro/serve/queue.py",
    "src/repro/serve/api.py",
    "src/repro/serve/__init__.py",
    "tests/",
    "scripts/check_deprecated_names.py",
)

SCAN_DIRS = ("src", "benchmarks", "examples", "scripts")

# reset_krls_tenants contains reset_tenants — match whole identifiers.
PATTERN = re.compile(
    r"(?<![A-Za-z0-9_])(" + "|".join(DEPRECATED) + r")(?![A-Za-z0-9_])"
)


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    for scan in SCAN_DIRS:
        for dirpath, _, files in os.walk(os.path.join(root, scan)):
            for fname in sorted(files):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, root)
                if rel.startswith(EXEMPT):
                    continue
                with open(path) as f:
                    for lineno, line in enumerate(f, 1):
                        m = PATTERN.search(line)
                        if m:
                            offenders.append(
                                f"{rel}:{lineno}: {m.group(1)} "
                                f"(use the serve.make_server facade)"
                            )
    if offenders:
        print(
            "deprecated serving factory names used outside shims/tests:",
            file=sys.stderr,
        )
        for o in offenders:
            print("  " + o, file=sys.stderr)
        return 1
    print(
        f"check_deprecated_names: clean "
        f"({len(DEPRECATED)} names, dirs: {', '.join(SCAN_DIRS)})"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
