"""How ``correct`` is decided: numbers against the plain reference, each
beside its own limit (``limits`` in the configuration file)."""
from __future__ import annotations

import numpy as np


def bf16(a) -> np.ndarray:
    """Round to bfloat16 (nearest even) and widen back to float32: the
    operand rounding of a one-pass bf16 matrix unit."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def matmul(a, b, precision: str):
    """``a @ b`` in float64, or with bf16 operands accumulated in float32."""
    if precision == "f64":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    return bf16(a) @ bf16(b)


def rel(got, want) -> float:
    """Norm-relative gap ``|got - want| / |want|`` (0 when both are 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    num = float(np.linalg.norm(got - want))
    den = float(np.linalg.norm(want))
    if den == 0.0:
        return 0.0 if num == 0.0 else float("inf")
    return num / den


def worst_group(got, want, groups) -> float:
    """Largest norm-relative gap over the groups (e.g. one per tenant) of
    two aligned value vectors; 0 with no values."""
    got, want, groups = np.asarray(got), np.asarray(want), np.asarray(groups)
    if len(groups) == 0:
        return 0.0
    order = np.argsort(groups, kind="stable")
    bounds = np.flatnonzero(np.diff(groups[order])) + 1
    return max(
        rel(got[idx], want[idx]) for idx in np.split(order, bounds)
    )


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}``: every number at or
    under its limit."""
    checks = {}
    for k, v in numbers.items():
        v = float(v)
        # JSON has no inf or nan: a gap that is not finite reads 1e300.
        checks[k] = {"value": v if np.isfinite(v) else 1e300,
                     "limit": float(limits[k])}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
