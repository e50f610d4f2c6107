"""Plain reference of ``klms_fleet.json``: RFF-KLMS (arXiv:1606.03685 eq. 6)
per tenant, replayed in float64 over exactly the arrivals a run submitted.

``theta <- theta + mu (y - theta.z(x)) z(x)`` with
``z(x) = sqrt(2/D) cos(x W + b)``, theta_0 = 0. A read returns
``theta.z(x)`` of the tenant's state after the writes that were published
when the read was served. Every tenant is checked: the replay runs all of
them at once, one round per arrival rank.
"""
from __future__ import annotations

import numpy as np

from bench.check import bf16, matmul


def tenants(run) -> np.ndarray:
    return np.arange(run.cfg["tenants"])


def features(x, w, b, precision):
    """``sqrt(2/D) cos(x W + b)``; the control rounds the GEMM's operands."""
    dt = np.float64 if precision == "f64" else np.float32
    proj = matmul(x, w, precision) + b.astype(dt)
    return np.sqrt(2.0 / w.shape[1]).astype(dt) * np.cos(proj)


def rowdot(a, z, precision):
    """Row-wise ``a . z``; the control rounds the operands."""
    if precision != "f64":
        a, z = bf16(a), bf16(z)
    return np.einsum("ij,ij->i", a, z)


def replay(run, ids, precision="f64") -> dict:
    """Final theta of tenants ``ids``, the prior prediction of each of
    their writes and the value of each of their reads (in the run's
    order), from the float64 recursion (or its bf16 control)."""
    cfg, w, b = run.cfg, run.w, run.b
    mu = cfg["hp"]["mu"]
    dt = np.float64 if precision == "f64" else np.float32
    wk, wx, wy = run.write_key, run.write_x, run.write_y.astype(dt)
    rk, rx, rpub = run.read_key, run.read_x, run.read_pub
    theta = np.zeros((cfg["tenants"], w.shape[1]), dt)
    counts = np.bincount(wk, minlength=cfg["tenants"])
    order = np.argsort(wk, kind="stable")
    starts = np.cumsum(counts) - counts
    priors = np.zeros(len(wk), dt)
    reads = np.zeros(len(rk), dt)
    rounds = int(counts.max(initial=0))
    r_order = np.argsort(rpub, kind="stable")
    r_bounds = np.searchsorted(rpub[r_order], np.arange(rounds + 2))
    for r in range(rounds + 1):
        # Reads served after exactly r of their tenant's writes.
        sel = r_order[r_bounds[r]:r_bounds[r + 1]]
        if len(sel):
            reads[sel] = rowdot(
                theta[rk[sel]], features(rx[sel], w, b, precision), precision
            )
        active = np.flatnonzero(counts > r)
        if not len(active):
            continue
        idx = order[starts[active] + r]
        z = features(wx[idx], w, b, precision)
        pred = rowdot(theta[active], z, precision)
        theta[active] += (mu * (wy[idx] - pred))[:, None] * z
        priors[idx] = pred
    keep_w, keep_r = np.isin(wk, ids), np.isin(rk, ids)
    return {"theta": theta[ids], "prior": priors[keep_w], "read": reads[keep_r]}
