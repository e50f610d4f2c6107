"""Plain reference of ``krls_fleet.json``: exponentially weighted RFF-KRLS
(arXiv:1606.03685 §6) per tenant, replayed in float64 over exactly the
arrivals a run submitted.

With ``z = sqrt(2/D) cos(x W + b)``, ``theta_0 = 0`` and ``P_0 = I/lam``,
one arrival ``(x, y)`` does::

    pz = P z;  g = pz / (beta + z.pz);  theta <- theta + g (y - theta.z)
    P <- (P - g pz^T) / beta

A read returns ``theta.z(x)`` of the tenant's state after the writes that
were published when it was served. A ``(D, D)`` float64 recursion costs
milliseconds per arrival on the host, so a sample of tenants is checked:
the one with the most writes and ``check_tenants - 1`` more drawn from the
seed among those with writes.
"""
from __future__ import annotations

import numpy as np

from bench.check import bf16, matmul
from bench.configs.klms_fleet import features, rowdot


def tenants(run) -> np.ndarray:
    counts = np.bincount(run.write_key, minlength=run.cfg["tenants"])
    hot = int(np.argmax(counts))
    rest = np.setdiff1d(np.flatnonzero(counts), [hot])
    rng = np.random.default_rng(np.random.SeedSequence(run.seed).spawn(7)[-1])
    k = min(len(rest), run.cfg["check_tenants"] - 1)
    return np.sort(np.concatenate([[hot], rng.choice(rest, k, replace=False)]))


def replay(run, ids, precision="f64") -> dict:
    """Final theta and P of tenants ``ids``, the prior prediction of each
    of their writes and the value of each of their reads (in the run's
    order), from the float64 recursion (or its bf16 control)."""
    cfg, w, b = run.cfg, run.w, run.b
    hp = cfg["hp"]
    lam, beta = hp["lam"], hp["beta"]
    dt = np.float64 if precision == "f64" else np.float32
    dfeat = w.shape[1]
    keep_w, keep_r = np.isin(run.write_key, ids), np.isin(run.read_key, ids)
    w_idx, r_idx = np.flatnonzero(keep_w), np.flatnonzero(keep_r)
    priors = np.zeros(len(run.write_key), dt)
    reads = np.zeros(len(run.read_key), dt)
    thetas, pmats = [], []
    for t in ids:
        mine = w_idx[run.write_key[w_idx] == t]
        my_reads = r_idx[run.read_key[r_idx] == t]
        z_w = features(run.write_x[mine], w, b, precision)
        z_r = features(run.read_x[my_reads], w, b, precision)
        pub = run.read_pub[my_reads]
        theta = np.zeros(dfeat, dt)
        p = np.eye(dfeat, dtype=dt) / dt(lam)
        for n in range(len(mine) + 1):
            at = pub == n
            if at.any():
                reads[my_reads[at]] = rowdot(
                    np.broadcast_to(theta, (int(at.sum()), dfeat)), z_r[at],
                    precision,
                )
            if n == len(mine):
                break
            z = z_w[n]
            pz = matmul(p, z, precision)
            g = pz / (dt(beta) + rowdot(z[None], pz[None], precision)[0])
            pred = rowdot(theta[None], z[None], precision)[0]
            theta = theta + g * (dt(run.write_y[mine[n]]) - pred)
            if precision == "f64":
                p -= np.outer(g, pz)
            else:
                p -= np.outer(bf16(g), bf16(pz))
            p /= dt(beta)
            priors[mine[n]] = pred
        thetas.append(theta)
        pmats.append(p)
    return {
        "theta": np.stack(thetas),
        "pmat": np.stack(pmats),
        "prior": priors[keep_w],
        "read": reads[keep_r],
    }
