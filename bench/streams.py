"""The paper's stream generators, per tenant, in numpy.

Each tenant of a fleet is one realization of a generator of arXiv:1606.03685
§5: its own hidden function, drawn from the seed, and iid inputs
``x ~ N(0, I_d)``. A stream is then the arrivals the traffic assigns to the
tenant, in order.

* ``kernel_expansion`` (§5.1, model (7)): ``y = sum_m a_m k(c_m, x) + eta``
  with ``M`` centers ``c_m ~ N(0, I)``, ``a_m ~ N(0, coeff_std^2)``, the
  Gaussian kernel of width ``sigma`` and ``eta ~ N(0, sigma_eta^2)``.
* ``wiener`` (§5.2, model (9)): ``y = w0.x + 0.1 (w1.x)^2 + eta`` with
  ``w0, w1 ~ N(0, I)``.
"""
from __future__ import annotations

import numpy as np

BLOCK = 1 << 16  # arrivals per vectorized block (bounds host memory)


def tenant_params(gen: dict, tenants: int, d: int, rng) -> dict:
    """Draw each tenant's hidden function for the generator ``gen``."""
    kind = gen["kind"]
    if kind == "kernel_expansion":
        m = gen["num_centers"]
        return {
            "centers": rng.standard_normal((tenants, m, d)),
            "coeffs": gen["coeff_std"] * rng.standard_normal((tenants, m)),
        }
    if kind == "wiener":
        return {
            "w0": rng.standard_normal((tenants, d)),
            "w1": rng.standard_normal((tenants, d)),
        }
    raise ValueError(f"unknown stream generator {kind!r}")


def targets(gen: dict, params: dict, keys: np.ndarray, xs: np.ndarray,
            noise: np.ndarray) -> np.ndarray:
    """Noisy targets ``y`` of arrivals ``xs (n, d)`` for tenants ``keys``,
    given standard normal ``noise (n,)``."""
    n = len(keys)
    ys = np.empty(n, np.float64)
    for lo in range(0, n, BLOCK):
        k, x = keys[lo:lo + BLOCK], xs[lo:lo + BLOCK].astype(np.float64)
        if gen["kind"] == "kernel_expansion":
            diff = x[:, None, :] - params["centers"][k]
            kern = np.exp(-np.sum(diff * diff, axis=-1)
                          / (2.0 * gen["sigma"] ** 2))
            ys[lo:lo + BLOCK] = np.sum(kern * params["coeffs"][k], axis=-1)
        else:
            lin = np.sum(x * params["w0"][k], axis=-1)
            quad = np.sum(x * params["w1"][k], axis=-1)
            ys[lo:lo + BLOCK] = lin + 0.1 * quad * quad
    return ys + gen["sigma_eta"] * noise
