"""Random Fourier feature maps (Rahimi & Recht) — the paper's core device.

Theorem 1 (paper): for a shift-invariant PD kernel ``kappa(x - y)`` with
Fourier transform ``p(omega)`` (a probability density by Bochner's theorem),

    z_{omega,b}(x) = sqrt(2) * cos(omega^T x + b),
    kappa(x - y)  = E_{omega~p, b~U[0,2pi]}[ z(x) z(y) ].

Sampling ``D`` features gives the Monte-Carlo estimate (paper eq. (2)–(4)):

    kappa(x - y) ~= z_Omega(x)^T z_Omega(y),
    z_Omega(x)   = sqrt(2/D) [cos(omega_i^T x + b_i)]_{i=1..D}.

For the Gaussian kernel ``kappa_sigma(u, v) = exp(-||u-v||^2 / (2 sigma^2))``
the spectral density is ``omega ~ N(0, I_d / sigma^2)`` — paper eq. (5),
whose published form reads ``sigma^D``: the ``D`` exponent is a typo for the
input dimension ``d`` (the density normalizer is ``(sigma sqrt(2 pi))^-d``);
``D`` is the paper's feature count, which never enters the density.

Two feature families live here:

* :func:`sample_rff` / :func:`rff_features` — the paper's trig features
  (unbiased for any shift-invariant kernel; Gaussian sampling built in).
* :func:`sample_prf` / :func:`positive_random_features` — positive random
  features for the *exponential* (softmax) kernel, used by the RFF linear
  attention layer. Same fixed-size-state insight, different kernel.

This module is the Monte-Carlo seed of the pluggable feature-map subsystem
in :mod:`repro.features`: deterministic Gaussian-quadrature, Taylor, QMC and
orthogonal families all satisfy the same contract there and canonicalize to
the affine-trig form ``scale * cos(x @ W + b)`` that generalizes eq. (3) —
new code should accept any such map rather than hardcoding :class:`RFF`.

Everything is a pure function over an explicit, immutable parameter struct so
it composes with jit / vmap / scan / pjit without ceremony.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import F32_DOT

__all__ = [
    "RFF",
    "sample_rff",
    "rff_features",
    "rff_features_unscaled",
    "kernel_estimate",
    "gaussian_kernel",
    "sample_prf",
    "positive_random_features",
    "softmax_kernel_estimate",
]


class RFF(NamedTuple):
    """Immutable random-feature parameters.

    Attributes:
      omega: ``(d, D)`` spectral samples (columns are the omega_i).
      bias:  ``(D,)`` phases drawn from U[0, 2pi] (trig features) or zeros
             (positive features).
    """

    omega: jax.Array
    bias: jax.Array

    @property
    def input_dim(self) -> int:
        return self.omega.shape[0]

    @property
    def num_features(self) -> int:
        return self.omega.shape[1]


def sample_rff(
    key: jax.Array,
    input_dim: int,
    num_features: int,
    sigma: float,
    dtype: jnp.dtype = jnp.float32,
    orthogonal: bool = False,
) -> RFF:
    """Draw RFF parameters for the Gaussian kernel ``exp(-||d||^2/(2 sigma^2))``.

    ``omega ~ N(0, I/sigma^2)``, ``b ~ U[0, 2pi]`` — paper §4, eq. (5).

    ``orthogonal=True`` (beyond-paper): Orthogonal Random Features
    (Yu et al. 2016) — blocks of up to ``input_dim`` spectral samples are
    orthogonalized and rescaled to chi(d) norms. Marginals are unchanged
    (the estimator stays unbiased) but the kernel-approximation variance
    drops strictly, so the same D buys a lower RFFKLMS error floor.
    """
    k_omega, k_bias = jax.random.split(key)
    bias = jax.random.uniform(
        k_bias, (num_features,), dtype, minval=0.0, maxval=2.0 * jnp.pi
    )
    if not orthogonal:
        omega = jax.random.normal(k_omega, (input_dim, num_features), dtype) / sigma
        return RFF(omega=omega, bias=bias)

    n_blocks = -(-num_features // input_dim)
    keys = jax.random.split(k_omega, n_blocks + 1)
    blocks = []
    for i in range(n_blocks):
        g = jax.random.normal(keys[i], (input_dim, input_dim), dtype)
        q, _ = jnp.linalg.qr(g)
        blocks.append(q)
    omega = jnp.concatenate(blocks, axis=1)[:, :num_features]
    norms = jnp.sqrt(
        jax.random.chisquare(
            keys[-1], input_dim, shape=(num_features,)
        ).astype(dtype)
    )
    return RFF(omega=omega * norms[None, :] / sigma, bias=bias)


def rff_features(rff: RFF, x: jax.Array) -> jax.Array:
    """``z_Omega(x) = sqrt(2/D) cos(x @ omega + b)`` — paper eq. (3).

    Args:
      rff: feature parameters ``(d, D)`` / ``(D,)``.
      x: inputs ``(..., d)``.

    Returns:
      features ``(..., D)`` such that ``z(x) @ z(y) ~= kappa(x - y)``.
    """
    d = rff.num_features
    proj = jnp.matmul(x, rff.omega, precision=F32_DOT) + rff.bias
    return jnp.sqrt(2.0 / d).astype(proj.dtype) * jnp.cos(proj)


def rff_features_unscaled(rff: RFF, x: jax.Array) -> jax.Array:
    """``sqrt(2) cos(x @ omega + b)`` — per-feature form of Theorem 1."""
    proj = jnp.matmul(x, rff.omega, precision=F32_DOT) + rff.bias
    return jnp.sqrt(2.0).astype(proj.dtype) * jnp.cos(proj)


def kernel_estimate(rff: RFF, x: jax.Array, y: jax.Array) -> jax.Array:
    """Monte-Carlo kernel estimate ``z(x)^T z(y)`` — paper eq. (4).

    Broadcasts over leading axes: ``x (..., d)``, ``y (..., d)``. When both
    arguments are the same array object (the ``kappa(0)`` norm check), the
    feature map is computed once instead of twice.
    """
    zx = rff_features(rff, x)
    zy = zx if y is x else rff_features(rff, y)
    return jnp.sum(zx * zy, axis=-1)


def gaussian_kernel(x: jax.Array, y: jax.Array, sigma: float) -> jax.Array:
    """Exact Gaussian kernel ``exp(-||x-y||^2 / (2 sigma^2))`` (oracle)."""
    sq = jnp.sum(jnp.square(x - y), axis=-1)
    return jnp.exp(-sq / (2.0 * sigma**2))


# ---------------------------------------------------------------------------
# Positive random features (softmax / exponential kernel) — used by the
# RFF linear-attention layer (the paper's fixed-size-state idea applied to
# the attention kernel; see DESIGN.md §2).
# ---------------------------------------------------------------------------


def sample_prf(
    key: jax.Array,
    input_dim: int,
    num_features: int,
    dtype: jnp.dtype = jnp.float32,
    orthogonal: bool = True,
) -> RFF:
    """Sample projections for positive random features of ``exp(q.k)``.

    Rows are standard Gaussian; when ``orthogonal=True`` blocks of up to
    ``input_dim`` rows are orthogonalized (QR) and re-scaled to chi(d) norms,
    which provably lowers estimator variance (orthogonal random features).
    """
    if not orthogonal:
        omega = jax.random.normal(key, (input_dim, num_features), dtype)
        return RFF(omega=omega, bias=jnp.zeros((num_features,), dtype))

    n_blocks = -(-num_features // input_dim)
    keys = jax.random.split(key, n_blocks + 1)
    blocks = []
    for i in range(n_blocks):
        g = jax.random.normal(keys[i], (input_dim, input_dim), dtype)
        q, _ = jnp.linalg.qr(g)
        blocks.append(q)
    omega = jnp.concatenate(blocks, axis=1)[:, :num_features]
    # re-scale columns to chi(d)-distributed norms so marginals match iid.
    norms = jnp.sqrt(
        jax.random.chisquare(keys[-1], input_dim, shape=(num_features,)).astype(dtype)
    )
    omega = omega * norms[None, :]
    return RFF(omega=omega, bias=jnp.zeros((num_features,), dtype))


def positive_random_features(
    rff: RFF, x: jax.Array, eps: float = 1e-6
) -> jax.Array:
    """``phi(x) = exp(x @ omega - ||x||^2/2) / sqrt(D)`` (+eps), so that
    ``phi(q)^T phi(k) ~= exp(q . k)`` in expectation (softmax kernel).

    No per-vector max-shift: a shift that differs between two keys biases
    their attention-weight *ratio* and breaks the prefill/decode state
    contract (a common constant would cancel; per-key constants don't).
    Inputs are pre-scaled by ``dh**-0.25`` at the attention layer, keeping
    the exponent moderate; the ``-||x||^2/2`` term keeps it unbiased.
    """
    d = rff.num_features
    proj = x @ rff.omega
    stab = proj - jnp.sum(jnp.square(x), axis=-1, keepdims=True) / 2.0
    return jnp.exp(stab) / jnp.sqrt(jnp.asarray(d, proj.dtype)) + eps


def softmax_kernel_estimate(rff: RFF, q: jax.Array, k: jax.Array) -> jax.Array:
    """Estimate ``exp(q . k)`` up to the stability shift (relative weights)."""
    pq = positive_random_features(rff, q)
    pk = positive_random_features(rff, k)
    return jnp.sum(pq * pk, axis=-1)
