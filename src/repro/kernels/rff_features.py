"""Pallas TPU kernel: fused affine-trig feature map ``s * cos(x @ W + b)``.

This is the compute hot-spot of every RFF algorithm in the paper (per-step
cost O(D d) is *this* op), and of the RFF-attention layer (where it runs at
(batch*seq, head_dim) x (head_dim, D) scale). The per-feature scale row
``s`` (default: the Monte-Carlo ``sqrt(2/D)``) is what makes the kernel
family-agnostic — weighted Gaussian-quadrature, QMC and orthogonal feature
maps (repro.features) all canonicalize to this exact form, so ONE kernel
serves every family.

TPU mapping:
  * GEMM on the MXU with (block_m, block_k) x (block_k, block_n) VMEM tiles,
    f32 accumulation in a VMEM scratch accumulator;
  * grid (M/bm, N/bn, K/bk), K innermost so the accumulator carries across
    the minor grid dimension;
  * bias-add + cos + scale fused on the *last* K step only (VPU work), so the
    transcendental is applied exactly once per output tile — no extra HBM
    round-trip for the activation.

Block shapes default to 128x128x128: MXU-aligned (multiples of 128 on both
GEMM dims), 3 * 64KiB f32 tiles + 64KiB accumulator ≈ 256 KiB VMEM — far
under the ~16 MiB/core budget, leaving room for double-buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import F32_DOT, canon_precision

__all__ = ["rff_features_kernel", "rff_features_pallas"]


def rff_features_kernel(x_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, *,
                        precision=None):
    """Grid point (i, j, k): accumulate x[i,k] @ w[k,j]; finalize on last k.

    The per-feature scale row ``s`` is applied with the bias-add/cos on the
    last K step (VPU work, one extra (1, bn) tile in VMEM). Padded-D columns
    carry s == 0, so their outputs are exactly 0 before the wrapper slices
    them off. ``precision="bf16"`` (contract in kernels/ref.py) feeds the
    MXU bf16 operands; the accumulator stays f32 either way.
    """
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bf16 = precision == "bf16"
    gemm_dtype = jnp.bfloat16 if bf16 else jnp.float32
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(gemm_dtype),
        w_ref[...].astype(gemm_dtype),
        preferred_element_type=jnp.float32,
        precision=None if bf16 else F32_DOT,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        proj = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = (s_ref[...].astype(jnp.float32) * jnp.cos(proj)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "interpret", "out_dtype", "precision",
    ),
)
def rff_features_pallas(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    s: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    out_dtype: jnp.dtype | None = None,
    precision: str | None = None,
) -> jax.Array:
    """``s * cos(x @ w + b)`` via pallas_call.

    Args:
      x: ``(M, d)`` inputs (any float dtype).
      w: ``(d, D)`` spectral matrix.
      b: ``(D,)`` phases.
      s: ``(D,)`` per-feature scales; None means the Monte-Carlo
         ``sqrt(2/D)`` (legacy RFF behavior, bitwise unchanged).
      precision: None/"f32" (legacy, bitwise unchanged) or "bf16" — the
        GEMM operands drop to bf16 with f32 accumulation and the feature
        block is emitted in bf16 (kernels/ref.py documents the contract).

    Shapes are padded up to block multiples internally (zero-padding the
    contraction dim is exact: it adds 0 to the pre-activation; zero-padding
    ``s`` zeroes padded output columns exactly).
    """
    m, d = x.shape
    d2, n = w.shape
    assert d == d2 and b.shape == (n,)
    precision = canon_precision(precision)
    if precision == "bf16":
        out_dtype = out_dtype or jnp.bfloat16
    out_dtype = out_dtype or x.dtype
    if s is None:
        # f32 regardless of w's dtype: the kernel multiplies in f32, and the
        # legacy static-scalar scale was a full-precision python float.
        s = jnp.full((n,), float((2.0 / n) ** 0.5), jnp.float32)  # true D
    assert s.shape == (n,)

    bm, bn, bk = (min(block_m, _ceil_to(m, 8)),
                  min(block_n, _ceil_to(n, 128)),
                  min(block_k, _ceil_to(d, 128)))
    mp, np_, kp = _ceil_to(m, bm), _ceil_to(n, bn), _ceil_to(d, bk)

    xp = _pad2(x, mp, kp)
    wp = _pad2(w, kp, np_)
    bp = jnp.pad(b, (0, np_ - n))[None, :]  # (1, Np)
    sp = jnp.pad(s, (0, np_ - n))[None, :]  # (1, Np), padded scales are 0

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(rff_features_kernel, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, bp, sp)
    return out[:m, :n]


def _ceil_to(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _pad2(a: jax.Array, r: int, c: int) -> jax.Array:
    return jnp.pad(a, ((0, r - a.shape[0]), (0, c - a.shape[1])))
