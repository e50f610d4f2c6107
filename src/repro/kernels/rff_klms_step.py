"""Pallas TPU kernel: fully-fused RFF-KLMS step for a bank of B filters.

The per-step hot path of the paper's Algorithm (§4) is, per stream,

    z     = sqrt(2/D) cos(W^T x + b)      (feature map, O(D d))
    y_hat = theta^T z                      (predict)
    e     = y - y_hat                      (prior error)
    theta <- theta + mu e z                (LMS update)

Run two-pass (feature kernel, then update) this costs two HBM round-trips of
the ``(B, D)`` activation ``z`` plus a second read of ``theta``. Fused, ``z``
never leaves VMEM: one read of ``x``/``W``/``b``/``theta``, one write of the
updated ``theta`` — the arithmetic intensity the serving bank needs.

TPU mapping:
  * grid over blocks of the bank axis B only; each grid step owns ``block_b``
    filters end-to-end (their full ``(block_b, D)`` theta row-block), so the
    predict-reduction over D and the dependent update happen entirely in VMEM
    with no cross-block communication;
  * the projection ``x @ W`` runs on the MXU in f32; cos / dot / axpy are VPU
    work on the same tile;
  * ``W (d, D)`` is grid-invariant (index_map pins it to block (0, 0)), so
    Pallas fetches it once and re-uses the same VMEM tile across the bank —
    the "one HBM read of W" property. VMEM budget: W d*D f32 (e.g.
    128x2048 = 1 MiB) + 3 theta/z tiles of block_b*D ≈ well under 16 MiB.

Padding (all exact): the contraction dim d zero-pads x columns / W rows
(adds 0 to the projection); padded D columns produce garbage z but the
*input* theta is zero there so the prediction is untouched, and the wrapper
slices the updated theta back to the true D; padded B rows are sliced off.

``mu`` is an array ``(B,)`` — per-filter step sizes, the hyperparameter-sweep
axis of the filter bank — broadcast from a scalar by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunking import _tick_get, _tick_put
from repro.kernels.ref import F32_DOT
from repro.kernels.rff_features import _ceil_to, _pad2

__all__ = [
    "rff_klms_step_kernel",
    "rff_klms_bank_step_pallas",
    "rff_klms_chunk_kernel",
    "rff_klms_bank_chunk_pallas",
]


def rff_klms_step_kernel(
    x_ref, w_ref, b_ref, s_ref, theta_ref, y_ref, mu_ref, theta_out_ref,
    pred_ref, err_ref
):
    """One bank-block: featurize, predict, error, update — all in VMEM.

    ``s`` is the per-feature scale row of the canonical affine-trig form
    (repro.features) — zero in padded-D columns, so padded z is exactly 0.
    """
    proj = jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=F32_DOT,
    ) + b_ref[...].astype(jnp.float32)
    z = s_ref[...].astype(jnp.float32) * jnp.cos(proj)  # (bb, D), VMEM-only
    theta = theta_ref[...].astype(jnp.float32)
    pred = jnp.sum(theta * z, axis=1, keepdims=True)  # (bb, 1)
    err = y_ref[...].astype(jnp.float32) - pred
    theta_out_ref[...] = (
        theta + mu_ref[...].astype(jnp.float32) * err * z
    ).astype(theta_out_ref.dtype)
    pred_ref[...] = pred.astype(pred_ref.dtype)
    err_ref[...] = err.astype(err_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def rff_klms_bank_step_pallas(
    theta: jax.Array,
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    mu: jax.Array,
    s: jax.Array | None = None,
    *,
    block_b: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused KLMS step for B independent filters sharing one feature map.

    Args:
      theta: ``(B, D)`` per-filter solutions.
      x: ``(B, d)`` one input sample per filter/stream.
      y: ``(B,)`` targets.
      w: ``(d, D)`` shared spectral matrix.
      b: ``(D,)`` shared phases.
      mu: scalar or ``(B,)`` per-filter step sizes.
      s: ``(D,)`` shared per-feature scales; None = Monte-Carlo
         ``sqrt(2/D)``.

    Returns:
      (theta_new ``(B, D)``, predictions ``(B,)``, prior errors ``(B,)``).
    """
    bsz, dfeat = theta.shape
    d = x.shape[-1]
    assert x.shape == (bsz, d) and y.shape == (bsz,)
    assert w.shape == (d, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = jnp.full((dfeat,), float((2.0 / dfeat) ** 0.5), jnp.float32)
    assert s.shape == (dfeat,)

    bb = min(block_b, _ceil_to(bsz, 8))
    bp, dp, np_ = _ceil_to(bsz, bb), _ceil_to(d, 128), _ceil_to(dfeat, 128)

    mu_col = jnp.broadcast_to(jnp.asarray(mu, theta.dtype), (bsz,))
    theta_p = _pad2(theta, bp, np_)
    x_p = _pad2(x, bp, dp)
    y_p = jnp.pad(y, (0, bp - bsz))[:, None]  # (Bp, 1)
    mu_p = jnp.pad(mu_col, (0, bp - bsz))[:, None]
    w_p = _pad2(w, dp, np_)
    b_p = jnp.pad(b, (0, np_ - dfeat))[None, :]  # (1, Np)
    s_p = jnp.pad(s, (0, np_ - dfeat))[None, :]  # (1, Np), padded scales 0

    grid = (bp // bb,)
    theta_new, pred, err = pl.pallas_call(
        rff_klms_step_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, dp), lambda i: (i, 0)),
            pl.BlockSpec((dp, np_), lambda i: (0, 0)),  # grid-invariant W
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
            pl.BlockSpec((bb, np_), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, np_), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, np_), theta.dtype),
            jax.ShapeDtypeStruct((bp, 1), theta.dtype),
            jax.ShapeDtypeStruct((bp, 1), theta.dtype),
        ],
        interpret=interpret,
    )(x_p, w_p, b_p, s_p, theta_p, y_p, mu_p)
    return theta_new[:bsz, :dfeat], pred[:bsz, 0], err[:bsz, 0]


# ---------------------------------------------------------------------------
# Time-blocked (chunked) variant: T ticks per Pallas launch.
#
# The per-tick kernel above amortizes the feature round-trip but still pays
# one launch + one HBM read/write of the full (B, D) theta *per tick*. The
# chunk kernel runs a (bank_blocks, T) grid with T as the minor dimension
# and carries theta in a VMEM *scratch* accumulator (the same device the
# rff_features K-loop uses): seeded from HBM at t == 0, updated in place for
# all T ticks of a bank block, written back once at t == T-1. Theta traffic
# drops from 2*B*D*4 bytes/tick to 2*B*D*4/T, and W/b are still fetched once
# per launch (d*D*4 / (B*T) bytes per tick).
# ---------------------------------------------------------------------------


def rff_klms_chunk_kernel(
    x_ref, w_ref, b_ref, s_ref, theta_ref, y_ref, mu_ref, mask_ref,
    theta_out_ref, pred_ref, err_ref, acc_ref
):
    """Grid point (i, t): tick t for bank block i on the resident theta tile.

    ``x_ref`` is tick t's ``(bb, d)`` slab of the time-major inputs; the
    ``(bb, T)`` target/mask/prediction/error tiles stay resident for the
    whole chunk and column t is selected in-kernel (``_tick_get``/
    ``_tick_put``).

    ``mask`` (0/1 per (filter, tick)) gates the update only — masked ticks
    still emit their prior prediction/error but leave theta untouched. With
    mask==1 the update expression multiplies by exactly 1.0, so an unmasked
    chunk is bitwise identical to T per-tick kernel calls (f32 state).

    The resident theta carries across ticks, so z's padded-D columns must
    be exactly zero — guaranteed structurally: the per-feature scale row
    ``s`` is zero-padded, and 0 * cos(garbage) == 0.
    """
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _seed():
        acc_ref[...] = theta_ref[...].astype(jnp.float32)

    proj = jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=F32_DOT,
    ) + b_ref[...].astype(jnp.float32)
    z = s_ref[...].astype(jnp.float32) * jnp.cos(proj)  # (bb, D), VMEM-only
    theta = acc_ref[...]
    pred = jnp.sum(theta * z, axis=1, keepdims=True)  # (bb, 1)
    err = _tick_get(y_ref, t).astype(jnp.float32) - pred
    gated = _tick_get(mask_ref, t).astype(jnp.float32) * err
    acc_ref[...] = theta + mu_ref[...].astype(jnp.float32) * gated * z
    _tick_put(pred_ref, t, pred)
    _tick_put(err_ref, t, err)

    @pl.when(t == nt - 1)
    def _writeback():
        theta_out_ref[...] = acc_ref[...].astype(theta_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def rff_klms_bank_chunk_pallas(
    theta: jax.Array,
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    mu: jax.Array,
    mask: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    block_b: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """T-chunked fused KLMS: one launch advances every filter by T ticks.

    Args:
      theta: ``(B, D)`` per-filter solutions.
      xs: ``(B, T, d)`` T samples per filter/stream.
      ys: ``(B, T)`` targets.
      w: ``(d, D)`` shared spectral matrix.
      b: ``(D,)`` shared phases.
      mu: scalar or ``(B,)`` per-filter step sizes.
      mask: optional ``(B, T)`` validity gate (1 = apply the update); the
        masked-remainder contract of the chunked run-loops and the serve
        queue's ragged-arrival chunks.
      s: ``(D,)`` shared per-feature scales; None = Monte-Carlo
         ``sqrt(2/D)``.

    Returns:
      (theta_new ``(B, D)``, predictions ``(B, T)``, prior errors ``(B, T)``).
    """
    bsz, tlen, d = xs.shape
    dfeat = theta.shape[-1]
    assert theta.shape == (bsz, dfeat) and ys.shape == (bsz, tlen)
    assert w.shape == (d, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = jnp.full((dfeat,), float((2.0 / dfeat) ** 0.5), jnp.float32)
    assert s.shape == (dfeat,)

    bb = min(block_b, _ceil_to(bsz, 8))
    bp, dp, np_ = _ceil_to(bsz, bb), _ceil_to(d, 128), _ceil_to(dfeat, 128)

    mu_col = jnp.broadcast_to(jnp.asarray(mu, theta.dtype), (bsz,))
    if mask is None:
        mask = jnp.ones((bsz, tlen), theta.dtype)
    theta_p = _pad2(theta, bp, np_)
    # Time-major (T, Bp, dp): tick t of bank block i is one (bb, dp) tile.
    xs_p = jnp.swapaxes(jnp.pad(xs, ((0, bp - bsz), (0, 0), (0, dp - d))), 0, 1)
    ys_p = jnp.pad(ys, ((0, bp - bsz), (0, 0)))
    mask_p = jnp.pad(mask.astype(theta.dtype), ((0, bp - bsz), (0, 0)))
    mu_p = jnp.pad(mu_col, (0, bp - bsz))[:, None]
    w_p = _pad2(w, dp, np_)
    b_p = jnp.pad(b, (0, np_ - dfeat))[None, :]  # (1, Np)
    s_p = jnp.pad(s, (0, np_ - dfeat))[None, :]  # (1, Np), padded scales 0

    grid = (bp // bb, tlen)  # t minor: theta tile resident across the chunk
    theta_new, pred, err = pl.pallas_call(
        rff_klms_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bb, dp), lambda i, t: (t, i, 0)),
            pl.BlockSpec((dp, np_), lambda i, t: (0, 0)),  # grid-invariant W
            pl.BlockSpec((1, np_), lambda i, t: (0, 0)),
            pl.BlockSpec((1, np_), lambda i, t: (0, 0)),
            pl.BlockSpec((bb, np_), lambda i, t: (i, 0)),
            pl.BlockSpec((bb, tlen), lambda i, t: (i, 0)),  # resident ticks
            pl.BlockSpec((bb, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((bb, tlen), lambda i, t: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, np_), lambda i, t: (i, 0)),  # revisited over t
            pl.BlockSpec((bb, tlen), lambda i, t: (i, 0)),
            pl.BlockSpec((bb, tlen), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, np_), theta.dtype),
            jax.ShapeDtypeStruct((bp, tlen), theta.dtype),
            jax.ShapeDtypeStruct((bp, tlen), theta.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, np_), jnp.float32)],
        interpret=interpret,
    )(xs_p, w_p, b_p, s_p, theta_p, ys_p, mu_p, mask_p)
    return theta_new[:bsz, :dfeat], pred[:bsz], err[:bsz]
