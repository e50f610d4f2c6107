"""Pallas TPU kernel: fully-fused RFF-KRLS (EW-RLS) step for a bank of B
tenants — the KRLS analogue of kernels/rff_klms_step.py.

Per tenant, the paper's §6 recursion on RFF-mapped data:

    z     = sqrt(2/D) cos(W^T x + b)        (feature map, O(D d))
    y_hat = theta^T z                        (predict)
    e     = y - y_hat                        (prior error)
    pz    = P z                              (O(D^2) matvec)
    denom = beta + z^T pz
    g     = pz / denom
    theta <- theta + g e
    P     <- (P - g pz^T) / beta             (rank-1 downdate)

Run two-pass (feature kernel, then the RLS update over a ``(B, D, D)``
batched matvec) this reads ``P`` from HBM twice and round-trips the ``(B,
D)`` activations ``z`` and ``pz``. Fused, each grid step owns ONE tenant
end-to-end: its ``(D, D)`` P tile is read once, the matvec, gain, theta
update and outer-product downdate all happen on that VMEM tile, and only the
updated P/theta go back out — per-tick HBM traffic drops from ~4 B D^2 reads
+ 2 B D^2 writes to the structural minimum of one read + one write of P.

TPU mapping:
  * grid over the bank axis B, one tenant per grid step (its full
    ``(D, D)`` P block — VMEM budget 2 * D^2 * 4 bytes, e.g. D=1024 = 8 MiB;
    tenants needing larger D belong to the sharded path in core/krls.py);
  * ``W (d, D)`` and ``b`` are grid-invariant (index_map pinned to block 0),
    fetched once and re-used across the bank;
  * the matvec ``z P^T``, the outer product ``g^T pz`` and the projection
    ``x W`` run on the MXU via ``dot_general``; cos / scalar work is VPU.

Padding (all exact): padded d columns add 0 to the projection; padded D
columns produce garbage z but every padded row/column of the *input* P and
theta is zero, so pz, denom, gain, the downdate and the prediction are
untouched in the real region and stay exactly zero in the padded region
(the wrapper slices them off).

``beta`` is an array ``(B,)`` — per-tenant forgetting factors (the
hyperparameter-sweep axis) — broadcast from a scalar by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunking import _lead_block, _tick_get, _tick_put, vmem_params
from repro.kernels.ref import F32_DOT
from repro.kernels.rff_features import _ceil_to, _pad2

__all__ = [
    "rff_krls_step_kernel",
    "rff_krls_bank_step_pallas",
    "rff_krls_chunk_kernel",
    "rff_krls_bank_chunk_pallas",
]


def rff_krls_step_kernel(
    x_ref, w_ref, b_ref, s_ref, theta_ref, p_ref, y_ref, beta_ref,
    theta_out_ref, p_out_ref, pred_ref, err_ref
):
    """One tenant: featurize, predict, full RLS downdate — all in VMEM.

    ``s`` is the per-feature scale row of the canonical affine-trig form
    (repro.features) — zero in padded-D columns, so padded z is exactly 0.
    """
    f32 = jnp.float32
    proj = jnp.dot(
        x_ref[...].astype(f32),
        w_ref[...].astype(f32),
        preferred_element_type=f32,
        precision=F32_DOT,
    ) + b_ref[...].astype(f32)
    z = s_ref[...].astype(f32) * jnp.cos(proj)  # (1, D), VMEM-only
    theta = theta_ref[...].astype(f32)  # (1, D)
    pred = jnp.sum(theta * z, axis=1, keepdims=True)  # (1, 1)
    err = y_ref[...].astype(f32) - pred
    beta = beta_ref[...].astype(f32)  # (1, 1)

    p = p_ref[...].astype(f32)  # (D, D)
    # pz[j] = sum_k P[j, k] z[k] — contract z's feature dim with P's column
    # dim; stays a (1, D) row so no relayout is needed.
    pz = jax.lax.dot_general(
        z, p, (((1,), (1,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (1, D)
    denom = beta + jnp.sum(z * pz, axis=1, keepdims=True)  # (1, 1)
    gain = pz / denom  # (1, D)
    theta_out_ref[...] = (theta + gain * err).astype(theta_out_ref.dtype)

    # outer(g, pz): contract the unit leading dims — an MXU (D,1)@(1,D).
    outer = jax.lax.dot_general(
        gain, pz, (((0,), (0,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (D, D)
    p_new = (p - outer) / beta
    # Same numerical hygiene as the dense path: symmetrize to fight drift.
    p_new = 0.5 * (p_new + p_new.T)
    p_out_ref[...] = p_new.astype(p_out_ref.dtype)
    pred_ref[...] = pred.astype(pred_ref.dtype)
    err_ref[...] = err.astype(err_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rff_krls_bank_step_pallas(
    theta: jax.Array,
    pmat: jax.Array,
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    beta: jax.Array,
    s: jax.Array | None = None,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused EW-RLS step for B independent tenants sharing one feature map.

    Args:
      theta: ``(B, D)`` per-tenant solutions.
      pmat: ``(B, D, D)`` per-tenant inverse-correlation estimates.
      x: ``(B, d)`` one input sample per tenant/stream.
      y: ``(B,)`` targets.
      w: ``(d, D)`` shared spectral matrix.
      b: ``(D,)`` shared phases.
      beta: scalar or ``(B,)`` per-tenant forgetting factors.
      s: ``(D,)`` shared per-feature scales; None = Monte-Carlo
         ``sqrt(2/D)``.

    Returns:
      (theta_new ``(B, D)``, pmat_new ``(B, D, D)``, predictions ``(B,)``,
      prior errors ``(B,)``).
    """
    bsz, dfeat = theta.shape
    d = x.shape[-1]
    assert pmat.shape == (bsz, dfeat, dfeat)
    assert x.shape == (bsz, d) and y.shape == (bsz,)
    assert w.shape == (d, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = jnp.full((dfeat,), float((2.0 / dfeat) ** 0.5), jnp.float32)
    assert s.shape == (dfeat,)

    dp, np_ = _ceil_to(d, 128), _ceil_to(dfeat, 128)
    beta_col = jnp.broadcast_to(jnp.asarray(beta, theta.dtype), (bsz,))

    # Per-tenant rows as (B, 1, N): each block is one tenant's (1, N) row,
    # whose last two dims equal the array's (the TPU tiling rule).
    theta_p = _pad2(theta, bsz, np_)[:, None, :]
    p_p = jnp.pad(
        pmat, ((0, 0), (0, np_ - dfeat), (0, np_ - dfeat))
    )
    x_p = _pad2(x, bsz, dp)[:, None, :]
    y_p = y[:, None, None]  # (B, 1, 1)
    beta_p = beta_col[:, None, None]
    w_p = _pad2(w, dp, np_)
    b_p = jnp.pad(b, (0, np_ - dfeat))[None, :]  # (1, Np)
    s_p = jnp.pad(s, (0, np_ - dfeat))[None, :]  # (1, Np), padded scales 0

    in_specs = [
        _lead_block(1, dp),
        pl.BlockSpec((dp, np_), lambda i: (0, 0)),  # grid-invariant W
        pl.BlockSpec((1, np_), lambda i: (0, 0)),
        pl.BlockSpec((1, np_), lambda i: (0, 0)),
        _lead_block(1, np_),
        _lead_block(np_, np_),
        _lead_block(1, 1),
        _lead_block(1, 1),
    ]
    out_specs = [
        _lead_block(1, np_),
        _lead_block(np_, np_),
        _lead_block(1, 1),
        _lead_block(1, 1),
    ]
    theta_new, p_new, pred, err = pl.pallas_call(
        rff_krls_step_kernel,
        grid=(bsz,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1, np_), theta.dtype),
            jax.ShapeDtypeStruct((bsz, np_, np_), pmat.dtype),
            jax.ShapeDtypeStruct((bsz, 1, 1), theta.dtype),
            jax.ShapeDtypeStruct((bsz, 1, 1), theta.dtype),
        ],
        # One (D, D) temporary besides the blocks: the rank-1 downdate.
        compiler_params=vmem_params(in_specs + out_specs, temps=[(np_, np_)]),
        interpret=interpret,
    )(x_p, w_p, b_p, s_p, theta_p, p_p, y_p, beta_p)
    return (
        theta_new[:, 0, :dfeat],
        p_new[:, :dfeat, :dfeat],
        pred[:, 0, 0],
        err[:, 0, 0],
    )


# ---------------------------------------------------------------------------
# Time-blocked (chunked) variant: T RLS ticks per Pallas launch.
#
# The dominant HBM cost of the per-tick kernel is the (D, D) P tile: one
# read + one write per tick (8*D^2 bytes at f32 — 8 MiB/tick at D=1024).
# The chunk kernel runs a (B, T) grid with T minor and carries each tenant's
# theta/P in VMEM *scratch* accumulators (the rff_features K-loop device):
# seeded from HBM at t == 0, downdated in place for all T ticks, written
# back once at t == T-1 — P traffic per tick drops by the full factor T,
# which is exactly the paper's fixed-size-state dividend (no dictionary
# growth means the T-step replay needs zero extra bookkeeping).
# ---------------------------------------------------------------------------


def rff_krls_chunk_kernel(
    x_ref, w_ref, b_ref, s_ref, theta_ref, p_ref, y_ref, beta_ref, mask_ref,
    theta_out_ref, p_out_ref, pred_ref, err_ref, th_acc, p_acc
):
    """Grid point (i, t): tick t for tenant i on the resident theta/P tiles.

    ``mask`` gates the state update only (masked ticks emit predictions but
    change nothing); with mask==1 each tick is the per-tick kernel verbatim.
    Padded-D columns of z are exactly zero (zero-padded scale row ``s``), so
    the resident P never accumulates garbage outside the true D block.
    """
    f32 = jnp.float32
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _seed():
        th_acc[...] = theta_ref[...].astype(f32)
        p_acc[...] = p_ref[...].astype(f32)

    proj = jnp.dot(
        x_ref[pl.ds(t, 1), :].astype(f32),  # tick t of the resident (T, d)
        w_ref[...].astype(f32),
        preferred_element_type=f32,
        precision=F32_DOT,
    ) + b_ref[...].astype(f32)
    z = s_ref[...].astype(f32) * jnp.cos(proj)  # (1, D), VMEM-only
    theta = th_acc[...]  # (1, D)
    pred = jnp.sum(theta * z, axis=1, keepdims=True)  # (1, 1)
    err = _tick_get(y_ref, t).astype(f32) - pred
    beta = beta_ref[...].astype(f32)  # (1, 1)
    m = _tick_get(mask_ref, t).astype(f32)  # (1, 1)

    p = p_acc[...]  # (D, D) — resident across the chunk
    pz = jax.lax.dot_general(
        z, p, (((1,), (1,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (1, D)
    denom = beta + jnp.sum(z * pz, axis=1, keepdims=True)  # (1, 1)
    gain = pz / denom  # (1, D)
    th_acc[...] = theta + gain * (m * err)

    outer = jax.lax.dot_general(
        gain, pz, (((0,), (0,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (D, D)
    p_new = (p - outer) / beta
    p_new = 0.5 * (p_new + p_new.T)
    p_acc[...] = jnp.where(m[0, 0] > 0, p_new, p)
    _tick_put(pred_ref, t, pred)
    _tick_put(err_ref, t, err)

    @pl.when(t == nt - 1)
    def _writeback():
        theta_out_ref[...] = th_acc[...].astype(theta_out_ref.dtype)
        p_out_ref[...] = p_acc[...].astype(p_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rff_krls_bank_chunk_pallas(
    theta: jax.Array,
    pmat: jax.Array,
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    beta: jax.Array,
    mask: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """T-chunked fused EW-RLS: one launch advances every tenant by T ticks.

    Args:
      theta: ``(B, D)`` per-tenant solutions.
      pmat: ``(B, D, D)`` per-tenant inverse-correlation estimates.
      xs: ``(B, T, d)`` T samples per tenant/stream.
      ys: ``(B, T)`` targets.
      w: ``(d, D)`` shared spectral matrix.
      b: ``(D,)`` shared phases.
      beta: scalar or ``(B,)`` per-tenant forgetting factors.
      mask: optional ``(B, T)`` validity gate (1 = apply the update).
      s: ``(D,)`` shared per-feature scales; None = Monte-Carlo
         ``sqrt(2/D)``.

    Returns:
      (theta_new ``(B, D)``, pmat_new ``(B, D, D)``, predictions ``(B, T)``,
      prior errors ``(B, T)``).
    """
    bsz, tlen, d = xs.shape
    dfeat = theta.shape[-1]
    assert theta.shape == (bsz, dfeat)
    assert pmat.shape == (bsz, dfeat, dfeat) and ys.shape == (bsz, tlen)
    assert w.shape == (d, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = jnp.full((dfeat,), float((2.0 / dfeat) ** 0.5), jnp.float32)
    assert s.shape == (dfeat,)

    dp, np_ = _ceil_to(d, 128), _ceil_to(dfeat, 128)
    beta_col = jnp.broadcast_to(jnp.asarray(beta, theta.dtype), (bsz,))
    if mask is None:
        mask = jnp.ones((bsz, tlen), theta.dtype)

    # Per-tenant rows as (B, 1, N) and the tenant's whole (T, d) input block
    # resident across the chunk: every block's last two dims equal the
    # array's (the TPU tiling rule for one-tenant blocks).
    theta_p = _pad2(theta, bsz, np_)[:, None, :]
    p_p = jnp.pad(pmat, ((0, 0), (0, np_ - dfeat), (0, np_ - dfeat)))
    xs_p = jnp.pad(xs, ((0, 0), (0, 0), (0, dp - d)))
    ys_p = ys[:, None, :]  # (B, 1, T)
    beta_p = beta_col[:, None, None]
    mask_p = mask.astype(theta.dtype)[:, None, :]
    w_p = _pad2(w, dp, np_)
    b_p = jnp.pad(b, (0, np_ - dfeat))[None, :]  # (1, Np)
    s_p = jnp.pad(s, (0, np_ - dfeat))[None, :]  # (1, Np), padded scales 0

    in_specs = [
        _lead_block(tlen, dp),
        pl.BlockSpec((dp, np_), lambda i, t: (0, 0)),  # grid-invariant W
        pl.BlockSpec((1, np_), lambda i, t: (0, 0)),
        pl.BlockSpec((1, np_), lambda i, t: (0, 0)),
        _lead_block(1, np_),
        _lead_block(np_, np_),
        _lead_block(1, tlen),
        _lead_block(1, 1),
        _lead_block(1, tlen),
    ]
    out_specs = [  # all revisited over t, written back once per tenant
        _lead_block(1, np_),
        _lead_block(np_, np_),
        _lead_block(1, tlen),
        _lead_block(1, tlen),
    ]
    scratch = [pltpu.VMEM((1, np_), jnp.float32), pltpu.VMEM((np_, np_), jnp.float32)]
    theta_new, p_new, pred, err = pl.pallas_call(
        rff_krls_chunk_kernel,
        grid=(bsz, tlen),  # t minor: theta/P tiles resident across the chunk
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1, np_), theta.dtype),
            jax.ShapeDtypeStruct((bsz, np_, np_), pmat.dtype),
            jax.ShapeDtypeStruct((bsz, 1, tlen), theta.dtype),
            jax.ShapeDtypeStruct((bsz, 1, tlen), theta.dtype),
        ],
        scratch_shapes=scratch,
        # One (D, D) temporary besides blocks and scratch: the downdate.
        compiler_params=vmem_params(
            in_specs + out_specs, scratch, temps=[(np_, np_)]
        ),
        interpret=interpret,
    )(xs_p, w_p, b_p, s_p, theta_p, p_p, ys_p, beta_p, mask_p)
    return (
        theta_new[:, 0, :dfeat],
        p_new[:, :dfeat, :dfeat],
        pred[:, 0],
        err[:, 0],
    )
