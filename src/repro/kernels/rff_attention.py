"""Pallas TPU kernel: chunked causal linear attention over random features.

This is the paper's fixed-size-state insight applied to attention (DESIGN.md
§2): with kernelized attention weights ``kappa(q_t, k_s) ~= phi(q_t)^T
phi(k_s)``, the causal attention output

    o_t = sum_{s<=t} phi(q_t)^T phi(k_s) v_s   /   sum_{s<=t} phi(q_t)^T phi(k_s)

is computable from a *fixed-size* running state ``S_t = sum phi(k_s) v_s^T in
R^{D x dv}`` and ``z_t = sum phi(k_s) in R^D`` — the exact analogue of
RFFKLMS's theta replacing the growing dictionary (here: the growing KV cache).

TPU adaptation — *chunkwise-parallel* form, not a per-token scan:
  * sequence is split into chunks of C tokens;
  * intra-chunk term: ``(Q K^T ∘ causal_mask) V`` — three MXU GEMMs;
  * inter-chunk term: ``Q @ S_prev`` — one MXU GEMM against the carried state;
  * the state lives in VMEM scratch and carries across the (sequential) minor
    grid dimension; each (batch*head) slice re-initializes it at chunk 0.

Grid: ``(BH, S/C)`` — minor dim is the chunk index, so for each bh the chunks
run in order while the state persists in scratch; different bh are
independent (state re-init at c == 0).

VMEM at defaults (C=256, D=256, dv=128, f32): q/k tiles 256KiB each, v 128KiB,
state 128KiB + z 1KiB, A 256KiB → ≈ 1 MiB, well within budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import F32_DOT, canon_precision, mp_project, mp_trig

__all__ = [
    "rff_attention_kernel",
    "rff_attention_pallas",
    "rff_attention_decode_block_kernel",
    "rff_attention_decode_block_pallas",
]


def rff_attention_kernel(
    q_ref, k_ref, v_ref, o_ref, s_ref, z_ref, *, normalize: bool, eps: float
):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    q = q_ref[0].astype(jnp.float32)  # (C, D)
    k = k_ref[0].astype(jnp.float32)  # (C, D)
    v = v_ref[0].astype(jnp.float32)  # (C, dv)

    cs = q.shape[0]
    # Causal mask including the diagonal (token attends to itself).
    row = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    mask = (row >= col).astype(jnp.float32)

    a = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * mask  # (C, C)
    out = jnp.dot(a, v, preferred_element_type=jnp.float32)  # intra
    out += jnp.dot(q, s_ref[...], preferred_element_type=jnp.float32)  # inter

    if normalize:
        denom = jnp.sum(a, axis=-1) + jnp.dot(
            q, z_ref[...][0], preferred_element_type=jnp.float32
        )
        out = out / (denom + eps)[:, None]

    o_ref[0] = out.astype(o_ref.dtype)

    # State update AFTER emitting this chunk's outputs.
    s_ref[...] += jnp.dot(k.T, v, preferred_element_type=jnp.float32)
    z_ref[...] += jnp.sum(k, axis=0)[None, :]


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "normalize", "eps", "interpret"),
)
def rff_attention_pallas(
    phi_q: jax.Array,
    phi_k: jax.Array,
    v: jax.Array,
    *,
    chunk: int = 256,
    normalize: bool = True,
    eps: float = 1e-6,
    interpret: bool = False,
) -> jax.Array:
    """Causal linear attention.

    Args:
      phi_q, phi_k: ``(BH, S, D)`` feature-mapped queries/keys (non-negative
        when ``normalize=True`` — use positive random features).
      v: ``(BH, S, dv)`` values.

    Returns:
      ``(BH, S, dv)`` attention outputs.
    """
    bh, s, d = phi_q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    assert s % c == 0, f"seq {s} must be divisible by chunk {c}"
    grid = (bh, s // c)
    return pl.pallas_call(
        functools.partial(rff_attention_kernel, normalize=normalize, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c, dv), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, dv), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), phi_q.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, dv), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(phi_q, phi_k, v)


# ---------------------------------------------------------------------------
# Fused decode-block kernel: T decode ticks per launch, state VMEM-resident.
#
# Per-token decode (ops.rff_attention_decode) pays one XLA launch AND one
# HBM round-trip of the whole (D, dv) state per token. This kernel is the
# predict kernel's theta-residency trick applied to attention state: a
# (BH, T, dh) block of PRE-PROJECTED q/k/v tokens enters, the per-head
# S (D, dv) / z (D,) state is read into VMEM once, all T strictly
# sequential ticks run against the resident copy, and the state is written
# back once — T ticks cost one launch and one state read/write instead
# of T.
#
# The feature map is fused too (the featurize GEMM the per-token path
# materialized in HBM): one (T, dh) @ (dh, D) MXU GEMM per block, in
# either the canonical affine-trig form (any as_trig family: rff/orf/
# qmc/gq) or the positive-random-feature (softmax-kernel) form, under the
# read-path precision contract of kernels/ref.py (bf16 GEMM operands, f32
# accumulation, f32 state — state never drops precision).
#
# Grid: (BH,) — one program per head; the T ticks are a fori_loop carrying
# (S, z) as values, so the state never leaves VMEM/registers mid-block.
# kernels.chunking.default_decode_block_t budgets T by charging the
# resident (D, dv) state + (dh, D) W tiles against VMEM.
# ---------------------------------------------------------------------------


def rff_attention_decode_block_kernel(
    q_ref,
    k_ref,
    v_ref,
    w_ref,
    b_ref,
    sc_ref,
    s_in_ref,
    z_in_ref,
    o_ref,
    s_out_ref,
    z_out_ref,
    phi_q_ref,
    phi_k_ref,
    *,
    tlen: int,
    dfeat: int,
    feature_kind: str,
    normalize: bool,
    eps: float,
    precision,
):
    q = q_ref[...].astype(jnp.float32)  # (Tp, dhp)
    k = k_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)  # (dhp, Dp)
    bias = b_ref[...].astype(jnp.float32)  # (1, Dp)
    sc = sc_ref[...].astype(jnp.float32)  # (1, Dp); padded columns are 0

    # Featurize the WHOLE block in one MXU GEMM per q/k — exactly
    # ref.decode_features_ref, inlined so padded-D handling stays in-kernel.
    def feat(x):
        proj = mp_project(x, w, precision)
        if feature_kind == "trig":
            phi = mp_trig(proj, bias, sc, precision)
        else:  # prf: sc is a 0/1 mask killing padded-D columns
            stab = proj - jnp.sum(jnp.square(x), axis=-1, keepdims=True) / 2.0
            phi = sc * (
                jnp.exp(stab) / jnp.sqrt(jnp.float32(dfeat)) + 1e-6
            )
            if canon_precision(precision) == "bf16":
                phi = phi.astype(jnp.bfloat16)
        return phi.astype(jnp.float32)

    # Feature rows go through VMEM scratch: a tick reads its row from a ref
    # (Mosaic has no dynamic slice of a value).
    phi_q_ref[...] = feat(q)  # (Tp, Dp)
    phi_k_ref[...] = feat(k)

    o_ref[...] = jnp.zeros_like(o_ref)

    def tick(i, carry):
        s_st, z_st = carry  # (Dp, dvp) f32, (1, Dp) f32
        qt = phi_q_ref[pl.ds(i, 1), :]  # (1, Dp)
        kt = phi_k_ref[pl.ds(i, 1), :]
        vt = v_ref[pl.ds(i, 1), :].astype(jnp.float32)  # (1, dvp)
        # Update BEFORE emitting — the token attends to itself (the
        # ops.rff_attention_decode contract).
        s_st = s_st + kt.T * vt  # rank-1, same elementwise order as oracle
        z_st = z_st + kt
        num = jnp.dot(
            qt, s_st, preferred_element_type=jnp.float32, precision=F32_DOT
        )
        if normalize:
            den = jnp.sum(qt * z_st, axis=-1) + eps
            num = num / den[:, None]
        o_ref[pl.ds(i, 1), :] = num.astype(o_ref.dtype)
        return s_st, z_st

    s_f, z_f = jax.lax.fori_loop(
        0,
        tlen,
        tick,
        (s_in_ref[...].astype(jnp.float32), z_in_ref[...].astype(jnp.float32)),
    )
    s_out_ref[...] = s_f
    z_out_ref[...] = z_f


@functools.partial(
    jax.jit,
    static_argnames=(
        "feature_kind", "normalize", "eps", "precision", "interpret",
    ),
)
def rff_attention_decode_block_pallas(
    s_state: jax.Array,
    z_state: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    b: jax.Array,
    s: jax.Array | None = None,
    *,
    feature_kind: str = "prf",
    normalize: bool = True,
    eps: float = 1e-6,
    precision: str | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """T decode ticks per launch with the (D, dv) state VMEM-resident.

    Args:
      s_state: ``(BH, D, dv)`` f32 running sum of phi(k) v^T.
      z_state: ``(BH, D)`` f32 running sum of phi(k).
      q, k: ``(BH, T, dh)`` pre-projected (RoPE'd, pre-scaled) tokens.
      v: ``(BH, T, dv)`` values.
      w: ``(dh, D)`` shared spectral matrix, b: ``(D,)`` phases.
      s: ``(D,)`` per-feature scales (trig) / column mask (prf); None =
        ``ref.default_decode_scale``.
      feature_kind: "trig" (affine-trig canonical form) or "prf".
      precision: None/"f32" or "bf16" per the kernels/ref.py contract.

    Returns:
      (outputs ``(BH, T, dv)`` f32, new_s ``(BH, D, dv)``, new_z
      ``(BH, D)``).

    Padding is exact: dh zero-pads (adds 0 to projections and ``||x||^2``),
    padded D columns carry scale/mask 0 so features are exactly 0 there,
    padded T rows are never ticked (the fori_loop stops at the real T),
    padded dv columns are sliced off.
    """
    from repro.kernels.ref import default_decode_scale
    from repro.kernels.chunking import _lead_block
    from repro.kernels.rff_features import _ceil_to, _pad2

    precision = canon_precision(precision)
    bh, tlen, dh = q.shape
    dv = v.shape[-1]
    dfeat = w.shape[-1]
    assert s_state.shape == (bh, dfeat, dv)
    assert z_state.shape == (bh, dfeat)
    assert w.shape == (dh, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = default_decode_scale(dfeat, feature_kind)
    assert s.shape == (dfeat,)

    tp = _ceil_to(tlen, 8)
    dhp, dp, dvp = _ceil_to(dh, 128), _ceil_to(dfeat, 128), _ceil_to(dv, 128)

    q_p = jnp.pad(q, ((0, 0), (0, tp - tlen), (0, dhp - dh)))
    k_p = jnp.pad(k, ((0, 0), (0, tp - tlen), (0, dhp - dh)))
    v_p = jnp.pad(v, ((0, 0), (0, tp - tlen), (0, dvp - dv)))
    w_p = _pad2(w, dhp, dp)
    b_p = jnp.pad(b, (0, dp - dfeat))[None, :]  # (1, Dp)
    s_p = jnp.pad(s, (0, dp - dfeat))[None, :]  # (1, Dp), padded scales 0
    sm_p = jnp.pad(
        s_state.astype(jnp.float32),
        ((0, 0), (0, dp - dfeat), (0, dvp - dv)),
    )
    # z rows as (BH, 1, Dp): a head's block then equals the array's last two
    # dims (the TPU tiling rule).
    zv_p = jnp.pad(z_state.astype(jnp.float32), ((0, 0), (0, dp - dfeat)))[
        :, None, :
    ]

    out, s_new, z_new = pl.pallas_call(
        functools.partial(
            rff_attention_decode_block_kernel,
            tlen=tlen,
            dfeat=dfeat,
            feature_kind=feature_kind,
            normalize=normalize,
            eps=eps,
            precision=precision,
        ),
        grid=(bh,),
        in_specs=[
            _lead_block(tp, dhp),
            _lead_block(tp, dhp),
            _lead_block(tp, dvp),
            pl.BlockSpec((dhp, dp), lambda i: (0, 0)),  # grid-invariant W
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            _lead_block(dp, dvp),
            _lead_block(1, dp),
        ],
        out_specs=[
            _lead_block(tp, dvp),
            _lead_block(dp, dvp),
            _lead_block(1, dp),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, dvp), jnp.float32),
            jax.ShapeDtypeStruct((bh, dp, dvp), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tp, dp), jnp.float32)] * 2,
        interpret=interpret,
    )(q_p, k_p, v_p, w_p, b_p, s_p, sm_p, zv_p)
    return (
        out[:, :tlen, :dv],
        s_new[:, :dfeat, :dv],
        z_new[:, 0, :dfeat],
    )
