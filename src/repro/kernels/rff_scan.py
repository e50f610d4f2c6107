"""Pallas TPU kernels: time-blocked chunk-element builders for the
parallel-in-time replay engine (core/scan.py).

The replay scan needs, per time chunk of Tc ticks, ONE composed element:

  * KLMS — the affine map ``theta -> A theta + v`` of the whole chunk,
    where one tick contributes ``A_t = I - mu z_t z_t^T``, ``v_t = mu y_t
    z_t`` and the chunk element is ``A = A_Tc ... A_1`` (and the matching
    folded offset).
  * KRLS (information form) — ``(g, Phi_add, r_add)`` with per-tick
    contribution ``(beta, z z^T, y z)`` under scalar-gated accumulation.

Building these naively as Tc (D, D) matmuls costs O(Tc D^3); these kernels
exploit that every tick is a RANK-1 perturbation of the running element, so
each tick folds into the resident accumulator with O(D^2) work:

  KLMS:  row = z A            (one MXU matvec against the resident tile)
         A  <- A - mu_eff * z^T row        (rank-1 downdate)
         v  <- v - mu_eff * ((z . v) - y) * z
  KRLS:  g   <- beta g
         Phi <- beta Phi + z z^T
         r   <- beta r + y z

TPU mapping reuses the chunk kernels' scratch-residency pattern
(rff_klms_step.py / rff_krls_step.py): grid ``(nc, Tc)`` with the tick axis
minor, the (D, D) accumulator lives in VMEM scratch — seeded to the algebra
identity at ``t == 0`` via ``pl.when``, updated in place for all Tc ticks,
written to HBM once at ``t == Tc - 1``. Element traffic is one (D, D) write
per CHUNK instead of per tick; ``W``/``b``/``s`` are grid-invariant and
fetched once per launch.

Masking: a masked tick multiplies its update by exactly 0 (KLMS
``mu_eff = 0``; KRLS ``beta_eff = 1``, contribution gate 0), so the padded
remainder of the last chunk composes the identity — same contract as the
chunked run-loops. Padded-D columns have zero scale so ``z`` is exactly 0
there: the KLMS accumulator keeps its identity diagonal and the KRLS
accumulator stays 0 in the padded block, and the wrappers slice both back
to the true D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chunking import _lead_block, _tick_get, vmem_params
from repro.kernels.ref import F32_DOT
from repro.kernels.rff_features import _ceil_to, _pad2

__all__ = [
    "rff_klms_elements_kernel",
    "rff_klms_chunk_elements_pallas",
    "rff_krls_elements_kernel",
    "rff_krls_chunk_elements_pallas",
]


def _element_call(kernel, nc, tlen, dp, np_, out_specs, out_shape, scratch,
                  interpret):
    """The ``(nc, Tc)`` pallas_call both element kernels share: inputs
    ``xs (nc, Tc, d)``, W, b, s, ``ys (nc, 1, Tc)``, a (1, 1) scalar and
    ``mask (nc, 1, Tc)``. The tick axis is minor, so chunk i's slabs and
    its element accumulators stay resident across its ticks."""
    in_specs = [
        _lead_block(tlen, dp),
        pl.BlockSpec((dp, np_), lambda i, t: (0, 0)),  # grid-invariant W
        pl.BlockSpec((1, np_), lambda i, t: (0, 0)),
        pl.BlockSpec((1, np_), lambda i, t: (0, 0)),
        _lead_block(1, tlen),
        pl.BlockSpec((1, 1), lambda i, t: (0, 0)),
        _lead_block(1, tlen),
    ]
    return pl.pallas_call(
        kernel,
        grid=(nc, tlen),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        # One (D, D) temporary besides blocks and scratch: the outer product.
        compiler_params=vmem_params(
            in_specs + out_specs, scratch, temps=[(np_, np_)]
        ),
        interpret=interpret,
    )


def rff_klms_elements_kernel(
    x_ref, w_ref, b_ref, s_ref, y_ref, mu_ref, mask_ref,
    a_out_ref, v_out_ref, a_acc, v_acc, *, normalized: bool, eps: float,
):
    """Grid point (i, t): fold tick t into chunk i's resident (A, v) tiles.

    The identity seed uses a broadcasted iota pair (Mosaic has no
    ``jnp.eye`` lowering for scratch writes). ``row = z A`` must read the
    PRE-update A — both rank-1 folds below consume only old-tile values.
    """
    f32 = jnp.float32
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _seed():
        rows = jax.lax.broadcasted_iota(jnp.int32, a_acc.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, a_acc.shape, 1)
        a_acc[...] = jnp.where(rows == cols, 1.0, 0.0).astype(f32)
        v_acc[...] = jnp.zeros_like(v_acc)

    proj = jnp.dot(
        x_ref[pl.ds(t, 1), :].astype(f32),  # tick t of the resident (Tc, d)
        w_ref[...].astype(f32),
        preferred_element_type=f32,
        precision=F32_DOT,
    ) + b_ref[...].astype(f32)
    z = s_ref[...].astype(f32) * jnp.cos(proj)  # (1, D), VMEM-only
    mu = mu_ref[...].astype(f32)  # (1, 1)
    if normalized:
        mu = mu / (eps + jnp.sum(z * z, axis=1, keepdims=True))
    mu_eff = _tick_get(mask_ref, t).astype(f32) * mu  # (1, 1); masked -> 0

    a = a_acc[...]  # (D, D) — resident across the chunk
    # row = z A: contract z's feature dim with A's row dim (MXU matvec).
    row = jax.lax.dot_general(
        z, a, (((1,), (0,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (1, D)
    # outer(z, row): contract the unit leading dims — an MXU (D,1)@(1,D).
    outer = jax.lax.dot_general(
        z, row, (((0,), (0,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (D, D)
    # Scale by the scalar, not the (1, 1) value: Mosaic cannot broadcast a
    # vector over both tile dims.
    a_acc[...] = a - mu_eff[0, 0] * outer

    v = v_acc[...]  # (1, D)
    zdotv = jnp.sum(z * v, axis=1, keepdims=True)  # (1, 1)
    v_acc[...] = v - mu_eff * (zdotv - _tick_get(y_ref, t).astype(f32)) * z

    @pl.when(t == nt - 1)
    def _writeback():
        a_out_ref[...] = a_acc[...].astype(a_out_ref.dtype)
        v_out_ref[...] = v_acc[...].astype(v_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("normalized", "eps", "interpret")
)
def rff_klms_chunk_elements_pallas(
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    mu: jax.Array,
    mask: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    normalized: bool = False,
    eps: float = 1e-6,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-chunk composed KLMS affine elements, one launch for all chunks.

    Args:
      xs: ``(nc, Tc, d)`` time-blocked inputs (kernels/chunking.py layout).
      ys: ``(nc, Tc)`` targets.
      w: ``(d, D)`` shared spectral matrix.
      b: ``(D,)`` shared phases.
      mu: scalar step size (one replayed stream, not a bank sweep).
      mask: optional ``(nc, Tc)`` validity gate (1 = real tick); masked
        ticks compose the identity.
      s: ``(D,)`` per-feature scales; None = Monte-Carlo ``sqrt(2/D)``.
      normalized: NKLMS step sizing ``mu / (eps + ||z||^2)`` — still affine
        because the normalizer depends only on ``z``.

    Returns:
      ``(a (nc, D, D), v (nc, D))`` f32 — chunk c's element maps a state
      entering the chunk to the state leaving it: ``theta -> a theta + v``.
    """
    nc, tlen, d = xs.shape
    dfeat = w.shape[-1]
    assert ys.shape == (nc, tlen)
    assert w.shape == (d, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = jnp.full((dfeat,), float((2.0 / dfeat) ** 0.5), jnp.float32)
    assert s.shape == (dfeat,)
    if mask is None:
        mask = jnp.ones((nc, tlen), jnp.float32)

    dp, np_ = _ceil_to(d, 128), _ceil_to(dfeat, 128)
    xs_p = jnp.pad(xs, ((0, 0), (0, 0), (0, dp - d)))
    w_p = _pad2(w, dp, np_)
    b_p = jnp.pad(b, (0, np_ - dfeat))[None, :]  # (1, Np)
    s_p = jnp.pad(s, (0, np_ - dfeat))[None, :]  # (1, Np), padded scales 0
    mu_p = jnp.broadcast_to(jnp.asarray(mu, jnp.float32), (1, 1))
    mask_p = mask.astype(jnp.float32)[:, None, :]  # (nc, 1, Tc)

    kernel = functools.partial(
        rff_klms_elements_kernel, normalized=normalized, eps=eps
    )
    a, v = _element_call(
        kernel, nc, tlen, dp, np_,
        out_specs=[_lead_block(np_, np_), _lead_block(1, np_)],
        out_shape=[
            jax.ShapeDtypeStruct((nc, np_, np_), jnp.float32),
            jax.ShapeDtypeStruct((nc, 1, np_), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((np_, np_), jnp.float32),
            pltpu.VMEM((1, np_), jnp.float32),
        ],
        interpret=interpret,
    )(xs_p, w_p, b_p, s_p, ys[:, None, :], mu_p, mask_p)
    return a[:, :dfeat, :dfeat], v[:, 0, :dfeat]


def rff_krls_elements_kernel(
    x_ref, w_ref, b_ref, s_ref, y_ref, beta_ref, mask_ref,
    g_out_ref, phi_out_ref, r_out_ref, g_acc, phi_acc, r_acc,
):
    """Grid point (i, t): fold tick t into chunk i's resident (g, Phi, r).

    A masked tick must compose the identity ``(1, 0, 0)``: its decay gate
    becomes exactly 1 and its additive contribution exactly 0.
    """
    f32 = jnp.float32
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _seed():
        g_acc[...] = jnp.ones_like(g_acc)
        phi_acc[...] = jnp.zeros_like(phi_acc)
        r_acc[...] = jnp.zeros_like(r_acc)

    proj = jnp.dot(
        x_ref[pl.ds(t, 1), :].astype(f32),  # tick t of the resident (Tc, d)
        w_ref[...].astype(f32),
        preferred_element_type=f32,
        precision=F32_DOT,
    ) + b_ref[...].astype(f32)
    z = s_ref[...].astype(f32) * jnp.cos(proj)  # (1, D), VMEM-only
    m = _tick_get(mask_ref, t).astype(f32)  # (1, 1)
    beta_eff = jnp.where(m > 0, beta_ref[...].astype(f32), 1.0)  # (1, 1)

    # outer(z, z): contract the unit leading dims — an MXU (D,1)@(1,D).
    outer = jax.lax.dot_general(
        z, z, (((0,), (0,)), ((), ())), preferred_element_type=f32,
        precision=F32_DOT,
    )  # (D, D)
    g_acc[...] = g_acc[...] * beta_eff
    # Scale by scalars, not (1, 1) values: Mosaic cannot broadcast a vector
    # over both tile dims.
    phi_acc[...] = beta_eff[0, 0] * phi_acc[...] + m[0, 0] * outer
    r_acc[...] = beta_eff * r_acc[...] + (m * _tick_get(y_ref, t).astype(f32)) * z

    @pl.when(t == nt - 1)
    def _writeback():
        g_out_ref[...] = g_acc[...].astype(g_out_ref.dtype)
        phi_out_ref[...] = phi_acc[...].astype(phi_out_ref.dtype)
        r_out_ref[...] = r_acc[...].astype(r_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rff_krls_chunk_elements_pallas(
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    beta: jax.Array,
    mask: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-chunk composed KRLS decay elements, one launch for all chunks.

    Args / layout as :func:`rff_klms_chunk_elements_pallas`, ``beta`` the
    scalar forgetting factor.

    Returns:
      ``(g (nc,), phi (nc, D, D), r (nc, D))`` f32 — chunk c's information-
      form element ``(Phi, r) -> (g Phi + phi, g r + r_add)``.
    """
    nc, tlen, d = xs.shape
    dfeat = w.shape[-1]
    assert ys.shape == (nc, tlen)
    assert w.shape == (d, dfeat) and b.shape == (dfeat,)
    if s is None:
        s = jnp.full((dfeat,), float((2.0 / dfeat) ** 0.5), jnp.float32)
    assert s.shape == (dfeat,)
    if mask is None:
        mask = jnp.ones((nc, tlen), jnp.float32)

    dp, np_ = _ceil_to(d, 128), _ceil_to(dfeat, 128)
    xs_p = jnp.pad(xs, ((0, 0), (0, 0), (0, dp - d)))
    w_p = _pad2(w, dp, np_)
    b_p = jnp.pad(b, (0, np_ - dfeat))[None, :]  # (1, Np)
    s_p = jnp.pad(s, (0, np_ - dfeat))[None, :]  # (1, Np), padded scales 0
    beta_p = jnp.broadcast_to(jnp.asarray(beta, jnp.float32), (1, 1))
    mask_p = mask.astype(jnp.float32)[:, None, :]  # (nc, 1, Tc)

    g, phi, r = _element_call(
        rff_krls_elements_kernel, nc, tlen, dp, np_,
        out_specs=[
            _lead_block(1, 1),
            _lead_block(np_, np_),
            _lead_block(1, np_),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nc, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nc, np_, np_), jnp.float32),
            jax.ShapeDtypeStruct((nc, 1, np_), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((np_, np_), jnp.float32),
            pltpu.VMEM((1, np_), jnp.float32),
        ],
        interpret=interpret,
    )(xs_p, w_p, b_p, s_p, ys[:, None, :], beta_p, mask_p)
    return g[:, 0, 0], phi[:, :dfeat, :dfeat], r[:, 0, :dfeat]
