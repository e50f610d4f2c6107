"""RFFKRLS — paper §6: exponentially-weighted RLS on RFF-mapped data.

"One only needs to choose the random samples omega_i and replace the
instances of x_n in the standard RLS algorithm with z_Omega(x_n)." The state
is a fixed ``(D,)`` weight vector plus a fixed ``(D, D)`` inverse-correlation
matrix — size independent of the stream length (contrast Engel's KRLS whose
kernel matrices grow with the dictionary).

Standard EW-RLS recursions (forgetting factor beta, regularizer lam):

    P_0   = I / lam
    z     = z_Omega(x_n)
    e     = y_n - theta^T z
    g     = P z / (beta + z^T P z)
    theta <- theta + g e
    P     <- (P - g z^T P) / beta

Per-step cost O(D^2) — fixed, vs O(M_n^2) growing for Engel's KRLS.

Sharded variant (this module's second half): the dense ``(D, D)`` matrix
``P`` is the only state that outgrows a single chip. Because the RFF
formulation keeps every quantity a fixed Euclidean object (Bouboulis et al.
2017 use exactly this to distribute KLMS over networks), ``P`` partitions
cleanly into row blocks ``(D/n, D)`` over a mesh axis, and the rank-1 RLS
update needs ONE ``psum`` per tick — see :func:`sharded_krls_run`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.klms import StepOut
from repro.features.base import (
    FeatureLike,
    TrigFeatures,
    as_trig,
    feature_dtype,
    featurize,
)
from repro.kernels.chunking import time_blocks, unblock_time, valid_time_mask
from repro.kernels.ref import F32_DOT

__all__ = [
    "RLSState",
    "rff_krls_init",
    "rff_krls_step",
    "rff_krls_run",
    "KRLS_SHARD_AXIS",
    "krls_state_specs",
    "krls_feature_specs",
    "shard_krls_rff",
    "sharded_krls_init",
    "make_sharded_krls_step",
    "make_sharded_krls_block_step",
    "make_sharded_krls_predict",
    "sharded_krls_run",
]


# Every f32 product here asks for full f32 precision: a TPU otherwise runs
# it as one bf16 pass, far outside the state's f32 contract.
_mm = functools.partial(jnp.matmul, precision=F32_DOT)


class RLSState(NamedTuple):
    theta: jax.Array  # (D,)
    pmat: jax.Array  # (D, D) inverse correlation estimate
    step: jax.Array  # () int32


def rff_krls_init(
    num_features: int, lam: float = 1e-4, dtype: jnp.dtype = jnp.float32
) -> RLSState:
    return RLSState(
        theta=jnp.zeros((num_features,), dtype),
        pmat=jnp.eye(num_features, dtype=dtype) / lam,
        step=jnp.zeros((), jnp.int32),
    )


def rls_step(
    theta: jax.Array,
    pmat: jax.Array,
    z: jax.Array,
    y: jax.Array,
    beta: float,
) -> tuple[jax.Array, jax.Array, StepOut]:
    """One EW-RLS update in feature space; returns (theta, P, out)."""
    y_hat = _mm(theta, z)
    err = y - y_hat
    pz = _mm(pmat, z)
    denom = beta + _mm(z, pz)
    gain = pz / denom
    theta = theta + gain * err
    pmat = (pmat - jnp.outer(gain, pz)) / beta
    # Symmetrize to fight drift over long streams (numerical hygiene).
    pmat = 0.5 * (pmat + pmat.T)
    return theta, pmat, StepOut(prediction=y_hat, error=err)


def rff_krls_step(
    state: RLSState,
    sample: tuple[jax.Array, jax.Array],
    rff: FeatureLike,
    beta: float = 0.9995,
) -> tuple[RLSState, StepOut]:
    x, y = sample
    z = featurize(rff, x)
    theta, pmat, out = rls_step(state.theta, state.pmat, z, y, beta)
    return RLSState(theta=theta, pmat=pmat, step=state.step + 1), out


def rff_krls_run(
    rff: FeatureLike,
    xs: jax.Array,
    ys: jax.Array,
    lam: float = 1e-4,
    beta: float = 0.9995,
    state: RLSState | None = None,
    chunk: int | None = None,
) -> tuple[RLSState, StepOut]:
    """Stream driver. Paper §6 settings: lam=1e-4, beta=0.9995, D=300.

    ``chunk=T`` scans over T-tick chunks: each chunk featurizes its T
    samples in one ``(T, d) @ (d, D)`` GEMM and replays the sequential RLS
    recursion over the precomputed rows (zero-masked final remainder).
    Matches the per-tick scan to feature-GEMM rounding (tested).
    """
    if state is None:
        state = rff_krls_init(rff.num_features, lam, feature_dtype(rff))
    if chunk is not None:
        return _rff_krls_run_chunked(rff, xs, ys, beta, state, chunk)

    def body(s, xy):
        return rff_krls_step(s, xy, rff, beta)

    return jax.lax.scan(body, state, (xs, ys))


def _rff_krls_run_chunked(
    rff: FeatureLike,
    xs: jax.Array,
    ys: jax.Array,
    beta: float,
    state: RLSState,
    chunk: int,
) -> tuple[RLSState, StepOut]:
    """Chunked scan: featurize T samples per GEMM, replay ticks in-chunk."""
    n = xs.shape[0]
    xs_c = time_blocks(xs, chunk)
    ys_c = time_blocks(ys, chunk)
    mask_c = valid_time_mask(n, chunk, xs.dtype)

    def body(s: RLSState, args):
        xc, yc, mc = args
        zc = featurize(rff, xc)  # (T, D) — one GEMM per chunk

        def tick(st: RLSState, zym):
            z, y, m = zym
            theta, pmat, out = rls_step(st.theta, st.pmat, z, y, beta)
            keep = m > 0
            return (
                RLSState(
                    theta=jnp.where(keep, theta, st.theta),
                    pmat=jnp.where(keep, pmat, st.pmat),
                    step=st.step + m.astype(st.step.dtype),
                ),
                out,
            )

        return jax.lax.scan(tick, s, (zc, yc, mc))

    state, outs = jax.lax.scan(body, state, (xs_c, ys_c, mask_c))
    return state, jax.tree.map(lambda a: unblock_time(a, n), outs)


# ---------------------------------------------------------------------------
# Sharded RFF-KRLS — partition P (and the feature bank) over a mesh axis.
#
# Layout (mesh axis ``shard``, n = axis size, Dn = D / n). The feature bank
# is the canonical affine-trig form (repro.features.as_trig), so any trig
# family — RFF, ORF, QMC, weighted Gaussian quadrature — shards identically:
#   omega (d, D)  -> column blocks (d, Dn)   each shard owns features rows_i
#   bias  (D,)    -> blocks (Dn,)
#   scale (D,)    -> blocks (Dn,)            per-feature quadrature weights
#   theta (D,)    -> row blocks (Dn,)
#   P     (D, D)  -> row blocks (Dn, D)      per-shard bytes: 4*D*Dn
#
# Per tick, each shard featurizes only its slice ``z_i`` and computes
#   pz_partial = z_i @ P[rows_i, :]          (valid because P is symmetric:
#                                             Pz = P^T z = sum_i P_i^T z_i)
#   yhat_partial = theta_i @ z_i
# One psum of the packed ``(2D + 1,)`` vector [pz_partial, scatter(z_i),
# yhat_partial] then gives every shard the full ``Pz``, the full ``z`` and
# the prediction; the gain, theta update and the (Dn, D) outer-product
# downdate are pure local work. The downdate is applied in the exactly
# symmetric form ``(pz_i pz_j) * (1/denom)`` (commutative products round
# identically on both sides of the diagonal), so P stays bitwise symmetric
# without the dense path's explicit re-symmetrization pass — which is what
# licenses the ``z_i @ P_i`` transpose trick above.
# ---------------------------------------------------------------------------

KRLS_SHARD_AXIS = "shard"


def krls_state_specs(axis: str = KRLS_SHARD_AXIS) -> RLSState:
    """PartitionSpecs for RLSState: theta/P row-sharded, step replicated."""
    return RLSState(theta=P(axis), pmat=P(axis, None), step=P())


def krls_feature_specs(axis: str = KRLS_SHARD_AXIS) -> TrigFeatures:
    """PartitionSpecs for the canonical trig feature bank: omega/bias/scale
    column-sharded (each shard featurizes exactly its P row block's slice)."""
    return TrigFeatures(omega=P(None, axis), bias=P(axis), scale=P(axis))


def shard_krls_rff(
    mesh: Mesh, rff: FeatureLike, axis: str = KRLS_SHARD_AXIS
) -> TrigFeatures:
    """Canonicalize to the affine-trig form and place it with feature
    columns partitioned over ``axis``. Any trig family works (RFF, ORF, QMC,
    GQ); non-trig families (Taylor) have no column decomposition of the
    featurize GEMM and raise here."""
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        as_trig(rff),
        krls_feature_specs(axis),
    )


def sharded_krls_init(
    mesh: Mesh,
    num_features: int,
    lam: float = 1e-4,
    dtype: jnp.dtype = jnp.float32,
    axis: str = KRLS_SHARD_AXIS,
) -> RLSState:
    """``rff_krls_init`` placed row-sharded over ``axis`` (D must divide)."""
    n = mesh.shape[axis]
    if num_features % n:
        raise ValueError(
            f"num_features={num_features} must divide the {axis!r} axis ({n})"
        )
    state = rff_krls_init(num_features, lam, dtype)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        state,
        krls_state_specs(axis),
    )


def _sharded_rls_tick(
    theta_l: jax.Array,  # (Dn,) local row block
    pmat_l: jax.Array,  # (Dn, D) local row block
    omega_l: jax.Array,  # (d, Dn) local feature columns
    bias_l: jax.Array,  # (Dn,)
    scale_l: jax.Array,  # (Dn,) local per-feature scales
    x: jax.Array,  # (d,) replicated
    y: jax.Array,  # () replicated
    beta: float,
    axis: str,
    num_features: int,
) -> tuple[jax.Array, jax.Array, StepOut]:
    """One sharded EW-RLS update; exactly one psum over ``axis``."""
    dfull = num_features
    dloc = theta_l.shape[0]
    offset = jax.lax.axis_index(axis) * dloc

    z_l = scale_l * jnp.cos(_mm(x, omega_l) + bias_l)  # (Dn,) local slice

    pz_part = _mm(z_l, pmat_l)  # (D,) — P^T z contribution of our rows (P sym)
    yhat_part = _mm(z_l, theta_l)  # () partial prediction
    z_scat = jax.lax.dynamic_update_slice(
        jnp.zeros((dfull,), z_l.dtype), z_l, (offset,)
    )
    packed = jnp.concatenate([pz_part, z_scat, yhat_part[None]])
    packed = jax.lax.psum(packed, axis)  # the tick's one collective

    pz = packed[:dfull]
    z = packed[dfull : 2 * dfull]
    y_hat = packed[2 * dfull]
    err = y - y_hat
    inv_denom = 1.0 / (beta + _mm(z, pz))

    pz_l = jax.lax.dynamic_slice(pz, (offset,), (dloc,))
    theta_l = theta_l + (err * inv_denom) * pz_l
    pmat_l = (pmat_l - jnp.outer(pz_l, pz) * inv_denom) / beta
    return theta_l, pmat_l, StepOut(prediction=y_hat, error=err)


def _sharded_rls_block_tick(
    theta_l: jax.Array,  # (Dn,) local row block
    pmat_l: jax.Array,  # (Dn, D) local row block
    omega_l: jax.Array,  # (d, Dn) local feature columns
    bias_l: jax.Array,  # (Dn,)
    scale_l: jax.Array,  # (Dn,) local per-feature scales
    xs: jax.Array,  # (k, d) replicated block of samples
    ys: jax.Array,  # (k,) replicated
    mask: jax.Array,  # (k,) replicated validity gate (1 = real tick)
    beta: float,
    axis: str,
    num_features: int,
) -> tuple[jax.Array, jax.Array, StepOut]:
    """k sharded EW-RLS ticks with ONE psum — the combine_every block.

    The per-tick path pays one ``(2D+1,)`` psum per sample. Here each shard
    featurizes its slice for all k samples, contributes the *block-start*
    partial matvecs ``P_0^T z_j`` and predictions ``theta_0 . z_j``, and a
    single packed ``(k, 2D+1)`` psum replicates them. The k-tick recursion
    is then replayed exactly from those block-start quantities:

        P_{i+1} z = (P_i z - (pz_i . z / denom_i) pz_i) / beta
        theta_{i+1} . z = theta_i . z + (e_i / denom_i)(pz_i . z)

    i.e. every per-tick ``pz_j = P_j z_j``, gain denominator and prior error
    is an O(k^2 D) combination of the psum'd vectors — pure replicated
    local work, no further collectives. The restructuring is algebraically
    EXACT (this is the fixed-size-state dividend: k rank-1 updates commute
    into closed form); only floating-point summation order differs from the
    per-tick path, and tests bound that drift at 1e-5 f32 / 1e-8 f64.
    Masked ticks (mask=0) contribute nothing and skip their downdate.
    """
    k = xs.shape[0]
    dfull = num_features
    dloc = theta_l.shape[0]
    offset = jax.lax.axis_index(axis) * dloc

    z_l = scale_l * jnp.cos(_mm(xs, omega_l) + bias_l)  # (k, Dn) local slices
    pz0_part = _mm(z_l, pmat_l)  # (k, D) — P_0^T z_j contributions (P sym)
    yhat0_part = _mm(z_l, theta_l)  # (k,) partial block-start predictions
    zero = jnp.zeros((), offset.dtype)  # match axis_index dtype under x64
    z_scat = jax.lax.dynamic_update_slice(
        jnp.zeros((k, dfull), z_l.dtype), z_l, (zero, offset)
    )
    packed = jnp.concatenate(
        [pz0_part, z_scat, yhat0_part[:, None]], axis=1
    )
    packed = jax.lax.psum(packed, axis)  # the block's ONE collective

    pz0 = packed[:, :dfull]  # (k, D) P_0 z_j
    z = packed[:, dfull : 2 * dfull]  # (k, D) full feature vectors
    yhat0 = packed[:, 2 * dfull]  # (k,) theta_0 . z_j

    # Replicated replay (k is static -> unrolled; O(k^2 D) VPU work).
    pzs, inv_dens, errs_m, preds, errs = [], [], [], [], []
    for j in range(k):
        v = pz0[j]
        yh = yhat0[j]
        for i in range(j):
            c = _mm(pzs[i], z[j])
            corr = c * inv_dens[i]
            v = (v - (mask[i] * corr) * pzs[i]) / jnp.where(
                mask[i] > 0, beta, 1.0
            )
            yh = yh + errs_m[i] * corr
        inv_den = 1.0 / (beta + _mm(z[j], v))
        e = ys[j] - yh
        pzs.append(v)
        inv_dens.append(inv_den)
        errs_m.append(mask[j] * e)
        preds.append(yh)
        errs.append(e)

    # Local state: theta additions commute into one (k,) @ (k, Dn) matvec;
    # P downdates replay in order with the exactly-symmetric (pz_i pz_j)
    # form (same commutative-rounding argument as the per-tick path).
    pz_mat = jnp.stack(pzs)  # (k, D)
    pz_loc = jax.lax.dynamic_slice(pz_mat, (zero, offset), (k, dloc))
    coeff = jnp.stack(errs_m) * jnp.stack(inv_dens)  # (k,)
    theta_l = theta_l + _mm(coeff, pz_loc)
    for j in range(k):
        downd = (
            pmat_l - jnp.outer(pz_loc[j], pz_mat[j]) * inv_dens[j]
        ) / beta
        pmat_l = jnp.where(mask[j] > 0, downd, pmat_l)
    return theta_l, pmat_l, StepOut(
        prediction=jnp.stack(preds), error=jnp.stack(errs)
    )


def make_sharded_krls_step(
    mesh: Mesh,
    rff: FeatureLike,
    beta: float = 0.9995,
    axis: str = KRLS_SHARD_AXIS,
):
    """Jitted one-tick function ``(state, x, y) -> (state, StepOut)``.

    ``rff`` is any trig-canonical feature map, given unsharded; it is placed
    via :func:`shard_krls_rff` and closed over. State arrays must carry the
    :func:`krls_state_specs` layout (use :func:`sharded_krls_init`).
    """
    tf = shard_krls_rff(mesh, rff, axis)
    dfull = tf.num_features
    sspec = krls_state_specs(axis)
    fspec = krls_feature_specs(axis)

    def body(omega_l, bias_l, scale_l, theta_l, pmat_l, step, x, y):
        theta_l, pmat_l, out = _sharded_rls_tick(
            theta_l, pmat_l, omega_l, bias_l, scale_l, x, y, beta, axis,
            dfull,
        )
        return theta_l, pmat_l, step + 1, out

    shmapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            fspec.omega, fspec.bias, fspec.scale,
            sspec.theta, sspec.pmat, sspec.step,
            P(), P(),
        ),
        out_specs=(sspec.theta, sspec.pmat, sspec.step, P()),
    )

    @jax.jit
    def step_fn(state: RLSState, x: jax.Array, y: jax.Array):
        theta, pmat, step, out = shmapped(
            tf.omega, tf.bias, tf.scale,
            state.theta, state.pmat, state.step, x, y,
        )
        return RLSState(theta=theta, pmat=pmat, step=step), out

    return step_fn


def make_sharded_krls_block_step(
    mesh: Mesh,
    rff: FeatureLike,
    beta: float = 0.9995,
    combine_every: int = 8,
    axis: str = KRLS_SHARD_AXIS,
):
    """Jitted k-tick function ``(state, xs (k, d), ys (k,)) -> (state,
    StepOut (k,))`` issuing ONE psum per k ticks (``combine_every``).

    The DCN-deployment form of :func:`make_sharded_krls_step`: collective
    count drops k-fold while the update stays algebraically exact (see
    :func:`_sharded_rls_block_tick` for the replay construction and its
    drift bound).
    """
    tf = shard_krls_rff(mesh, rff, axis)
    dfull = tf.num_features
    k = combine_every
    sspec = krls_state_specs(axis)
    fspec = krls_feature_specs(axis)

    def body(omega_l, bias_l, scale_l, theta_l, pmat_l, step, xs, ys):
        mask = jnp.ones((k,), xs.dtype)
        theta_l, pmat_l, out = _sharded_rls_block_tick(
            theta_l, pmat_l, omega_l, bias_l, scale_l, xs, ys, mask, beta,
            axis, dfull,
        )
        return theta_l, pmat_l, step + k, out

    shmapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            fspec.omega, fspec.bias, fspec.scale,
            sspec.theta, sspec.pmat, sspec.step,
            P(), P(),
        ),
        out_specs=(sspec.theta, sspec.pmat, sspec.step, P()),
    )

    @jax.jit
    def block_step_fn(state: RLSState, xs: jax.Array, ys: jax.Array):
        theta, pmat, step, out = shmapped(
            tf.omega, tf.bias, tf.scale,
            state.theta, state.pmat, state.step, xs, ys,
        )
        return RLSState(theta=theta, pmat=pmat, step=step), out

    return block_step_fn


def make_sharded_krls_predict(
    mesh: Mesh, rff: FeatureLike, axis: str = KRLS_SHARD_AXIS
):
    """Jitted ``(state, x) -> y_hat`` on the sharded layout (one psum)."""
    tf = shard_krls_rff(mesh, rff, axis)
    fspec = krls_feature_specs(axis)

    def body(omega_l, bias_l, scale_l, theta_l, x):
        z_l = scale_l * jnp.cos(_mm(x, omega_l) + bias_l)
        return jax.lax.psum(_mm(z_l, theta_l), axis)

    shmapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(fspec.omega, fspec.bias, fspec.scale, P(axis), P()),
        out_specs=P(),
    )

    @jax.jit
    def predict_fn(state: RLSState, x: jax.Array) -> jax.Array:
        return shmapped(tf.omega, tf.bias, tf.scale, state.theta, x)

    return predict_fn


@functools.lru_cache(maxsize=None)
def _sharded_krls_run_program(
    mesh: Mesh, axis: str, beta: float, dfull: int, combine_every: int = 1
):
    """Build (and cache) the jitted whole-stream program for one
    (mesh, axis, beta, D, k) — repeat drivers re-use the compiled scan.

    ``combine_every == 1`` scans per-tick ticks (one psum each);
    ``combine_every == k`` scans k-tick blocks (one packed psum each) and
    takes an extra replicated ``mask (nblocks, k)`` input for the
    zero-padded final block.
    """
    sspec = krls_state_specs(axis)
    k = combine_every

    fspec = krls_feature_specs(axis)
    if k == 1:

        def node(omega_l, bias_l, scale_l, theta_l, pmat_l, step, xs, ys):
            def body(carry, xy):
                th, pm = carry
                x, y = xy
                th, pm, out = _sharded_rls_tick(
                    th, pm, omega_l, bias_l, scale_l, x, y, beta, axis, dfull
                )
                return (th, pm), out

            # The row blocks enter already varying over ``axis``, so the
            # scan carry types match without a cast.
            (theta_l, pmat_l), outs = jax.lax.scan(
                body, (theta_l, pmat_l), (xs, ys)
            )
            return theta_l, pmat_l, step + xs.shape[0], outs

        in_specs = (
            fspec.omega, fspec.bias, fspec.scale,
            sspec.theta, sspec.pmat, sspec.step,
            P(), P(),
        )
    else:

        def node(
            omega_l, bias_l, scale_l, theta_l, pmat_l, step, xs, ys, mask
        ):
            def body(carry, xym):
                th, pm = carry
                xb, yb, mb = xym
                th, pm, out = _sharded_rls_block_tick(
                    th, pm, omega_l, bias_l, scale_l, xb, yb, mb, beta,
                    axis, dfull,
                )
                return (th, pm), out

            (theta_l, pmat_l), outs = jax.lax.scan(
                body, (theta_l, pmat_l), (xs, ys, mask)
            )
            outs = jax.tree.map(lambda a: a.reshape(-1), outs)
            ticks = jnp.sum(mask).astype(step.dtype)
            return theta_l, pmat_l, step + ticks, outs

        in_specs = (
            fspec.omega, fspec.bias, fspec.scale,
            sspec.theta, sspec.pmat, sspec.step,
            P(), P(), P(),
        )

    shmapped = jax.shard_map(
        node,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(sspec.theta, sspec.pmat, sspec.step, P()),
    )
    return jax.jit(shmapped)


def sharded_krls_run(
    mesh: Mesh,
    rff: FeatureLike,
    xs: jax.Array,
    ys: jax.Array,
    lam: float = 1e-4,
    beta: float = 0.9995,
    state: RLSState | None = None,
    axis: str = KRLS_SHARD_AXIS,
    combine_every: int = 1,
) -> tuple[RLSState, StepOut]:
    """Stream driver on the sharded layout: scan over time *inside* one
    shard_map, so the whole stream is a single program with one psum/tick.

    ``xs (n, d)`` / ``ys (n,)`` are replicated (each tick is one global
    sample — the single-stream setting; the bank engine handles multi-tenant
    batches). Numerically equivalent to :func:`rff_krls_run` to ~1e-5.

    ``combine_every=k`` batches k ticks per psum (the DCN deployment knob):
    the stream is scanned in k-tick blocks through the packed-psum replay
    of :func:`_sharded_rls_block_tick` (zero-masked final block for
    ``n % k``). Exact modulo FP summation order — drift vs the per-tick
    psum is bounded at 1e-5 f32 / 1e-8 f64 in tests.
    """
    if state is None:
        state = sharded_krls_init(
            mesh, rff.num_features, lam, feature_dtype(rff), axis
        )
    tf = shard_krls_rff(mesh, rff, axis)
    program = _sharded_krls_run_program(
        mesh, axis, beta, tf.num_features, combine_every
    )
    if combine_every == 1:
        theta, pmat, step, outs = program(
            tf.omega, tf.bias, tf.scale,
            state.theta, state.pmat, state.step, xs, ys,
        )
        return RLSState(theta=theta, pmat=pmat, step=step), outs

    k = combine_every
    n = xs.shape[0]
    xs_b = time_blocks(xs, k)
    ys_b = time_blocks(ys, k)
    mask_b = valid_time_mask(n, k, xs.dtype)
    theta, pmat, step, outs = program(
        tf.omega, tf.bias, tf.scale,
        state.theta, state.pmat, state.step,
        xs_b, ys_b, mask_b,
    )
    outs = jax.tree.map(lambda a: a[:n], outs)
    return RLSState(theta=theta, pmat=pmat, step=step), outs
