"""Parallel-in-time replay: learner recurrences as associative scan elements.

The paper's fixed-size-state dividend, pushed one level further. Because the
RFF map turns every learner's state into a fixed-size Euclidean object, each
per-tick update is a *structured affine map* on that state — and affine maps
compose associatively. T strictly-sequential ticks therefore rebuild in
O(log T) depth via ``jax.lax.associative_scan`` (the Blelloch up/down sweep
of SNIPPETS.md's ``MatScan``), which is what makes tenant rebuild from a
replay log, bulk import, and recovery after bank-slot eviction
*throughput*-bound instead of latency-bound.

Two element algebras cover every scannable learner in core/:

* **Affine elements** (KLMS / NKLMS): the LMS tick is
  ``theta' = (I - mu z z^T) theta + mu y z`` — an :class:`AffineElement`
  ``(A, v)`` acting as ``theta -> A theta + v``, composed by
  ``(A2 A1, A2 v1 + v2)``. Normalized LMS fits because ``mu_eff`` depends
  only on ``z``. Composition is a (D, D) matmul, so the parallel scan
  trades O(D) extra work for O(T / log T) less depth.
* **Decay elements** (KRLS): Sherman-Morrison order-dependence disappears in
  information form. With ``Phi = P^{-1}`` the EW-RLS recursion is
  ``Phi' = beta Phi + z z^T``, ``r' = beta r + y z`` and
  ``theta = Phi^{-1} r`` — a :class:`DecayElement` ``(g, Phi_add, r_add)``
  whose combine is O(D^2) adds, the *same* order as a sequential tick. The
  one matrix inversion happens once at the end, not once per tick; the
  rank-1 inverse-update order the sequential path commits to is recovered
  only to solver accuracy, so the dense sequential replay
  (:func:`repro.core.krls.rff_krls_run`) stays the fallback where exact
  inversion order matters (tolerances pinned in tests/test_replay.py).

Execution modes (``replay_klms`` / ``replay_krls``):

* ``"sequential"`` — the existing jitted per-tick/chunked drivers; bitwise
  the training path (the rebuild-correctness reference).
* ``"scan"`` — XLA ``associative_scan`` over per-tick elements. O(log T)
  depth; materializes (T, D, D) elements, so it is the small-D/medium-T
  reference implementation.
* ``"blocked"`` — the production path: a time-blocked Pallas kernel
  (kernels/rff_scan.py) composes each chunk's ticks into ONE element on a
  VMEM-resident (D, D) accumulator (the chunk kernels' scratch-residency
  pattern, O(D^2) rank-1 composition per tick), then a short cross-chunk
  ``associative_scan`` over the nc per-chunk elements finishes in
  O(Tc + log nc) depth with only (nc, D, D) materialized.

Non-trig feature families (taylor) run the generic ``featurize`` path under
``"scan"``; ``"blocked"`` requires the canonical affine-trig form and falls
back to ``"scan"`` otherwise.

Padded logs: ``"scan"`` and ``"sequential"`` take a tick ``mask``, so a log
of any length replays at a power-of-two bucket (:func:`pad_log`,
:func:`replay_bucket`) and a server compiles one replay program per bucket,
not one per log length. A masked tick is the identity: the element algebra's
identity under ``"scan"``, a gated tick under ``"sequential"``; the rebuilt
``step`` counts the real ticks only.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.klms import (
    LMSState,
    rff_klms_init,
    rff_klms_run,
    rff_klms_step,
    rff_nklms_step,
)
from repro.core.krls import RLSState, rff_krls_init, rff_krls_run, rff_krls_step
from repro.features.base import (
    FeatureLike,
    as_trig_or_none,
    feature_dtype,
    featurize,
)
from repro.kernels import ops

__all__ = [
    "AffineElement",
    "DecayElement",
    "ScanElement",
    "affine_combine",
    "affine_identity",
    "affine_apply",
    "decay_combine",
    "decay_identity",
    "decay_apply",
    "klms_to_element",
    "nklms_to_element",
    "krls_to_element",
    "klms_scan_element",
    "nklms_scan_element",
    "krls_scan_element",
    "replay_klms",
    "replay_krls",
    "replay_bucket",
    "replay_buckets",
    "pad_log",
    "gated_run",
]


# ---------------------------------------------------------------------------
# Element algebras.
# ---------------------------------------------------------------------------


class AffineElement(NamedTuple):
    """One (or a batch of) affine state maps ``theta -> a @ theta + v``.

    Attributes:
      a: ``(..., D, D)`` linear part (``I - mu z z^T`` for one LMS tick).
      v: ``(..., D)`` offset (``mu y z`` for one LMS tick).
    """

    a: jax.Array
    v: jax.Array


def affine_combine(first: AffineElement, second: AffineElement) -> AffineElement:
    """Compose two affine maps: apply ``first``, then ``second``.

    ``(A2, v2) . (A1, v1) = (A2 A1, A2 v1 + v2)`` — associative, which is
    the whole point. Leading batch axes broadcast (``associative_scan``
    calls this on stacked slices).
    """
    return AffineElement(
        a=jnp.einsum("...ij,...jk->...ik", second.a, first.a),
        v=jnp.einsum("...ij,...j->...i", second.a, first.v) + second.v,
    )


def affine_identity(num_features: int, dtype=jnp.float32) -> AffineElement:
    """The do-nothing tick: ``(I, 0)``."""
    return AffineElement(
        a=jnp.eye(num_features, dtype=dtype),
        v=jnp.zeros((num_features,), dtype),
    )


def affine_apply(element: AffineElement, theta: jax.Array) -> jax.Array:
    """``A theta + v`` — advance a start state through a composed element."""
    return jnp.einsum("...ij,...j->...i", element.a, theta) + element.v


class DecayElement(NamedTuple):
    """Scalar-gated additive maps ``(Phi, r) -> (g Phi + phi, g r + r_add)``.

    The information-form KRLS algebra: one tick contributes
    ``(g=beta, phi=z z^T, r=y z)``. Composition stays O(D^2) — no matmul —
    so the parallel scan costs the same work as the sequential recursion.
    """

    g: jax.Array  # (...,) scalar decay
    phi: jax.Array  # (..., D, D) additive information matrix
    r: jax.Array  # (..., D) additive information vector


def decay_combine(first: DecayElement, second: DecayElement) -> DecayElement:
    """Compose two decay elements: apply ``first``, then ``second``."""
    g2 = second.g
    return DecayElement(
        g=g2 * first.g,
        phi=g2[..., None, None] * first.phi + second.phi,
        r=g2[..., None] * first.r + second.r,
    )


def decay_identity(num_features: int, dtype=jnp.float32) -> DecayElement:
    """The do-nothing tick: ``(1, 0, 0)``."""
    return DecayElement(
        g=jnp.ones((), dtype),
        phi=jnp.zeros((num_features, num_features), dtype),
        r=jnp.zeros((num_features,), dtype),
    )


def decay_apply(
    element: DecayElement, phi0: jax.Array, r0: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Advance start information state ``(Phi_0, r_0)`` through an element."""
    return (
        element.g[..., None, None] * phi0 + element.phi,
        element.g[..., None] * r0 + element.r,
    )


# ---------------------------------------------------------------------------
# Per-learner tick elements.
# ---------------------------------------------------------------------------


def klms_to_element(z: jax.Array, y: jax.Array, mu) -> AffineElement:
    """One KLMS tick as an affine element: ``(I - mu z z^T, mu y z)``.

    ``z`` ``(..., D)`` featurized inputs, ``y`` ``(...,)`` targets; leading
    axes batch (build all T tick elements in one call).
    """
    dfeat = z.shape[-1]
    eye = jnp.eye(dfeat, dtype=z.dtype)
    mu = jnp.asarray(mu, z.dtype)
    a = eye - mu * z[..., :, None] * z[..., None, :]
    return AffineElement(a=a, v=mu * y[..., None] * z)


def nklms_to_element(
    z: jax.Array, y: jax.Array, mu, eps: float = 1e-6
) -> AffineElement:
    """One normalized-LMS tick: ``mu_eff = mu / (eps + ||z||^2)`` — still
    affine in theta because the normalizer depends only on ``z``."""
    mu_eff = jnp.asarray(mu, z.dtype) / (
        eps + jnp.sum(z * z, axis=-1, keepdims=True)
    )
    a = (
        jnp.eye(z.shape[-1], dtype=z.dtype)
        - mu_eff[..., None] * z[..., :, None] * z[..., None, :]
    )
    return AffineElement(a=a, v=mu_eff * y[..., None] * z)


def krls_to_element(z: jax.Array, y: jax.Array, beta) -> DecayElement:
    """One EW-RLS tick in information form: ``(beta, z z^T, y z)``."""
    beta = jnp.asarray(beta, z.dtype)
    return DecayElement(
        g=jnp.broadcast_to(beta, z.shape[:-1]),
        phi=z[..., :, None] * z[..., None, :],
        r=y[..., None] * z,
    )


# ---------------------------------------------------------------------------
# The ScanElement contract — one bundle per learner family, carried by
# core.learner.OnlineLearner so drivers can replay any scannable learner
# without branching on the algorithm.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ScanElement:
    """A learner recurrence packaged as an associative algebra.

    Attributes:
      to_element: ``(z, y) -> element`` — one tick (hyperparams closed
        over), batched over leading axes.
      combine: associative ``(first, second) -> element`` composition.
      identity: ``(num_features, dtype) -> element`` — the no-op tick.
      apply: ``(element, state) -> state`` — advance a learner state
        through a composed element (the only non-element-space step).
    """

    to_element: Callable
    combine: Callable
    identity: Callable
    apply: Callable


def _affine_apply_state(element: AffineElement, state: LMSState) -> LMSState:
    """Advance an :class:`LMSState` through a composed affine element.

    A composed element has no memory of how many ticks it folded, so step
    accounting is the driver's job (``replay_*`` add the log length)."""
    return LMSState(theta=affine_apply(element, state.theta), step=state.step)


def klms_scan_element(mu: float) -> ScanElement:
    """The KLMS recurrence as a :class:`ScanElement` (fixed ``mu``)."""
    return ScanElement(
        to_element=lambda z, y: klms_to_element(z, y, mu),
        combine=affine_combine,
        identity=affine_identity,
        apply=_affine_apply_state,
    )


def nklms_scan_element(mu: float, eps: float = 1e-6) -> ScanElement:
    """The normalized-KLMS recurrence as a :class:`ScanElement`."""
    return ScanElement(
        to_element=lambda z, y: nklms_to_element(z, y, mu, eps),
        combine=affine_combine,
        identity=affine_identity,
        apply=_affine_apply_state,
    )


def krls_scan_element(beta: float) -> ScanElement:
    """The EW-RLS recurrence (information form) as a :class:`ScanElement`.

    ``apply`` converts the composed element back to covariance form with one
    solve + one inversion — see :func:`_decay_to_rls` for the numerics.
    """
    return ScanElement(
        to_element=lambda z, y: krls_to_element(z, y, beta),
        combine=decay_combine,
        identity=decay_identity,
        apply=_decay_apply_state,
    )


def _decay_to_rls(
    phi: jax.Array, r: jax.Array, step: jax.Array
) -> RLSState:
    """Information form -> covariance form: ``theta = Phi^{-1} r``,
    ``P = Phi^{-1}`` (symmetrized, same hygiene as the sequential path)."""
    pmat = jnp.linalg.inv(phi)
    pmat = 0.5 * (pmat + pmat.T)
    theta = jnp.linalg.solve(phi, r)
    return RLSState(theta=theta, pmat=pmat, step=step)


def _decay_apply_state(element: DecayElement, state: RLSState) -> RLSState:
    """Advance an :class:`RLSState` through a composed decay element.

    The start covariance is inverted once (``Phi_0 = P_0^{-1}``,
    ``r_0 = Phi_0 theta_0``) — exact for the fresh ``P_0 = I / lam`` and
    solver-accurate for warm starts. Step accounting is the driver's job
    (a composed element has no memory of how many ticks it folded).
    """
    phi0 = jnp.linalg.inv(state.pmat)
    phi0 = 0.5 * (phi0 + phi0.T)
    r0 = phi0 @ state.theta
    phi, r = decay_apply(element, phi0, r0)
    return _decay_to_rls(phi, r, state.step)


# ---------------------------------------------------------------------------
# Replay drivers — rebuild a learner state from a (xs, ys) log.
# ---------------------------------------------------------------------------


def _last(tree):
    return jax.tree.map(lambda a: a[-1], tree)


def replay_bucket(n: int, capacity: int) -> int:
    """The padded length a log of ``n`` ticks replays at: the next power of
    two, at most ``capacity`` (the log's ring size), never below ``n``."""
    return max(n, min(1 << max(n - 1, 0).bit_length(), capacity))


def replay_buckets(capacity: int) -> list[int]:
    """Every bucket a log ring of ``capacity`` replays at: 1, 2, 4, ... and
    ``capacity``."""
    out = [1]
    while out[-1] < capacity:
        out.append(min(2 * out[-1], capacity))
    return out


def pad_log(xs, ys, bucket: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A host log ``xs (n, d)``, ``ys (n,)`` zero-padded to ``bucket`` ticks,
    and the ``(bucket,)`` mask that marks the ``n`` real ones."""
    xs, ys = np.asarray(xs), np.asarray(ys)
    n = len(ys)
    xs_p = np.zeros((bucket, xs.shape[-1]), xs.dtype)
    ys_p = np.zeros((bucket,), ys.dtype)
    xs_p[:n], ys_p[:n] = xs, ys
    return xs_p, ys_p, np.arange(bucket) < n


def _gate(mask: jax.Array, new, old):
    """``new`` where ``mask`` (leading axes) is set, else ``old``."""
    def leaf(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))
        return jnp.where(m, a, b)

    return jax.tree.map(leaf, new, old)


def gated_run(step_fn: Callable, state, xs, ys, mask):
    """Sequential replay ``(state, x, y) -> (state, out)`` over a padded
    log: a masked-off tick leaves every state leaf as it was (the chunk
    kernels' masked tick), so the real ticks run exactly as unpadded."""

    def body(s, xym):
        x, y, m = xym
        return _gate(m, step_fn(s, x, y)[0], s), None

    return jax.lax.scan(body, state, (xs, ys, jnp.asarray(mask)))[0]


def _ticks(xs: jax.Array, mask) -> jax.Array:
    """Real ticks of a (possibly padded) log, as the state's int32 step."""
    if mask is None:
        return jnp.asarray(xs.shape[0], jnp.int32)
    return jnp.sum(jnp.asarray(mask), dtype=jnp.int32)


def replay_klms(
    rff: FeatureLike,
    xs: jax.Array,
    ys: jax.Array,
    mu,
    state: Optional[LMSState] = None,
    mode: str = "scan",
    chunk: Optional[int] = None,
    normalized: bool = False,
    eps: float = 1e-6,
    kernel_mode: str = "auto",
    mask: Optional[jax.Array] = None,
) -> LMSState:
    """Rebuild a KLMS state from a replay log ``xs (T, d)``, ``ys (T,)``.

    ``mode``:
      * ``"sequential"`` — jitted per-tick scan (:func:`rff_klms_run`);
        bitwise the training path.
      * ``"scan"`` — per-tick affine elements + ``associative_scan``
        (O(log T) depth, (T, D, D) element memory).
      * ``"blocked"`` — Pallas per-chunk element composition + short
        cross-chunk scan (O(Tc + log nc) depth, (nc, D, D) memory);
        ``chunk=None`` picks the element-aware VMEM-budget default.

    Non-sequential modes match the sequential trajectory to reassociation
    rounding (pinned in tests/test_replay.py), not bitwise — composing
    ``A_t`` products reorders the floating-point work by design.

    ``mask`` ``(T,)`` (``"scan"`` / ``"sequential"``): ticks where it is
    False are padding and replay as the identity (:func:`pad_log`).
    """
    if state is None:
        state = rff_klms_init(rff.num_features, feature_dtype(rff))
    if mask is not None and mode == "blocked":
        raise ValueError("the blocked replay takes no tick mask")
    if mode == "sequential":
        if mask is not None:
            step = rff_nklms_step if normalized else rff_klms_step
            hp = (mu, eps) if normalized else (mu,)
            return gated_run(
                lambda s, x, y: step(s, (x, y), rff, *hp), state, xs, ys, mask
            )
        final, _ = rff_klms_run(
            rff, xs, ys, mu, state=state, normalized=normalized
        )
        return final
    tf = as_trig_or_none(rff)
    if mode == "blocked" and tf is None:
        mode = "scan"  # non-trig families have no fused kernel form
    if mode == "scan":
        fm = rff if tf is None else tf
        z = featurize(fm, xs)  # (T, D) — one GEMM
        to_el = nklms_to_element if normalized else klms_to_element
        args = (mu, eps) if normalized else (mu,)
        elements = to_el(z, ys, *args)
        if mask is not None:
            elements = _gate(
                jnp.asarray(mask), elements,
                affine_identity(z.shape[-1], z.dtype),
            )
        composed = _last(jax.lax.associative_scan(affine_combine, elements))
    elif mode == "blocked":
        a, v = ops.rff_klms_chunk_elements(
            xs, ys, tf.omega, tf.bias, mu, tf.scale,
            mode=kernel_mode, chunk=chunk, normalized=normalized, eps=eps,
        )
        composed = _last(
            jax.lax.associative_scan(affine_combine, AffineElement(a, v))
        )
    else:
        raise ValueError(f"unknown replay mode {mode!r}")
    return LMSState(
        theta=affine_apply(composed, state.theta),
        step=state.step + _ticks(xs, mask),
    )


def replay_krls(
    rff: FeatureLike,
    xs: jax.Array,
    ys: jax.Array,
    lam: float = 1e-4,
    beta: float = 0.9995,
    state: Optional[RLSState] = None,
    mode: str = "scan",
    chunk: Optional[int] = None,
    kernel_mode: str = "auto",
    mask: Optional[jax.Array] = None,
) -> RLSState:
    """Rebuild a KRLS state from a replay log ``xs (T, d)``, ``ys (T,)``.

    ``mode`` as :func:`replay_klms`, with ``"sequential"`` the dense
    Sherman-Morrison replay (:func:`rff_krls_run`) — the fallback where
    exact inversion order matters. Scan modes accumulate the information
    form and invert ONCE; they track the sequential trajectory to solver
    accuracy (<= 1e-5 f32 / 1e-8 f64 over >= 1024 ticks, pinned in
    tests/test_replay.py). ``mask`` as :func:`replay_klms`.
    """
    if mask is not None and mode == "blocked":
        raise ValueError("the blocked replay takes no tick mask")
    if mode == "sequential":
        if mask is not None:
            if state is None:
                state = rff_krls_init(rff.num_features, lam, feature_dtype(rff))
            return gated_run(
                lambda s, x, y: rff_krls_step(s, (x, y), rff, beta),
                state, xs, ys, mask,
            )
        final, _ = rff_krls_run(
            rff, xs, ys, lam=lam, beta=beta, state=state
        )
        return final
    dtype = feature_dtype(rff)
    tf = as_trig_or_none(rff)
    if mode == "blocked" and tf is None:
        mode = "scan"
    if mode == "scan":
        fm = rff if tf is None else tf
        z = featurize(fm, xs)  # (T, D) — one GEMM
        elements = krls_to_element(z, ys, beta)
        if mask is not None:
            elements = _gate(
                jnp.asarray(mask), elements,
                decay_identity(z.shape[-1], z.dtype),
            )
        composed = _last(jax.lax.associative_scan(decay_combine, elements))
    elif mode == "blocked":
        g, phi, r = ops.rff_krls_chunk_elements(
            xs, ys, tf.omega, tf.bias, beta, tf.scale,
            mode=kernel_mode, chunk=chunk,
        )
        composed = _last(
            jax.lax.associative_scan(decay_combine, DecayElement(g, phi, r))
        )
    else:
        raise ValueError(f"unknown replay mode {mode!r}")
    dfeat = rff.num_features
    if state is None:
        # Fresh start: Phi_0 = lam I exactly — no inversion needed.
        phi0 = lam * jnp.eye(dfeat, dtype=dtype)
        phi, r = decay_apply(composed, phi0, jnp.zeros((dfeat,), dtype))
        return _decay_to_rls(phi, r, _ticks(xs, mask))
    final = _decay_apply_state(composed, state)
    return final._replace(step=state.step + _ticks(xs, mask))
