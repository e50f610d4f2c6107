"""Unified ``OnlineLearner`` interface over every kernel adaptive filter.

The five algorithms in core/ (RFFKLMS, normalized RFFKLMS, QKLMS, RFFKRLS,
ALD-KRLS) historically exposed ad-hoc ``*_init/_step/_run`` signatures. This
module wraps each behind one protocol:

    init(key) -> state                    (key ignored by deterministic inits)
    step(state, x, y) -> (state, StepOut) (one online sample)
    run(state, xs, ys) -> (state, StepOut arrays)   (lax.scan stream drive)
    predict(state, x) -> y_hat            (inference, no update)
    rebuild(xs, ys, state, mode) -> state (parallel-in-time replay; falls
                                           back to a sequential run for
                                           learners without scan elements)

so drivers, benchmarks, the vmapped filter bank (core/bank.py) and the
serving loop never branch on the algorithm. Adapters are thin closures over
the existing pure functions — the legacy API stays available and every
adapter is numerically identical to the ``rff_*_run`` it wraps (tested).

The design also makes the *feature family* a constructor argument ("No-Trick
Kernel Adaptive Filtering using Deterministic Features" motivates swapping
RFF for deterministic maps): every RFF-family adapter takes any
:mod:`repro.features` map — the legacy ``RFF`` struct, a canonical
``TrigFeatures``, or a ``FeatureMap`` of any family (rff / orf / qmc / gq /
taylor) — and drives it through the generic ``featurize`` contract. Only
the sharded-KRLS adapter requires a trig-canonical family (its shard_map
program inlines the affine-trig activation).

An ``OnlineLearner`` is a static bundle of pure functions — close over it in
jitted code (don't pass it as a traced argument); only ``state`` is a pytree.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.klms import (
    rff_klms_init,
    rff_klms_step,
    rff_nklms_step,
)
from repro.core.krls import (
    KRLS_SHARD_AXIS,
    make_sharded_krls_predict,
    make_sharded_krls_step,
    rff_krls_init,
    rff_krls_step,
    sharded_krls_init,
)
from repro.core.krls_ald import ald_krls_init, ald_krls_predict, ald_krls_step
from repro.core.qklms import qklms_init, qklms_predict, qklms_step
from repro.core.scan import (
    ScanElement,
    gated_run,
    klms_scan_element,
    krls_scan_element,
    nklms_scan_element,
    replay_klms,
    replay_krls,
)
from repro.features.base import FeatureLike, feature_dtype, featurize

__all__ = [
    "OnlineLearner",
    "klms_learner",
    "nklms_learner",
    "krls_learner",
    "sharded_krls_learner",
    "qklms_learner",
    "ald_krls_learner",
]


@dataclass(frozen=True)
class OnlineLearner:
    """Algorithm-agnostic online learner: three pure functions + a driver.

    Attributes:
      init_fn: ``(key | None) -> state`` — fresh filter state.
      step_fn: ``(state, x, y) -> (state, StepOut)`` — one online update.
      predict_fn: ``(state, x) -> y_hat`` — inference without updating.
      scan_element: the recurrence as an associative algebra
        (:class:`repro.core.scan.ScanElement`), or None for learners whose
        state update is not an associative element (growing-dictionary
        baselines, sharded programs).
      replay_fn: ``(xs, ys, state=None, mode=..., chunk=..., mask=...)
        -> state`` — the parallel-in-time state rebuild (core/scan.py), or
        None to fall back to a sequential ``run`` in :meth:`rebuild`.
    """

    init_fn: Callable
    step_fn: Callable
    predict_fn: Callable
    scan_element: Optional[ScanElement] = None
    replay_fn: Optional[Callable] = None

    def init(self, key: Optional[jax.Array] = None):
        return self.init_fn(key)

    def step(self, state, x: jax.Array, y: jax.Array):
        return self.step_fn(state, x, y)

    def predict(self, state, x: jax.Array) -> jax.Array:
        return self.predict_fn(state, x)

    def run(self, state, xs: jax.Array, ys: jax.Array):
        """Drive the filter over a stream ``xs (n, d)``, ``ys (n,)``.

        ``state=None`` starts fresh. Returns (final state, per-step StepOut
        arrays) — ``out.error**2`` is the learning-curve quantity.
        """
        if state is None:
            state = self.init()

        def body(s, xy):
            return self.step_fn(s, *xy)

        return jax.lax.scan(body, state, (xs, ys))

    def rebuild(
        self,
        xs: jax.Array,
        ys: jax.Array,
        state=None,
        mode: str = "scan",
        chunk: Optional[int] = None,
        mask: Optional[jax.Array] = None,
    ):
        """Reconstruct the final state from a replay log (no per-tick outs).

        ``mode="sequential"`` (or a learner without a ``replay_fn``) drives
        the ordinary scan — bitwise the training path. ``"scan"`` /
        ``"blocked"`` rebuild through the associative-element engine in
        O(log T) / O(Tc + log nc) depth within the tolerances pinned in
        tests/test_replay.py. ``mask`` ``(T,)`` marks a padded log's real
        ticks (``core.scan.pad_log``); padding replays as the identity and
        the rebuilt step counts real ticks only.
        """
        if self.replay_fn is None or mode == "sequential":
            if mask is not None:
                return gated_run(
                    self.step_fn, self.init() if state is None else state,
                    xs, ys, mask,
                )
            final, _ = self.run(state, xs, ys)
            return final
        return self.replay_fn(
            xs, ys, state=state, mode=mode, chunk=chunk, mask=mask
        )


def klms_learner(rff: FeatureLike, mu: float) -> OnlineLearner:
    """RFFKLMS (paper §4): fixed-size theta, per-step O(D d).

    ``rff`` is any feature map from :mod:`repro.features` (or the legacy
    ``RFF`` struct) — deterministic families drop in unchanged."""
    return OnlineLearner(
        init_fn=lambda key=None: rff_klms_init(
            rff.num_features, feature_dtype(rff)
        ),
        step_fn=lambda s, x, y: rff_klms_step(s, (x, y), rff, mu),
        predict_fn=lambda s, x: featurize(rff, x) @ s.theta,
        scan_element=klms_scan_element(mu),
        replay_fn=lambda xs, ys, state=None, mode="scan", chunk=None, mask=None: (
            replay_klms(
                rff, xs, ys, mu, state=state, mode=mode, chunk=chunk, mask=mask
            )
        ),
    )


def nklms_learner(
    rff: FeatureLike, mu: float, eps: float = 1e-6
) -> OnlineLearner:
    """Normalized RFFKLMS: mu_eff = mu / (eps + ||z||^2)."""
    return OnlineLearner(
        init_fn=lambda key=None: rff_klms_init(
            rff.num_features, feature_dtype(rff)
        ),
        step_fn=lambda s, x, y: rff_nklms_step(s, (x, y), rff, mu, eps),
        predict_fn=lambda s, x: featurize(rff, x) @ s.theta,
        scan_element=nklms_scan_element(mu, eps),
        replay_fn=lambda xs, ys, state=None, mode="scan", chunk=None, mask=None: (
            replay_klms(
                rff, xs, ys, mu, state=state, mode=mode, chunk=chunk,
                normalized=True, eps=eps, mask=mask,
            )
        ),
    )


def krls_learner(
    rff: FeatureLike, lam: float = 1e-4, beta: float = 0.9995
) -> OnlineLearner:
    """RFFKRLS (paper §6): fixed (D,) theta + (D, D) inverse correlation."""
    return OnlineLearner(
        init_fn=lambda key=None: rff_krls_init(
            rff.num_features, lam, feature_dtype(rff)
        ),
        step_fn=lambda s, x, y: rff_krls_step(s, (x, y), rff, beta),
        predict_fn=lambda s, x: featurize(rff, x) @ s.theta,
        scan_element=krls_scan_element(beta),
        replay_fn=lambda xs, ys, state=None, mode="scan", chunk=None, mask=None: (
            replay_krls(
                rff, xs, ys, lam=lam, beta=beta, state=state, mode=mode,
                chunk=chunk, mask=mask,
            )
        ),
    )


def sharded_krls_learner(
    mesh,
    rff: FeatureLike,
    lam: float = 1e-4,
    beta: float = 0.9995,
    axis: str = KRLS_SHARD_AXIS,
) -> OnlineLearner:
    """RFFKRLS with ``P`` row-sharded over mesh ``axis`` (one psum/tick).

    Drop-in replacement for :func:`krls_learner` past the single-chip memory
    wall: state leaves are globally-shaped arrays carrying the
    ``core.krls.krls_state_specs`` layout, and step/predict are jitted
    ``shard_map`` programs. Numerically equivalent to the dense adapter to
    ~1e-5 (tested over 500+ ticks on an 8-way host mesh).
    """
    step = make_sharded_krls_step(mesh, rff, beta, axis)
    predict = make_sharded_krls_predict(mesh, rff, axis)
    return OnlineLearner(
        init_fn=lambda key=None: sharded_krls_init(
            mesh, rff.num_features, lam, feature_dtype(rff), axis
        ),
        step_fn=step,
        predict_fn=predict,
    )


def qklms_learner(
    input_dim: int,
    sigma: float,
    mu: float,
    eps: float,
    capacity: int = 512,
    dtype: jnp.dtype = jnp.float32,
) -> OnlineLearner:
    """QKLMS baseline (growing dictionary, fixed-capacity buffer)."""
    return OnlineLearner(
        init_fn=lambda key=None: qklms_init(capacity, input_dim, dtype),
        step_fn=lambda s, x, y: qklms_step(s, (x, y), sigma, mu, eps),
        predict_fn=lambda s, x: qklms_predict(s, x, sigma),
    )


def ald_krls_learner(
    input_dim: int,
    sigma: float,
    nu: float = 5e-4,
    capacity: int = 256,
    dtype: jnp.dtype = jnp.float32,
) -> OnlineLearner:
    """Engel's ALD-KRLS baseline (growing dictionary, O(M^2) per step)."""
    return OnlineLearner(
        init_fn=lambda key=None: ald_krls_init(capacity, input_dim, dtype),
        step_fn=lambda s, x, y: ald_krls_step(s, (x, y), sigma, nu),
        predict_fn=lambda s, x: ald_krls_predict(s, x, sigma),
    )
