"""The ``FeatureMap`` contract — one pluggable device under every learner.

The paper's entire efficiency argument rests on a fixed-size feature map
whose inner product approximates the kernel:

    kappa(x, y) ~= z(x)^T z(y),    z(x) in R^D.

Historically the repo hardcoded the Monte-Carlo RFF map
(``core.rff.rff_features``) at every call site. This module makes the map a
first-class subsystem: a feature map is

  * a **pytree param struct** (so it flows through jit / vmap / scan /
    shard_map unchanged),
  * a pure ``featurize(params, x) -> (..., D)`` function,
  * ``num_features`` / ``input_dim`` / per-feature ``weights`` metadata.

Canonical affine-trig form
--------------------------
Every trigonometric family (Monte-Carlo RFF, orthogonal random features,
quasi-Monte-Carlo, deterministic Gaussian quadrature) canonicalizes to

    z(x) = scale * cos(x @ omega + bias),        scale per-feature (D,),

captured by :class:`TrigFeatures`. This is the ONE form the Pallas kernels
(``kernels/rff_features.py``, the fused KLMS/KRLS bank step kernels and the
chunked multi-tick engine) consume — swapping families changes the params,
never the kernels. Pairs ``(cos(w.x), sin(w.x))`` fit the form because
``sin(t) = cos(t - pi/2)``; per-node quadrature weights ``a_j`` become
per-feature scales ``sqrt(a_j)``.

Non-trig families (the Taylor map in ``features/deterministic.py``) satisfy
the same :class:`FeatureMap` contract and run through every generic
(XLA/vmap) path; only the fused trig kernels require :func:`as_trig`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels.ref import F32_DOT

if TYPE_CHECKING:  # runtime import is lazy: core.klms/krls import this module
    from repro.core.rff import RFF

__all__ = [
    "TrigFeatures",
    "FeatureMap",
    "FeatureLike",
    "trig_features",
    "trig_weights",
    "featurize",
    "as_trig",
    "as_trig_or_none",
    "feature_weights",
    "num_features",
    "input_dim",
    "feature_dtype",
    "uniform_trig_scale",
    "trig_from_rff",
]


class TrigFeatures(NamedTuple):
    """Canonical affine-trig feature parameters (the Pallas-kernel contract).

    ``z(x) = scale * cos(x @ omega + bias)`` with per-feature scale, so one
    struct expresses Monte-Carlo RFF (uniform ``sqrt(2/D)`` scale), ORF,
    QMC cos/sin pairs and weighted Gaussian-quadrature nodes.

    Attributes:
      omega: ``(d, D)`` spectral points (columns are the omega_i).
      bias:  ``(D,)`` phases (``U[0, 2pi]`` draws, or ``0 / -pi/2`` for
             deterministic cos/sin pairs).
      scale: ``(D,)`` per-feature scales ``sqrt(a_i)`` — the square roots of
             the quadrature weights.
    """

    omega: jax.Array
    bias: jax.Array
    scale: jax.Array

    @property
    def input_dim(self) -> int:
        return self.omega.shape[0]

    @property
    def num_features(self) -> int:
        return self.omega.shape[1]

    @property
    def dtype(self) -> jnp.dtype:
        return self.omega.dtype


def uniform_trig_scale(
    num_features: int, dtype: jnp.dtype = jnp.float32
) -> jax.Array:
    """The Monte-Carlo ``sqrt(2/D)`` scale as a per-feature ``(D,)`` array.

    Computed exactly like ``core.rff.rff_features``'s scalar
    (``jnp.sqrt(2.0 / D)`` in the default precision, then cast) — for ~13%
    of D values that differs by 1 ulp from the f64-sqrt-then-cast route, and
    canonicalizing an :class:`repro.core.rff.RFF` must change NOTHING
    numerically (the adapter bit-exactness tests pin this).
    """
    scalar = jnp.sqrt(2.0 / num_features).astype(dtype)
    return jnp.broadcast_to(scalar, (num_features,))


def trig_from_rff(rff: "RFF") -> TrigFeatures:
    """Canonicalize the paper's RFF struct: uniform ``sqrt(2/D)`` scale."""
    return TrigFeatures(
        omega=rff.omega,
        bias=rff.bias,
        scale=uniform_trig_scale(rff.num_features, rff.omega.dtype),
    )


def trig_features(tf: TrigFeatures, x: jax.Array) -> jax.Array:
    """``z(x) = scale * cos(x @ omega + bias)`` — inputs ``(..., d)``."""
    proj = jnp.matmul(x, tf.omega, precision=F32_DOT) + tf.bias
    return tf.scale.astype(proj.dtype) * jnp.cos(proj)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class FeatureMap:
    """A feature family behind one contract: params pytree + pure featurize.

    Instances are pytrees (``params`` holds the leaves; everything else is
    static aux data), so a ``FeatureMap`` can be passed straight into jitted
    functions, vmapped over, or closed over — exactly like the ``RFF``
    NamedTuple it generalizes.

    Attributes:
      family: registry name (``rff`` / ``orf`` / ``qmc`` / ``gq`` /
        ``taylor``).
      params: the param pytree — :class:`TrigFeatures` for trig families,
        a family-specific struct otherwise. Must expose ``num_features`` /
        ``input_dim`` / ``dtype`` properties.
      featurize_fn: pure ``(params, x) -> (..., D)``.
      weights_fn: pure ``(params,) -> (D,)`` per-feature quadrature weights
        (``scale**2`` for trig families).
      deterministic: True when construction ignores PRNG keys entirely — the
        zero-seed-variance families (QMC, GQ, Taylor).
    """

    family: str
    params: Any
    featurize_fn: Callable[[Any, jax.Array], jax.Array]
    weights_fn: Callable[[Any], jax.Array]
    deterministic: bool

    def tree_flatten(self):
        aux = (
            self.family,
            self.featurize_fn,
            self.weights_fn,
            self.deterministic,
        )
        return (self.params,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        family, featurize_fn, weights_fn, deterministic = aux
        return cls(
            family=family,
            params=children[0],
            featurize_fn=featurize_fn,
            weights_fn=weights_fn,
            deterministic=deterministic,
        )

    @property
    def num_features(self) -> int:
        return self.params.num_features

    @property
    def input_dim(self) -> int:
        return self.params.input_dim

    @property
    def dtype(self) -> jnp.dtype:
        return self.params.dtype

    @property
    def weights(self) -> jax.Array:
        """Per-feature quadrature weights ``a_i`` (``scale**2`` for trig).

        Trig families sum to ``2 kappa(0)``: cos/sin pairs carry each node
        weight twice (``cos^2 + sin^2`` collapses the pair, so
        ``||z||^2 = 1`` exactly for gq/qmc), while random-phase features
        contribute ``E[cos^2] = 1/2`` each (``||z||^2 = 1`` in expectation).
        """
        return self.weights_fn(self.params)

    @property
    def trig(self) -> Optional[TrigFeatures]:
        """The canonical affine-trig form, or None for non-trig families."""
        return self.params if isinstance(self.params, TrigFeatures) else None

    def featurize(self, x: jax.Array) -> jax.Array:
        return self.featurize_fn(self.params, x)


def trig_weights(params: TrigFeatures) -> jax.Array:
    """Per-feature quadrature weights of a trig map: ``scale**2``.

    Module-level (not a closure) on purpose: ``weights_fn`` is pytree aux
    data, and identically-constructed maps must compare structurally equal
    so jitted functions taking a map as a traced argument don't retrace per
    instance (the rebuild-anywhere serving story for deterministic maps).
    """
    return jnp.square(params.scale)


def trig_map(family: str, params: TrigFeatures, deterministic: bool) -> FeatureMap:
    """Wrap canonical trig params as a :class:`FeatureMap`."""
    return FeatureMap(
        family=family,
        params=params,
        featurize_fn=trig_features,
        weights_fn=trig_weights,
        deterministic=deterministic,
    )


# Anything the learners accept where a feature map is expected. ``RFF`` stays
# valid so every pre-subsystem call site keeps working unchanged. (The RFF
# reference is a forward string: core.klms/krls import this module, so the
# concrete class is only touched lazily at call time.)
FeatureLike = Union[FeatureMap, TrigFeatures, "RFF"]


def _is_rff(fm: Any) -> bool:
    from repro.core.rff import RFF

    return isinstance(fm, RFF)


def featurize(fm: FeatureLike, x: jax.Array) -> jax.Array:
    """Family-agnostic feature map: ``(..., d) -> (..., D)``."""
    if isinstance(fm, FeatureMap):
        return fm.featurize(x)
    if isinstance(fm, TrigFeatures):
        return trig_features(fm, x)
    if _is_rff(fm):
        from repro.core.rff import rff_features

        return rff_features(fm, x)
    raise TypeError(f"not a feature map: {type(fm).__name__}")


def as_trig_or_none(fm: FeatureLike) -> Optional[TrigFeatures]:
    """Canonical ``(W, b, scale)`` form, or None if the family has none."""
    if isinstance(fm, TrigFeatures):
        return fm
    if _is_rff(fm):
        return trig_from_rff(fm)
    if isinstance(fm, FeatureMap):
        return fm.trig
    raise TypeError(f"not a feature map: {type(fm).__name__}")


def as_trig(fm: FeatureLike) -> TrigFeatures:
    """Canonical trig form; raises for non-trig families (e.g. ``taylor``).

    The fused Pallas kernels and the sharded KRLS path inline the affine-trig
    activation and therefore require this form; non-trig families run through
    the generic ``featurize`` paths instead.
    """
    tf = as_trig_or_none(fm)
    if tf is None:
        family = fm.family if isinstance(fm, FeatureMap) else type(fm).__name__
        raise TypeError(
            f"feature family {family!r} has no affine-trig canonical form; "
            "use the generic (featurize-based) path for it"
        )
    return tf


def feature_weights(fm: FeatureLike) -> jax.Array:
    """Per-feature quadrature weights ``a_i`` (``scale**2`` for trig maps)."""
    if isinstance(fm, FeatureMap):
        return fm.weights
    return jnp.square(as_trig(fm).scale)


def num_features(fm: FeatureLike) -> int:
    return fm.num_features


def input_dim(fm: FeatureLike) -> int:
    return fm.input_dim


def feature_dtype(fm: FeatureLike) -> jnp.dtype:
    """Working dtype of a feature map (RFF has no ``.dtype`` property)."""
    if _is_rff(fm):
        return fm.omega.dtype
    return fm.dtype
