"""Distributed (diffusion) RFF-KLMS — the paper's §1 motivation, ref [21].

Classic diffusion KLMS must ship *growing dictionaries* between nodes and
cross-match them (sequential searches per neighbor). With RFF the solution is
a fixed ``theta in R^D``, so the combine step is a single fixed-size
collective — exactly why the paper calls RFF the enabler for distributed
kernel adaptive filtering.

Adapt-then-Combine (ATC) diffusion over a JAX mesh axis:

    adapt:    theta_k' = theta_k + mu e_k z(x_k)        (local LMS step)
    combine:  theta_k  = sum_j c_jk theta_j'            (here: uniform pmean)

Implemented with ``shard_map`` over the ``data`` axis; the combine is a
``lax.pmean`` — on real hardware an ICI all-reduce of D floats per step
(or per round when ``combine_every > 1``).

Also provides an int8-quantized combine with error feedback, the standard
gradient-compression trick, for DCN-bound (cross-pod) deployments.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.klms import LMSState, rff_klms_init, rff_klms_step
from repro.core.rff import RFF

__all__ = [
    "DiffusionState",
    "diffusion_klms_run",
    "quantize_int8",
    "dequantize_int8",
]


class DiffusionState(NamedTuple):
    lms: LMSState  # per-node filter state (theta sharded over nodes)
    comp_err: jax.Array  # (D,) error-feedback residual for compression


def quantize_int8(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization; returns (q, scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _node_stream(
    rff: RFF,
    xs: jax.Array,
    ys: jax.Array,
    mu: float,
    combine_every: int,
    compress: bool,
    axis: str,
) -> tuple[jax.Array, jax.Array]:
    """Per-node body under shard_map: local adapt + periodic pmean combine."""
    # shard_map passes the local block with a leading node axis of size 1.
    xs = xs[0]  # (n, d) local stream shard
    ys = ys[0]
    n = xs.shape[0]
    state = DiffusionState(
        lms=rff_klms_init(rff.num_features, xs.dtype),
        comp_err=jnp.zeros((rff.num_features,), xs.dtype),
    )
    # the carry becomes device-varying after one data-dependent update;
    # mark the init as varying so scan's carry types match.
    state = jax.tree.map(
        lambda a: jax.lax.pcast(a, axis, to="varying"), state
    )

    def combine(theta: jax.Array, comp_err: jax.Array):
        if not compress:
            return jax.lax.pmean(theta, axis), comp_err
        # error-feedback int8: quantize (theta + residual), average the
        # dequantized messages, keep the local quantization error.
        msg = theta + comp_err
        q, scale = quantize_int8(msg)
        deq = dequantize_int8(q, scale)
        new_err = msg - deq
        return jax.lax.pmean(deq, axis), new_err

    def body(s: DiffusionState, inp):
        xy, step_idx = inp
        lms, out = rff_klms_step(s.lms, xy, rff, mu)
        do_combine = (step_idx + 1) % combine_every == 0
        theta_c, err_c = combine(lms.theta, s.comp_err)
        theta = jnp.where(do_combine, theta_c, lms.theta)
        comp_err = jnp.where(do_combine, err_c, s.comp_err)
        return DiffusionState(LMSState(theta, lms.step), comp_err), out.error

    (final, errs) = jax.lax.scan(body, state, ((xs, ys), jnp.arange(n)))
    return final.lms.theta[None], errs[None]


def diffusion_klms_run(
    mesh: Mesh,
    axis: str,
    rff: RFF,
    xs: jax.Array,
    ys: jax.Array,
    mu: float,
    combine_every: int = 1,
    compress: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Run ATC diffusion RFF-KLMS over mesh ``axis``.

    Args:
      xs: ``(nodes, n, d)`` per-node streams (node axis sharded over ``axis``).
      ys: ``(nodes, n)``.

    Returns:
      (theta per node ``(nodes, D)``, prior errors ``(nodes, n)``).
    """
    body = functools.partial(
        _node_stream,
        rff,
        mu=mu,
        combine_every=combine_every,
        compress=compress,
        axis=axis,
    )
    spec = P(axis)
    shmapped = jax.shard_map(
        lambda x, y: body(xs=x, ys=y),
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec),
    )
    xs = jax.device_put(xs, NamedSharding(mesh, spec))
    ys = jax.device_put(ys, NamedSharding(mesh, spec))
    return jax.jit(shmapped)(xs, ys)
