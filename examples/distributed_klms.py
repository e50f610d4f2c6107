"""Diffusion RFF-KLMS over a device mesh — the paper's distributed payoff.

Classic diffusion KLMS ships growing dictionaries between nodes; with RFF,
nodes exchange one fixed R^D vector per combine round (here: a pmean over
the mesh's data axis, optionally int8-compressed with error feedback).

Run (forces 8 host devices; must be set before jax imports):

    PYTHONPATH=src python examples/distributed_klms.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.core.distributed import diffusion_klms_run
from repro.core.rff import sample_rff
from repro.data.synthetic import gen_nonlinear_wiener
from repro.launch.mesh import make_mesh


def main():
    nodes = 8
    mesh = make_mesh((nodes,), ("data",))
    rff = sample_rff(jax.random.PRNGKey(0), 5, 100, sigma=5.0)

    # one common unknown system, observed as per-node streams
    xs_all, ys_all = gen_nonlinear_wiener(
        jax.random.PRNGKey(1), num_samples=800 * nodes
    )
    xs = xs_all.reshape(nodes, 800, -1)
    ys = ys_all.reshape(nodes, 800)

    for label, kwargs in (
        ("isolated nodes     ", dict(combine_every=10**9)),
        ("diffusion (f32)    ", dict()),
        ("diffusion (int8+EF)", dict(compress=True)),
    ):
        theta, errs = diffusion_klms_run(mesh, "data", rff, xs, ys, mu=0.5, **kwargs)
        mse = float(jnp.mean(errs[:, -100:] ** 2))
        spread = float(jnp.max(jnp.abs(theta - jnp.mean(theta, 0, keepdims=True))))
        print(f"{label}: steady MSE {mse:.5f}   node-solution spread {spread:.2e}")

    print("\nper-round network payload: "
          f"f32 {100*4} B/node vs int8 {100} B/node (fixed D=100, forever)")


if __name__ == "__main__":
    main()
