"""Distribution-layer tests.

The production-mesh checks (16x16 / 2x16x16, all 40 cells) live in the
dry-run artifacts (experiments/dryrun). Here: diffusion RFF-KLMS semantics
on small forced-multi-device meshes via a subprocess (device count locks at
backend init, so the main test process cannot do it), and sharding-spec
divisibility audited mathematically for every arch x mesh.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DIFFUSION_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.core.distributed import diffusion_klms_run
from repro.core.rff import sample_rff
from repro.data.synthetic import gen_nonlinear_wiener

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rff = sample_rff(jax.random.PRNGKey(0), 5, 100, sigma=5.0)
nodes = 8
# ONE underlying system, split into per-node streams (the diffusion setting:
# common unknown plant, per-node observations)
xs_all, ys_all = gen_nonlinear_wiener(jax.random.PRNGKey(1), num_samples=600 * nodes)
xs = xs_all.reshape(nodes, 600, -1)
ys = ys_all.reshape(nodes, 600)

theta, errs = diffusion_klms_run(mesh, "data", rff, xs, ys, mu=0.5)
# combine every step => all thetas equal
spread = float(jnp.max(jnp.abs(theta - theta[0:1])))
mse_diff = float(jnp.mean(errs[:, -100:] ** 2))

theta_solo, errs_solo = diffusion_klms_run(
    mesh, "data", rff, xs, ys, mu=0.5, combine_every=10**9)
mse_solo = float(jnp.mean(errs_solo[:, -100:] ** 2))

theta_c, errs_c = diffusion_klms_run(
    mesh, "data", rff, xs, ys, mu=0.5, compress=True)
mse_comp = float(jnp.mean(errs_c[:, -100:] ** 2))

print(json.dumps({
    "spread": spread, "mse_diffusion": mse_diff,
    "mse_solo": mse_solo, "mse_compressed": mse_comp,
}))
"""


@pytest.mark.slow
def test_diffusion_klms_on_8_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", _DIFFUSION_SCRIPT],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # per-step combine keeps all node solutions identical
    assert res["spread"] < 1e-4
    # cooperation helps: diffusion <= isolated-node error floor
    assert res["mse_diffusion"] <= res["mse_solo"] * 1.05
    # int8+EF combine lands near the uncompressed floor
    assert res["mse_compressed"] <= res["mse_diffusion"] * 1.5


def _audit_specs(mesh_axes: dict):
    """Every sharded dim must divide by the product of its mesh axes."""
    from repro.configs import ARCH_IDS, get_config
    from repro.launch import sharding as sh
    from repro.launch.specs import resolve_cell
    from repro.configs.base import SHAPES

    class FakeMesh:
        def __init__(self, axes):
            self.shape = dict(axes)
            self.axis_names = tuple(axes)
            self.size = int(np.prod(list(axes.values())))

    mesh = FakeMesh(mesh_axes)
    bad = []
    for arch in ARCH_IDS:
        for shape_name in ("train_4k", "long_500k"):
            cfg, _ = resolve_cell(get_config(arch), SHAPES[shape_name])
            params_shape = jax.eval_shape(
                lambda cfg=cfg: __import__(
                    "repro.models.transformer", fromlist=["init_params"]
                ).init_params(jax.random.PRNGKey(0), cfg)
            )
            specs = sh.param_specs(cfg, mesh, params_shape)

            def check(path, leaf, spec):
                for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 9):
                    if part is None:
                        continue
                    axes = part if isinstance(part, tuple) else (part,)
                    total = int(np.prod([mesh.shape[a] for a in axes]))
                    if dim % total:
                        bad.append((arch, shape_name, jax.tree_util.keystr(path), dim, total))

            jax.tree_util.tree_map_with_path(check, params_shape, specs)
    assert not bad, bad[:10]


def test_param_spec_divisibility_single_pod():
    _audit_specs({"data": 16, "model": 16})


def test_param_spec_divisibility_multi_pod():
    _audit_specs({"pod": 2, "data": 16, "model": 16})


def test_dryrun_artifacts_complete():
    """All 80 cells (40 x 2 meshes) must exist and be green."""
    d = os.path.join(REPO, "experiments", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run artifacts not generated yet")
    files = [f for f in os.listdir(d) if f.endswith(".json")]
    assert len(files) >= 80, f"expected 80 cells, found {len(files)}"
    for f in files:
        rec = json.load(open(os.path.join(d, f)))
        assert "roofline" in rec and "memory" in rec, f
        assert rec["cost"]["flops_per_device"] > 0, f
