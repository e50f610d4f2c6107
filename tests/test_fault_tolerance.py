"""Checkpoint/restart, crash recovery, straggler watchdog, elastic remesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.lm_data import batch_at_step
from repro.train import checkpoint as ckpt
from repro.launch.mesh import make_mesh
from repro.train.elastic import remesh
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return get_config("qwen2-0.5b").reduced()


def _batch_fn(cfg):
    def fn(step):
        return {
            "tokens": batch_at_step(
                0, step, global_batch=4, seq_len=16, vocab=cfg.vocab_size
            )
        }

    return fn


def test_checkpoint_roundtrip(tmp_path, key):
    state = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3))}}
    ckpt.save(str(tmp_path), 5, state)
    restored, step = ckpt.restore(str(tmp_path))
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(10.0))


def test_checkpoint_keep_k_gc(tmp_path):
    for s in range(1, 8):
        ckpt.save(str(tmp_path), s, {"x": jnp.zeros(2)}, keep=3)
    assert ckpt.list_steps(str(tmp_path)) == [5, 6, 7]


def test_restore_survives_corrupt_latest(tmp_path):
    """A truncated newest checkpoint must fall back, not crash (node died
    mid-write is the normal case at 1000-node scale)."""
    ckpt.save(str(tmp_path), 1, {"x": jnp.ones(4)})
    ckpt.save(str(tmp_path), 2, {"x": 2 * jnp.ones(4)})
    # corrupt step 2 (simulate a crash mid-write that still got renamed)
    with open(os.path.join(str(tmp_path), "step_2.ckpt"), "wb") as f:
        f.write(b"garbage")
    restored, step = ckpt.restore(str(tmp_path))
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.ones(4))


def test_trainer_resume_bit_exact(tmp_path, key):
    """train 6 straight == train 3 + crash + resume 3 (stateless data)."""
    cfg = _tiny_cfg()

    tA = Trainer(
        cfg,
        TrainerConfig(total_steps=6, ckpt_every=100, ckpt_dir=str(tmp_path / "a"),
                      num_microbatches=2, log_every=100),
        _batch_fn(cfg),
    )
    tA.run()
    thetaA = jax.tree.leaves(tA.state["params"])[0]

    dirB = str(tmp_path / "b")
    tB1 = Trainer(
        cfg,
        TrainerConfig(total_steps=3, ckpt_every=3, ckpt_dir=dirB,
                      num_microbatches=2, log_every=100),
        _batch_fn(cfg),
    )
    tB1.run()
    del tB1  # "crash"
    tB2 = Trainer(
        cfg,
        TrainerConfig(total_steps=6, ckpt_every=3, ckpt_dir=dirB,
                      num_microbatches=2, log_every=100),
        _batch_fn(cfg),
    )
    tB2.run()
    thetaB = jax.tree.leaves(tB2.state["params"])[0]
    np.testing.assert_allclose(
        np.asarray(thetaA, np.float32), np.asarray(thetaB, np.float32),
        atol=1e-6,
    )


def test_straggler_watchdog_detects_slow_steps(tmp_path):
    cfg = _tiny_cfg()
    t = Trainer(
        cfg,
        TrainerConfig(total_steps=14, ckpt_every=100, ckpt_dir=str(tmp_path),
                      log_every=100),
        _batch_fn(cfg),
        delay_injector=lambda step: 0.4 if step == 12 else 0.0,
    )
    t.run()
    assert t.straggler_events >= 1


def test_elastic_remesh_preserves_values(key):
    """Re-sharding to a new (here: same-size) mesh preserves the state."""
    state = {"w": jax.random.normal(key, (8, 8))}
    mesh = make_mesh((1,), ("data",))
    shard = jax.tree.map(
        lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()), state
    )
    moved = remesh(state, shard)
    np.testing.assert_array_equal(np.asarray(moved["w"]), np.asarray(state["w"]))


def test_gradient_compression_error_feedback():
    """int8+EF: compression error stays O(1) over many rounds instead of
    accumulating (the residual re-injection property)."""
    from repro.optim.compression import compress_tree, decompress_tree, init_state

    grads = {"w": jnp.linspace(-1, 1, 1000)}
    st = init_state(grads)
    total_sent = jnp.zeros(1000)
    for _ in range(50):
        q, s, st = compress_tree(grads, st)
        total_sent = total_sent + decompress_tree(q, s)["w"]
    # after T rounds, sum of sent ~= T * grads (EF guarantees bounded bias)
    err = float(jnp.max(jnp.abs(total_sent / 50 - grads["w"])))
    assert err < 1e-3
