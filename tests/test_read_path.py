"""Read-path overhaul tests: fused predict-only kernel vs the vmapped
adapter oracle, the mixed-precision contract per feature family, the
VMEM-budget default chunk T, and adaptive flush sizing in the serve queue.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import (
    bank_predict,
    bank_predict_block,
    klms_bank_init,
)
from repro.core.klms import LMSState, rff_klms_run
from repro.core.learner import klms_learner
from repro.core.rff import sample_rff
from repro.features import as_trig, make_feature_map
from repro.kernels import ops, ref
from repro.kernels.chunking import default_chunk_t
from repro.kernels.rff_predict import rff_bank_predict_pallas
from repro.serve.queue import klms_micro_batch_queue

TRIG_FAMILIES = ("rff", "orf", "qmc", "gq")
ALL_FAMILIES = TRIG_FAMILIES + ("taylor",)

# bf16 has an 8-bit mantissa: a D-term f32 accumulation of bf16-rounded
# features against unit-scale theta lands within ~2^-8 of the f32 path.
# The contract tests/README quote is this bound per family.
BF16_PRED_TOL = 2e-2


def _fm(family, d=4, dfeat=64, sigma=2.0, seed=0):
    return make_feature_map(
        family, d, dfeat, sigma, key=jax.random.PRNGKey(seed)
    )


def _bank_inputs(key, bank, qlen, d, dfeat, scale=0.3):
    ks = jax.random.split(key, 2)
    theta = scale * jax.random.normal(ks[0], (bank, dfeat))
    xq = jax.random.normal(ks[1], (bank, qlen, d))
    return theta, xq


@pytest.mark.parametrize("family", TRIG_FAMILIES)
def test_predict_kernel_bitwise_vs_oracle_f32(key, family):
    """Interpret-mode fused predict == the predict oracle, bitwise, for
    every trig family (the acceptance contract of the read-path kernel)."""
    fm = _fm(family)
    tf = as_trig(fm)
    theta, xq = _bank_inputs(key, 5, 13, 4, tf.num_features)
    want = ref.rff_bank_predict_ref(theta, xq, tf.omega, tf.bias, tf.scale)
    got = rff_bank_predict_pallas(
        theta, xq, tf.omega, tf.bias, tf.scale, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("family", TRIG_FAMILIES)
def test_predict_kernel_bitwise_vs_oracle_bf16(key, family):
    """Kernel and oracle share ONE mixed-precision definition — interpret
    mode matches bitwise at bf16 too; the tolerance lives between bf16 and
    the f32 reference, not between kernel and oracle."""
    fm = _fm(family)
    tf = as_trig(fm)
    theta, xq = _bank_inputs(key, 5, 13, 4, tf.num_features)
    want16 = ref.rff_bank_predict_ref(
        theta, xq, tf.omega, tf.bias, tf.scale, "bf16"
    )
    got16 = rff_bank_predict_pallas(
        theta, xq, tf.omega, tf.bias, tf.scale, precision="bf16", interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got16), np.asarray(want16))
    want32 = ref.rff_bank_predict_ref(theta, xq, tf.omega, tf.bias, tf.scale)
    assert float(jnp.max(jnp.abs(want16 - want32))) < BF16_PRED_TOL


@pytest.mark.parametrize(
    "bank,qlen,d,D", [(1, 1, 2, 17), (9, 70, 5, 96), (16, 3, 8, 128)]
)
def test_predict_kernel_shape_sweep(key, bank, qlen, d, D):
    """Padding on every axis (bank, query, d, D) is exact."""
    rff = sample_rff(jax.random.PRNGKey(3), d, D, sigma=2.0)
    tf = as_trig(rff)
    theta, xq = _bank_inputs(key, bank, qlen, d, D)
    want = ref.rff_bank_predict_ref(theta, xq, tf.omega, tf.bias, tf.scale)
    got = rff_bank_predict_pallas(
        theta, xq, tf.omega, tf.bias, tf.scale, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Block-shape invariance: different (block_b, block_q) tilings agree.
    got2 = rff_bank_predict_pallas(
        theta, xq, tf.omega, tf.bias, tf.scale, block_b=1, block_q=8, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want))


def test_predict_oracle_matches_vmapped_adapter(key):
    """The predict oracle IS the PR-1 `bank_predict` adapter, batched: per
    query they agree to reduction-order rounding (matvec vs mul-reduce)."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 96, sigma=2.0)
    tf = as_trig(rff)
    theta, xq = _bank_inputs(key, 6, 11, 5, 96)
    learner = klms_learner(rff, 0.5)
    state = LMSState(theta=theta, step=jnp.zeros((6,), jnp.int32))
    adapter = jnp.stack(
        [bank_predict(learner, state, xq[:, i]) for i in range(11)], axis=1
    )
    oracle = ref.rff_bank_predict_ref(theta, xq, tf.omega, tf.bias, tf.scale)
    np.testing.assert_allclose(
        np.asarray(adapter), np.asarray(oracle), atol=1e-6, rtol=1e-6
    )


@pytest.mark.parametrize("family", ALL_FAMILIES)
def test_bank_predict_block_all_families(key, family):
    """The family-agnostic read path (fused for trig, featurize fallback
    for taylor) matches the per-query adapter for every family."""
    fm = _fm(family)
    dfeat = fm.num_features
    theta, xq = _bank_inputs(key, 4, 7, 4, dfeat)
    state = LMSState(theta=theta, step=jnp.zeros((4,), jnp.int32))
    learner = klms_learner(fm, 0.5)
    adapter = jnp.stack(
        [bank_predict(learner, state, xq[:, i]) for i in range(7)], axis=1
    )
    got = bank_predict_block(state, xq, fm, mode="xla")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(adapter), atol=1e-6, rtol=1e-6
    )
    got_interp = bank_predict_block(state, xq, fm, mode="interpret")
    np.testing.assert_allclose(
        np.asarray(got_interp), np.asarray(adapter), atol=1e-6, rtol=1e-6
    )


@pytest.mark.parametrize("family", ALL_FAMILIES)
def test_bf16_read_tolerance_all_families(key, family):
    """The documented bf16-vs-f32 prediction tolerance holds for all five
    families (taylor runs the generic bf16-feature fallback)."""
    fm = _fm(family)
    dfeat = fm.num_features
    theta, xq = _bank_inputs(key, 4, 32, 4, dfeat)
    state = LMSState(theta=theta, step=jnp.zeros((4,), jnp.int32))
    f32 = bank_predict_block(state, xq, fm, mode="xla")
    bf16 = bank_predict_block(state, xq, fm, mode="xla", precision="bf16")
    err = float(jnp.max(jnp.abs(f32 - bf16)))
    assert err < BF16_PRED_TOL, (family, err)
    assert err > 0  # bf16 really ran at reduced precision


def test_rff_features_precision_contract(key):
    """ops.rff_features precision knob: bf16 output dtype, interpret-vs-ref
    bitwise, and error bounded against the f32 path."""
    rff = sample_rff(jax.random.PRNGKey(1), 6, 80, sigma=2.0)
    tf = as_trig(rff)
    x = jax.random.normal(key, (33, 6))
    z32 = ops.rff_features(x, tf.omega, tf.bias, tf.scale, mode="xla")
    z16 = ops.rff_features(
        x, tf.omega, tf.bias, tf.scale, mode="xla", precision="bf16"
    )
    assert z16.dtype == jnp.bfloat16
    zi = ops.rff_features(
        x, tf.omega, tf.bias, tf.scale, mode="interpret", precision="bf16"
    )
    np.testing.assert_array_equal(np.asarray(zi), np.asarray(z16))
    # |z| <= max scale, so absolute feature error sits at bf16 epsilon.
    assert float(jnp.max(jnp.abs(z16.astype(jnp.float32) - z32))) < 1e-2
    # f32 stays bitwise-legacy.
    z_legacy = ops.rff_features(x, tf.omega, tf.bias, tf.scale, mode="xla",
                                precision="f32")
    np.testing.assert_array_equal(np.asarray(z_legacy), np.asarray(z32))


def test_default_chunk_t_corners():
    """Pin the VMEM-budget heuristic at representative (B, D) corners."""
    # Serving-sized KLMS bank: budget is stream-bound -> saturates the cap.
    assert default_chunk_t(16, 128) == 512
    # KRLS carries the (D, D) P tile; still saturates at moderate D...
    assert default_chunk_t(8, 512, pmat=True) == 512
    # ...but a huge-D P busts the budget entirely -> the dispatch floor.
    assert default_chunk_t(8, 1408, pmat=True) == 8
    # Tighter budget exercises the power-of-two floor between the clamps
    # (the resident state and W tile take ~344 KiB of it).
    assert default_chunk_t(16, 256, vmem_budget=450_000) == 256
    # f64 streams halve the tick count before clamping.
    assert default_chunk_t(16, 256, jnp.float64, vmem_budget=450_000) == 128
    # A wide input dim is charged at its real lane-padded width: the KRLS
    # chunk kernel keeps the tenant's whole (T, d) input slab resident, so
    # it shrinks T (vs the low-d default of one lane tile, which would
    # still pick 512 here).
    assert default_chunk_t(1, 512, pmat=True, input_dim=2048) == 32
    assert default_chunk_t(1, 512, pmat=True) == 512
    # Everything stays inside the documented clamp range.
    for bank in (1, 8, 64):
        for dfeat in (17, 128, 2048):
            for pmat in (False, True):
                for din in (None, 4, 700):
                    t = default_chunk_t(bank, dfeat, pmat=pmat,
                                        input_dim=din)
                    assert 8 <= t <= 512 and t & (t - 1) == 0


def test_precision_knob_validated_identically_everywhere(key):
    """A typo'd precision string raises on EVERY backend path instead of
    silently running f32 on one of them."""
    rff = sample_rff(jax.random.PRNGKey(0), 4, 32, sigma=2.0)
    tf = as_trig(rff)
    theta, xq = _bank_inputs(key, 2, 3, 4, 32)
    state = LMSState(theta=theta, step=jnp.zeros((2,), jnp.int32))
    x2 = xq.reshape(-1, 4)
    for bad in ("f16", "fp16", "half"):
        with pytest.raises(ValueError):
            ref.rff_bank_predict_ref(
                theta, xq, tf.omega, tf.bias, tf.scale, bad
            )
        with pytest.raises(ValueError):
            rff_bank_predict_pallas(
                theta, xq, tf.omega, tf.bias, tf.scale, precision=bad,
                interpret=True,
            )
        with pytest.raises(ValueError):
            ops.rff_features(
                x2, tf.omega, tf.bias, tf.scale, mode="interpret",
                precision=bad,
            )
        with pytest.raises(ValueError):
            bank_predict_block(state, xq, rff, mode="xla", precision=bad)
    # The aliases stay accepted on every path.
    out = bank_predict_block(state, xq, rff, mode="xla", precision="bfloat16")
    assert out.shape == (2, 3)


def test_chunk_none_uses_default_and_matches_explicit(key):
    """chunk=None routes through default_chunk_t and stays numerically the
    per-tick schedule (the KLMS chunk path is bitwise by contract)."""
    rff = sample_rff(jax.random.PRNGKey(0), 4, 48, sigma=2.0)
    tf = as_trig(rff)
    bank, n = 3, 40
    ks = jax.random.split(key, 2)
    xs = jax.random.normal(ks[0], (bank, n, 4))
    ys = jax.random.normal(ks[1], (bank, n))
    theta0 = jnp.zeros((bank, 48))
    th_none, p_none, e_none = ops.rff_klms_bank_chunk(
        theta0, xs, ys, tf.omega, tf.bias, 0.5, None, tf.scale, mode="xla"
    )
    th_exp, p_exp, e_exp = ops.rff_klms_bank_chunk(
        theta0, xs, ys, tf.omega, tf.bias, 0.5, None, tf.scale, mode="xla",
        chunk=8,
    )
    np.testing.assert_array_equal(np.asarray(th_none), np.asarray(th_exp))
    np.testing.assert_array_equal(np.asarray(e_none), np.asarray(e_exp))


def test_adaptive_queue_matches_sequential():
    """Backlog-adaptive flush T preserves the ragged-stream contract: every
    tenant sees exactly its own sequential trajectory."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    rng = np.random.RandomState(1)
    xs = rng.randn(120, 5).astype(np.float32)
    ys = rng.randn(120).astype(np.float32)
    streams = {0: 55, 1: 7, 2: 0, 3: 23}
    per_tenant, offs = {}, 0
    for t, n in streams.items():
        per_tenant[t] = (xs[offs:offs + n], ys[offs:offs + n])
        offs += n

    q = klms_micro_batch_queue(rff, 4, mu=0.5, chunk=16, mode="xla",
                               adaptive=True)
    order = [t for t, n in streams.items() for _ in range(n)]
    rng.shuffle(order)
    results = {t: [] for t in streams}
    iters = {t: 0 for t in streams}
    seen_chunks = set()
    for i, t in enumerate(order):
        k = iters[t]
        iters[t] += 1
        q.submit(t, per_tenant[t][0][k], per_tenant[t][1][k])
        if i % 7 == 6:  # frequent flushes -> shallow adaptive chunks
            seen_chunks.add(q._flush_chunk())
            for b, res in q.flush().items():
                results[b].extend(res)
    while any(q.backlog()):
        seen_chunks.add(q._flush_chunk())
        for b, res in q.flush().items():
            results[b].extend(res)

    assert q.arrivals == [55, 7, 0, 23]
    assert len(seen_chunks) > 1  # adaptation actually varied T
    assert all(1 <= c <= 16 and c & (c - 1) == 0 for c in seen_chunks)
    for t, n in streams.items():
        if n == 0:
            assert not results[t]
            continue
        assert len(results[t]) == n
        _, want = rff_klms_run(rff, per_tenant[t][0], per_tenant[t][1], 0.5)
        got = np.array([e for _, e in results[t]])
        np.testing.assert_allclose(got, np.asarray(want.error), atol=1e-5)


def test_bank_predict_block_on_trained_bank(key):
    """End-to-end: train a bank, then the fused read path reproduces the
    adapter's predictions on the trained theta."""
    rff = sample_rff(jax.random.PRNGKey(0), 5, 64, sigma=5.0)
    learner = klms_learner(rff, 0.5)
    bank = 4
    state = klms_bank_init(rff, bank)
    ks = jax.random.split(key, 2)
    xs = jax.random.normal(ks[0], (bank, 30, 5))
    ys = jax.random.normal(ks[1], (bank, 30))
    from repro.core.bank import klms_bank_run

    state, _ = klms_bank_run(rff, xs, ys, 0.5, state=state, mode="xla")
    xq = jax.random.normal(jax.random.PRNGKey(9), (bank, 5, 5))
    adapter = jnp.stack(
        [bank_predict(learner, state, xq[:, i]) for i in range(5)], axis=1
    )
    got = bank_predict_block(state, xq, rff, mode="xla")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(adapter), atol=1e-6, rtol=1e-6
    )
