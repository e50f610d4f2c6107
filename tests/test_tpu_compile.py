"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology. This is what interpret mode cannot show —
block shapes the TPU tiling refuses, and kernels that need more fast memory
(VMEM) than the compiler's scoped limit. Each case compiles one kernel at
the widths the tenant-fleet server runs (``chip_smoke.py``) and asserts
that the program holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunking import SCOPED_VMEM_LIMIT, default_chunk_t, vmem_params
from repro.kernels.rff_attention import rff_attention_decode_block_pallas
from repro.kernels.rff_klms_step import (
    rff_klms_bank_chunk_pallas,
    rff_klms_bank_step_pallas,
)
from repro.kernels.rff_krls_step import (
    rff_krls_bank_chunk_pallas,
    rff_krls_bank_step_pallas,
)
from repro.kernels.rff_predict import rff_bank_predict_pallas
from repro.kernels.rff_scan import (
    rff_klms_chunk_elements_pallas,
    rff_krls_chunk_elements_pallas,
)

D_IN = 5  # paper §5/§6 input dim
D_FEAT = 1000  # paper §5 example 1, padded to 1024 in the kernels
KLMS_BANK, KRLS_BANK, CHUNK = 1024, 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """``(fn, *shapes) -> compiled`` for one described v5e chip."""
    # A cached compile for a described chip cannot be read back without
    # one, so keep the persistent cache out of these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in shapes]
        compiled = fn.lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield run
    jax.config.update("jax_enable_compilation_cache", was)


def test_klms_chunk_compiles(compile_for_chip):
    b, t, n = KLMS_BANK, CHUNK, D_FEAT
    compile_for_chip(
        rff_klms_bank_chunk_pallas,
        (b, n), (b, t, D_IN), (b, t), (D_IN, n), (n,), (b,), (b, t),
    )


def test_klms_chunk_compiles_at_default_chunk(compile_for_chip):
    """The T the VMEM budget picks compiles under the default limit."""
    b, n = KLMS_BANK, D_FEAT
    t = default_chunk_t(b, n, input_dim=D_IN)
    assert t == 512
    compile_for_chip(
        rff_klms_bank_chunk_pallas,
        (b, n), (b, t, D_IN), (b, t), (D_IN, n), (n,), (b,), (b, t),
    )


def test_krls_chunk_compiles(compile_for_chip):
    """D=1000 pads to a 4 MiB P tile: the kernel asks for more VMEM."""
    b, t, n = KRLS_BANK, CHUNK, D_FEAT
    compile_for_chip(
        rff_krls_bank_chunk_pallas,
        (b, n), (b, n, n), (b, t, D_IN), (b, t), (D_IN, n), (n,), (b,),
        (b, t),
    )


def test_krls_chunk_compiles_at_default_chunk(compile_for_chip):
    """Where P fits the budget (D=512), the default T needs no raised
    VMEM limit."""
    b, n = KRLS_BANK, 512
    t = default_chunk_t(b, n, pmat=True, input_dim=D_IN)
    compile_for_chip(
        rff_krls_bank_chunk_pallas,
        (b, n), (b, n, n), (b, t, D_IN), (b, t), (D_IN, n), (n,), (b,),
        (b, t),
    )


@pytest.mark.parametrize("kind", ["klms", "krls"])
def test_step_kernels_compile(compile_for_chip, kind):
    n = D_FEAT
    if kind == "klms":
        b = KLMS_BANK
        compile_for_chip(
            rff_klms_bank_step_pallas,
            (b, n), (b, D_IN), (b,), (D_IN, n), (n,), (b,),
        )
    else:
        b = KRLS_BANK
        compile_for_chip(
            rff_krls_bank_step_pallas,
            (b, n), (b, n, n), (b, D_IN), (b,), (D_IN, n), (n,), (b,),
        )


@pytest.mark.parametrize(
    "bank,queries",
    [(KLMS_BANK, 64), (1, 64), (1, 1)],  # predict_block; Server.predict rows
)
def test_predict_compiles(compile_for_chip, bank, queries):
    compile_for_chip(
        rff_bank_predict_pallas,
        (bank, D_FEAT), (bank, queries, D_IN), (D_IN, D_FEAT), (D_FEAT,),
    )


@pytest.mark.parametrize("kind", ["klms", "krls"])
def test_chunk_elements_compile(compile_for_chip, kind):
    """Replay chunk elements at D=1000 and the budget's element chunk."""
    n = D_FEAT
    t = default_chunk_t(1, n, input_dim=D_IN, elements=True)
    fn = (
        rff_klms_chunk_elements_pallas
        if kind == "klms"
        else rff_krls_chunk_elements_pallas
    )
    compile_for_chip(fn, (4, t, D_IN), (4, t), (D_IN, n), (n,), ())


def test_decode_block_compiles(compile_for_chip):
    """One 8-head block of 64 tokens, head dim 128, D=256 features."""
    bh, t, dh, n = 8, 64, 128, 256
    compile_for_chip(
        rff_attention_decode_block_pallas,
        (bh, n, dh), (bh, n), (bh, t, dh), (bh, t, dh), (bh, t, dh),
        (dh, n), (n,),
    )


def test_vmem_params_only_past_the_scoped_limit():
    """Small kernels keep the compiler default; a (1024, 1024) P tile in
    and out, double-buffered, plus a P scratch, asks for a limit that
    covers all five tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(*shape):
        return pl.BlockSpec(shape, lambda i: (i,) + (0,) * (len(shape) - 1))

    assert vmem_params([spec(8, 1024), spec(128, 1024)]) is None
    p_tile = spec(None, 1024, 1024)
    big = vmem_params(
        [p_tile, p_tile], [pltpu.VMEM((1024, 1024), jnp.float32)]
    )
    assert big.vmem_limit_bytes > SCOPED_VMEM_LIMIT
    assert big.vmem_limit_bytes >= 5 * 4 * 2**20
    assert big.vmem_limit_bytes % 2**20 == 0
