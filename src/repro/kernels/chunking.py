"""Time-axis chunking utilities shared by every chunked run-loop.

One place for the pad-to-multiple / reshape-into-blocks / masked-remainder
bookkeeping so the kernel dispatchers (kernels/ops.py), the single-stream
drivers (core/klms.py, core/krls.py) and the sharded combine_every driver
(core/krls.py) can't drift apart on remainder handling.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "num_chunks",
    "time_blocks",
    "valid_time_mask",
    "unblock_time",
    "default_chunk_t",
    "default_decode_block_t",
    "vmem_params",
]

# The scoped-VMEM limit the TPU compiler gives one kernel unless the kernel
# asks for more: 16 MiB on v5e (its refusals read "limit 16.00M"). A
# kernel's double-buffered blocks plus its scratch must fit under it.
SCOPED_VMEM_LIMIT = 16 * 2**20
# Headroom kept above a kernel's counted blocks, scratch and temporaries
# for what the compiler adds on its own.
_VMEM_SLACK = 2**20

# Per-launch VMEM budget for the chunked kernels: the default limit less
# the slack, so the T chosen by :func:`default_chunk_t` never makes a
# kernel ask for a raised limit that its resident state alone does not
# need. Checked by compiling for v5e (tests/test_tpu_compile.py).
DEFAULT_VMEM_BUDGET = SCOPED_VMEM_LIMIT - _VMEM_SLACK


def _tile_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of one block: the last two dims pad to (8, 128) tiles;
    squeezed (None) dims count as 1."""
    shape = tuple(1 if s is None else s for s in shape)
    *lead, r, c = (1,) * max(0, 2 - len(shape)) + shape
    return math.prod(lead) * (-(-r // 8) * 8) * (-(-c // 128) * 128) * itemsize


def _vmem_bytes(blocks=(), scratch=(), temps=(), itemsize: int = 4) -> int:
    """VMEM a kernel holds: every block double-buffered by the pipeline,
    plus its scratch and the largest values its body holds at once (all
    given as shapes)."""
    return (
        2 * sum(_tile_bytes(b, itemsize) for b in blocks)
        + sum(_tile_bytes(v, itemsize) for v in scratch)
        + sum(_tile_bytes(t, itemsize) for t in temps)
    )


def vmem_params(specs, scratch=(), temps=()):
    """Compiler params for a kernel whose VMEM may exceed the scoped limit.

    ``specs`` are the kernel's input and output BlockSpecs, ``scratch`` its
    VMEM scratch shapes and ``temps`` the shapes of the largest values its
    body holds at once. Returns None (the compiler default) when their
    :func:`_vmem_bytes` plus the slack fits the default limit, else a limit
    of that sum rounded up to a MiB. The KRLS kernels at D=1024 are the
    case: their (D, D) P tile is 4 MiB, so in and out blocks alone take
    16 MiB (the chunk kernel compiled for v5e at a 24 MiB limit and was
    refused at 22 MiB).
    """
    need = _vmem_bytes(
        [s.block_shape for s in specs], [v.shape for v in scratch], temps
    ) + _VMEM_SLACK
    if need <= SCOPED_VMEM_LIMIT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=-(-need // 2**20) * 2**20)


# Shared tiling helpers of the one-tenant and time-blocked kernels.


def _lead_block(rows: int, cols: int) -> pl.BlockSpec:
    """Slab ``i`` of a ``(n, rows, cols)`` array, the leading dim squeezed.

    One-tenant (or one-chunk) kernels lay per-tenant rows out as
    ``(n, 1, N)`` and per-tenant inputs as ``(n, T, d)``, so the block's
    last two dims equal the array's (the TPU tiling rule); the index map
    ignores any tick axis, so the slab stays resident across a chunk.
    """
    return pl.BlockSpec((None, rows, cols), lambda i, *_: (i, 0, 0))


# Per-(row, tick) scalars of the time-blocked kernels (targets, masks,
# predictions, errors) sit in one ``(rows, T)`` tile that stays resident for
# the whole chunk: a ``(rows, 1)`` block per tick breaks the TPU tiling rule,
# and Mosaic cannot index the lane dim at a traced offset. Column ``t`` is
# therefore read and written through a lane-iota select — one value plus
# zeros per row, so the read is exact.


def _tick_get(ref, t) -> jax.Array:
    """Column ``t`` of a resident ``(rows, T)`` tile, as ``(rows, 1)``."""
    tile = ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, tile.ndim - 1)
    return jnp.sum(jnp.where(lane == t, tile, 0), axis=-1, keepdims=True)


def _tick_put(ref, t, val: jax.Array) -> None:
    """Write ``(rows, 1)`` ``val`` into column ``t`` of a resident tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, len(ref.shape) - 1)
    ref[...] = jnp.where(lane == t, val.astype(ref.dtype), ref[...])


# Bank-axis block the KLMS chunk kernel tiles with (rff_klms_step.py
# block_b default); the KRLS chunk and element kernels own one tenant or
# one chunk per grid row.
_BLOCK_B = 8
_LANES = 128


def _chunk_kernel_vmem(bb, din, dp, pmat, elements):
    """The blocks of the chunk kernel :func:`default_chunk_t` sizes, split
    into what T does not change (f32 blocks, scratch, temporaries) and a
    ``t -> blocks`` function for the stream-dtype tiles T does change."""
    shared = [(din, dp), (1, dp), (1, dp)]  # grid-invariant W, b, s
    if elements:  # kernels/rff_scan.py: one chunk per grid row
        fixed = shared + [(1, 1), (1, 1), (dp, dp), (1, dp)]
        return fixed, [(1, 1), (dp, dp), (1, dp)], [(dp, dp)], (
            lambda t: [(t, din), (1, t), (1, t)]  # x slab, y, mask
        )
    if pmat:  # kernels/rff_krls_step.py chunk kernel: one tenant per row
        fixed = shared + [(1, dp), (dp, dp), (1, 1), (1, dp), (dp, dp)]
        return fixed, [(1, dp), (dp, dp)], [(dp, dp)], (
            lambda t: [(t, din)] + [(1, t)] * 4  # x slab, y/mask/pred/err
        )
    # kernels/rff_klms_step.py chunk kernel: x streamed per tick.
    fixed = shared + [(bb, din), (bb, dp), (bb, 1), (bb, dp)]
    return fixed, [(bb, dp)], [], lambda t: [(bb, t)] * 4


def default_chunk_t(
    bank: int,
    dfeat: int,
    dtype=jnp.float32,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    pmat: bool = False,
    input_dim: int | None = None,
    elements: bool = False,
) -> int:
    """VMEM-budget-aware default tick count T for one chunked launch.

    Counts the chunk kernel's own blocks the way :func:`vmem_params` does
    (tile-padded, double-buffered, plus scratch and temporaries): the state
    resident per grid row (theta ``(block_b, D)`` for KLMS; one tenant's
    ``(D, D)`` P tile for KRLS; the ``(D, D)`` element accumulator and
    output for the replay elements), the grid-invariant ``W`` tile, and the
    tiles that grow with T — the ``(rows, T)`` target/mask/prediction/error
    tiles and, for the one-tenant kernels, the resident ``(T, d)`` input
    slab. T is the largest power of two in [8, 512] whose total fits
    ``vmem_budget``. When the resident state alone busts the budget
    (huge-D KRLS) the floor of 8 still amortizes dispatch; the kernel then
    raises its own limit (:func:`vmem_params`).

    ``bank`` only matters below the bank-block width; ``dtype`` is the
    *stream* dtype — state is always charged at f32. ``input_dim`` is the
    true input d, charged at its lane-padded width (default: one 128-lane
    tile — the low-d serving shapes). ``pmat=True`` sizes the KRLS chunk
    kernel, ``elements=True`` the replay chunk-element kernels
    (kernels/rff_scan.py); neither sizes the KLMS chunk kernel.
    """
    item = jnp.dtype(dtype).itemsize
    bb = max(1, min(_BLOCK_B, bank))
    dp = -(-dfeat // _LANES) * _LANES
    din = _LANES if input_dim is None else -(-input_dim // _LANES) * _LANES
    fixed, scratch, temps, grows = _chunk_kernel_vmem(
        bb, din, dp, pmat, elements
    )
    spare = vmem_budget - _vmem_bytes(fixed, scratch, temps)
    t = 512
    while t > 8 and _vmem_bytes(grows(t), itemsize=item) > spare:
        t //= 2
    return t


def default_decode_block_t(
    dfeat: int,
    dv: int,
    head_dim: int,
    dtype=jnp.float32,
    vmem_budget: int = SCOPED_VMEM_LIMIT // 2,
) -> int:
    """VMEM-budget-aware default T for one fused decode-block launch.

    The decode-block attention kernel (kernels/rff_attention.py) owns one
    head per grid step: the ``(D, dv)`` S tile, the ``(D,)`` z row and the
    grid-invariant ``(dh, D)`` W tile are resident for the whole block
    (that residency IS the win — one state read/write per T ticks), and
    each token streams two ``(dh,)`` q/k rows, a ``(dv,)`` v row, a
    ``(dv,)`` output row and two ``(D,)`` feature rows. T is the largest
    power of two whose streamed tokens fit the budget left after the
    resident tiles, clamped to [8, 512] exactly like
    :func:`default_chunk_t`. ``dtype`` is the *stream* dtype (bf16 halves
    the feature-row charge under the read-path precision contract); state
    is always charged at f32. Unlike :func:`default_chunk_t` this charge
    counts no double-buffering or scratch, so its default budget is half
    the scoped limit.
    """
    item = jnp.dtype(dtype).itemsize
    dp = -(-dfeat // _LANES) * _LANES
    dhp = -(-head_dim // _LANES) * _LANES
    dvp = -(-dv // _LANES) * _LANES
    state_bytes = dp * dvp * 4 + dp * 4  # resident S tile + z row, f32
    w_bytes = dhp * dp * 4  # grid-invariant W tile
    # Per token: q/k rows, v + output rows, phi_q/phi_k feature rows.
    stream_bytes = (2 * dhp + 2 * dvp) * item + 2 * dp * item
    spare = vmem_budget - state_bytes - w_bytes
    if spare < 8 * stream_bytes:
        return 8
    t = 1 << ((spare // stream_bytes).bit_length() - 1)  # floor pow2
    return int(min(512, t))


def num_chunks(n: int, chunk: int) -> int:
    """ceil(n / chunk) — the scan length after chunking."""
    return -(-n // chunk)


def time_blocks(a: jax.Array, chunk: int, axis: int = 0) -> jax.Array:
    """Zero-pad ``axis`` to a multiple of ``chunk`` and split it into a
    leading scan axis: ``(..., n, ...) -> (nc, ..., chunk, ...)``."""
    n = a.shape[axis]
    nc = num_chunks(n, chunk)
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, nc * chunk - n)
    ap = jnp.pad(a, widths)
    ap = ap.reshape(a.shape[:axis] + (nc, chunk) + a.shape[axis + 1 :])
    return jnp.moveaxis(ap, axis, 0)


def valid_time_mask(n: int, chunk: int, dtype=jnp.float32) -> jax.Array:
    """``(nc, chunk)`` gate: 1 for real ticks, 0 for the padded tail."""
    nc = num_chunks(n, chunk)
    return jnp.pad(jnp.ones((n,), dtype), (0, nc * chunk - n)).reshape(
        nc, chunk,
    )


def unblock_time(a: jax.Array, n: int, axis: int = 0) -> jax.Array:
    """Inverse of :func:`time_blocks` on stacked scan outputs:
    ``(nc, ..., chunk, ...) -> (..., n, ...)`` with the padding sliced off."""
    a = jnp.moveaxis(a, 0, axis)  # (..., nc, chunk, ...)
    a = a.reshape(a.shape[:axis] + (-1,) + a.shape[axis + 2 :])
    return jax.lax.slice_in_dim(a, 0, n, axis=axis)
