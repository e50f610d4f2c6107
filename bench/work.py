"""Operations and bytes that a kernel's work needs, from shapes alone.

Only useful work counts: a masked tenant-tick, padding of D to the tile
and a second copy of state count nothing. So no implementation of the same
work can read above 100% of its roofline, and one that skips masked ticks
or copies reads higher.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of one chip of ``kind`` (``device_kind`` as JAX
    reports it). A kind that is not in the table is an error."""
    with open(path) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"no published peaks for device kind {kind!r} in {path}")
    return kinds[kind]


def krls_chunk(dfeat: int, d: int, ticks: int, tenant_flushes: int,
               flushes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of ``flushes`` RFF-KRLS chunk flushes that train
    ``ticks`` unmasked tenant-ticks, ``tenant_flushes`` being the sum over
    the flushes of the tenants with at least one unmasked tick.

    Per tick (D = ``dfeat``): the featurize GEMM ``2 d D``; the matvec
    ``P z`` ``2 D^2``; the outer product ``g pz^T`` ``D^2``; the downdate
    ``(P - g pz^T) / beta`` ``2 D^2``; and ``8 D`` of vector work (bias,
    scale, prediction, ``z.pz``, gain, theta update).

    Bytes: each active tenant's P and theta read and written once per
    flush, the shared W, b and scale read once per flush, and per tick the
    input (x, y, mask) read and the prediction and error written.
    """
    D = dfeat
    flops = ticks * (5 * D * D + 2 * d * D + 8 * D)
    nbytes = F32 * (
        tenant_flushes * 2 * (D * D + D)
        + flushes * (d * D + 2 * D)
        + ticks * (d + 2 + 2)
    )
    return float(flops), float(nbytes)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Percent of the chip's roofline: the least time the work could take
    (the larger of flops over peak FLOP/s and bytes over peak bandwidth)
    over the time it took."""
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
