"""Shared arithmetic of the per-layer metric readers (``bench/metrics``).

A reader gets an :class:`Observation` of the traced window and returns its
number, or None when the window holds nothing to read: the harness then
leaves the metric out of the result line.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from bench import work
from bench.trace_reduce import DeviceTrace

KRLS_CHUNK_KERNEL = "rff_krls_bank_chunk_pallas"


@dataclass
class Observation:
    cfg: dict
    peak: dict  # this device kind's row of bench/peaks.json
    spans: list = field(default_factory=list)  # repro.obs spans in the window
    flushes: list = field(default_factory=list)  # (active tenants, ticks) each
    device: Optional[DeviceTrace] = None


def mean_span(obs: Observation, name: str, scale: float) -> Optional[float]:
    durs = [s.duration for s in obs.spans if s.name == name]
    return scale * sum(durs) / len(durs) if durs else None


def submit_self_us(obs: Observation) -> Optional[float]:
    """Mean time of ``serve.submit`` per arrival, less its ``queue.flush``
    children."""
    submits = {s.span_id: s.duration for s in obs.spans if s.name == "serve.submit"}
    if not submits:
        return None
    flush = sum(
        s.duration for s in obs.spans
        if s.name == "queue.flush" and s.parent_id in submits
    )
    return 1e6 * (sum(submits.values()) - flush) / len(submits)


def krls_chunk_roofline(obs: Observation) -> Optional[float]:
    """Roofline share of the KRLS chunk kernel over the window's flushes."""
    if obs.device is None or not obs.flushes:
        return None
    seconds = obs.device.ops.get(KRLS_CHUNK_KERNEL, 0.0)
    if seconds <= 0.0:
        return None
    flops, nbytes = work.krls_chunk(
        obs.cfg["num_features"],
        obs.cfg["input_dim"],
        ticks=sum(t for _, t in obs.flushes),
        tenant_flushes=sum(a for a, _ in obs.flushes),
        flushes=len(obs.flushes),
    )
    return work.roofline_share(flops, nbytes, seconds, obs.peak)


def idle_share(obs: Observation) -> Optional[float]:
    if obs.device is None or obs.device.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - obs.device.busy_s / obs.device.window_s)
