"""The per-layer readers of the write path's phase spans: each returns the
mean of its span in its unit, None where the window holds none, and is
reported in both ingest cells."""
from pathlib import Path

import pytest

from bench import cells, readers
from repro.obs.trace import Span

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["krls_fleet.ingest", "klms_fleet.ingest"]

# metric -> (span it reads, unit, seconds -> unit)
PHASE_METRICS = {
    "watermark_us.ingest": ("snapshot.watermark", "us", 1e6),
    "flush_batch_ms.ingest": ("queue.batch", "ms", 1e3),
    "flush_launch_ms.ingest": ("queue.launch", "ms", 1e3),
    "flush_wait_ms.ingest": ("queue.wait", "ms", 1e3),
    "flush_results_ms.ingest": ("queue.results", "ms", 1e3),
}


def _span(name, t0, seconds, span_id, parent_id=None):
    sp = Span(name, span_id, parent_id, 0 if parent_id is None else 1, t0, {})
    sp.t1 = t0 + seconds
    return sp


def _window():
    """Two submits, the second flushing: every phase span with known and
    different durations, beside the spans that enclose them."""
    durations = {
        "snapshot.watermark": [2e-6, 4e-6],
        "queue.batch": [3e-3],
        "queue.launch": [1e-3],
        "queue.wait": [7e-3],
        "queue.results": [5e-4],
    }
    spans = [_span("serve.submit", 0.0, 1e-5, 0),
             _span("serve.submit", 1.0, 2e-2, 1),
             _span("queue.flush", 1.0, 1.2e-2, 2, parent_id=1)]
    for name, secs in durations.items():
        for s in secs:
            # Start at 0: the duration is then exact in binary.
            spans.append(_span(name, 0.0, s, len(spans), parent_id=1))
    return spans, durations


def _reader(name):
    return cells.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_phase_reader_returns_the_mean_in_its_unit(metric):
    span, _unit, scale = PHASE_METRICS[metric]
    spans, durations = _window()
    obs = readers.Observation(cfg={}, peak={}, spans=spans)
    want = scale * sum(durations[span]) / len(durations[span])
    assert _reader(metric).read(obs) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_phase_reader_is_silent_without_its_span(metric):
    span = PHASE_METRICS[metric][0]
    spans, _ = _window()
    obs = readers.Observation(
        cfg={}, peak={}, spans=[s for s in spans if s.name != span])
    assert _reader(metric).read(obs) is None
    assert _reader(metric).read(readers.Observation(cfg={}, peak={})) is None


@pytest.mark.parametrize("workload", CELLS)
def test_phase_metrics_resolve_in_both_ingest_cells(workload):
    cell = cells.resolve(workload, ROOT)
    entries = {m["name"]: (m, mod) for m, mod in cell.per_layer}
    for metric, (_span_name, unit, _scale) in PHASE_METRICS.items():
        m, mod = entries[metric]
        assert m["unit"] == unit and m["source"] == "program_span"
        assert m["moves"] == "ingest_rate" and m["workloads"] == CELLS
        assert Path(mod.__file__) == ROOT / "bench" / "metrics" / f"{metric}.py"
