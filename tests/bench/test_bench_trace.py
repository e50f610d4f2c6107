"""The reduction of a profiler trace to device busy time, kernel time and
idle gaps: on hand-made intervals, and on a trace recorded on a TPU v5e
(``data/ycsb_a.xplane.pb.gz``: one traced second of ``krls_fleet.ycsb_a``
at 100 requests/s, ``--seconds 2 --trace 1``), against a slower,
independent recount."""
import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "data" / "ycsb_a.xplane.pb.gz"


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 6)]) == [
        (0, 2.5), (3, 4), (5, 6)]
    assert tr.union([]) == []


def test_gaps_and_clip():
    busy = tr.union(tr.clip([(-1, 1), (2, 3), (8, 12)], 0, 10))
    assert busy == [(0, 1), (2, 3), (8, 10)]
    assert tr.gaps(busy, 0, 10) == [(1, 2), (3, 8)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_innermost_span_names_each_point():
    spans = [
        (0.0, 10.0, "serve.submit"),
        (2.0, 6.0, "queue.flush"),
        (3.0, 4.0, "kernel.krls_chunk"),
        (12.0, 13.0, "serve.predict"),
    ]
    points = [1.0, 2.5, 3.5, 5.0, 11.0, 12.5, 20.0]
    assert tr.innermost(spans, points) == [
        "serve.submit", "queue.flush", "kernel.krls_chunk", "queue.flush",
        "none", "serve.predict", "none"]


def test_op_names():
    assert tr.op_name(
        "%rff_krls_bank_chunk_pallas.8 = (f32[8,1,1024]) custom-call(%pad.57)"
    ) == "rff_krls_bank_chunk_pallas"
    assert tr.op_name("%copy-done.1 = f32[1,1000] copy-done(%x)") == "copy-done"
    assert tr.op_name("jit_step(1343550181198901227)") == "jit_step"


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "ycsb_a.xplane.pb"
    with gzip.open(TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load(str(path))


def _recount(profile):
    """Busy time and kernel time by brute force: device time on a 1 us
    grid over the window, and the kernel's events summed directly."""
    host = [
        e for p in profile.planes if p.name.startswith("/host:")
        for line in p.lines for e in line.events if e.name == tr.WINDOW
    ]
    lo, hi = host[0].start_ns, host[0].end_ns
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    kernel = 0.0
    for p in profile.planes:
        if not p.name.startswith("/device:TPU:"):
            continue
        for line in p.lines:
            for e in line.events:
                a, b = max(e.start_ns, lo), min(e.end_ns, hi)
                if b <= a:
                    continue
                if line.name == "XLA Modules":
                    grid[int((a - lo) // 1000):int((b - lo) // 1000)] = True
                elif line.name == "XLA Ops" and "rff_krls_bank_chunk_pallas" in e.name.split(" = ")[0]:
                    kernel += (b - a) * 1e-9
    return (hi - lo) * 1e-9, grid.sum() * 1e-6, kernel


def test_recorded_chip_trace(profile):
    t = tr.reduce(profile)
    window, busy, kernel = _recount(profile)
    assert t.devices == 1
    # As the chip run that recorded the trace printed them.
    assert t.window_s == pytest.approx(1.002891579, rel=1e-6)
    assert t.busy_s == pytest.approx(0.593417825, rel=1e-6)
    assert t.ops["rff_krls_bank_chunk_pallas"] == pytest.approx(0.442720452, rel=1e-6)
    assert t.window_s == pytest.approx(window, rel=1e-9)
    assert t.busy_s == pytest.approx(busy, abs=2e-6 * max(1, t.gaps))
    assert 0.0 < t.busy_s < t.window_s
    assert t.ops["rff_krls_bank_chunk_pallas"] == pytest.approx(kernel, rel=1e-9)
    assert "while" not in t.ops  # the loop around the kernel is not counted twice
    # Every idle second of the window is charged to some host span or none.
    assert sum(t.idle.values()) == pytest.approx(t.window_s - t.busy_s, rel=1e-6)
    assert set(t.idle) <= {"serve.submit", "serve.predict", "queue.flush",
                           "serve.flush", "serve.drain", "none"} | {
        k for k in t.idle if k.startswith(("kernel.", "bench."))}
