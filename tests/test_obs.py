"""Observability-layer contracts (repro/obs, serve/metrics, kernel dispatch).

Five families:

* tracer — span nesting/ordering/depth with an injected fake clock,
  ring-buffer overflow truncation accounting, JSONL and Chrome trace-event
  exports (the Chrome export must also satisfy the repo's own
  ``scripts/check_bench_schema.py --trace`` validator);
* probes — ``stats_tap`` reductions pinned against pure-numpy oracles
  (including the non-finite latch), ``ProbeMonitor`` degradation events at
  the pinned default thresholds, event-buffer capping;
* metrics — ``Histogram.observe`` float-exponent bucketing (sub-unit
  observations must NOT collapse into bucket 0 — the bug the frexp fix
  removed), percentile semantics, cross-registry ``merge``;
* dispatch telemetry — live launch/remainder counters and bytes-moved
  gauges from the kernels/ops.py host wrappers, and the traced-vs-live
  split under an enclosing jit;
* server integration — observability must be a pure *observer*: a traced
  + probed server is BITWISE state-identical to an untraced one on the
  same stream, its spans cover the serve tiers, its flush overhead stays
  within a pinned (generous) factor, and ``Server.observability()``
  exports the documented schema; the write path's phase spans nest as
  the benchmark's readers assume (``queue.flush`` a direct child of
  ``serve.submit``), and the backlog gauge is set at export.
"""
import importlib.util
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rff import sample_rff
from repro.kernels import ops
from repro.obs import probes as obs_probes
from repro.obs import telemetry as obs_telemetry
from repro.obs import trace as obs_trace
from repro.serve import api
from repro.serve.metrics import Histogram, MetricsRegistry

D_IN, D_FEAT = 3, 16
RFF = sample_rff(jax.random.PRNGKey(0), D_IN, D_FEAT, 1.0)


class FakeClock:
    """Deterministic monotonic clock: advances by ``step`` per call."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def ragged_traffic(tenants=3, n=24, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            int(rng.integers(0, tenants)),
            rng.normal(size=D_IN).astype(np.float32),
            float(rng.normal()),
        )
        for _ in range(n)
    ]


def assert_trees_bitwise(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# Tracer: nesting, ordering, ring overflow, exports
# ---------------------------------------------------------------------------


def test_span_nesting_parents_depths_and_close_order():
    tr = obs_trace.Tracer(clock=FakeClock())
    with tr.span("serve.submit", tenant=1) as outer:
        with tr.span("queue.flush") as mid:
            with tr.span("kernel.klms_chunk"):
                pass
        tr.instant("snapshot.publish", version=2)
    spans = tr.spans()
    # Spans record at close (innermost first); instants record when called.
    assert [s.name for s in spans] == [
        "kernel.klms_chunk", "queue.flush", "snapshot.publish",
        "serve.submit",
    ]
    by_name = {s.name: s for s in spans}
    k, q, s = (
        by_name["kernel.klms_chunk"],
        by_name["queue.flush"],
        by_name["serve.submit"],
    )
    assert s.parent_id is None and s.depth == 0
    assert q.parent_id == s.span_id and q.depth == 1
    assert k.parent_id == q.span_id and k.depth == 2
    inst = by_name["snapshot.publish"]
    assert inst.kind == "instant"
    assert inst.parent_id == s.span_id and inst.duration == 0.0
    assert mid.t1 is not None and outer.t1 is not None
    # Fake clock: every span got a strictly positive integer duration.
    assert k.duration > 0 and q.duration > k.duration
    assert s.attrs == {"tenant": 1}


def test_ring_overflow_drops_oldest_and_flags_truncation():
    tr = obs_trace.Tracer(capacity=4, clock=FakeClock())
    for i in range(10):
        with tr.span(f"serve.op{i}"):
            pass
    assert len(tr.spans()) == 4
    assert [s.name for s in tr.spans()] == [
        "serve.op6", "serve.op7", "serve.op8", "serve.op9",
    ]
    assert tr.dropped == 6 and tr.truncated
    header = json.loads(tr.to_jsonl().splitlines()[0])
    assert header == {
        "kind": "header", "spans": 4, "dropped": 6, "truncated": True,
    }
    chrome = tr.to_chrome_trace()
    assert chrome["otherData"] == {"dropped": 6, "truncated": True}


def test_tracer_rejects_zero_capacity():
    with pytest.raises(ValueError, match="capacity"):
        obs_trace.Tracer(capacity=0)


def test_jsonl_round_trips_every_span():
    tr = obs_trace.Tracer(clock=FakeClock())
    with tr.span("serve.flush", ticks=3):
        tr.instant("probe.degraded", probe="finite")
    lines = [json.loads(ln) for ln in tr.to_jsonl().splitlines()]
    assert lines[0]["kind"] == "header" and not lines[0]["truncated"]
    recs = {r["name"]: r for r in lines[1:]}
    assert recs["serve.flush"]["attrs"] == {"ticks": 3}
    assert recs["serve.flush"]["dur_us"] > 0
    assert recs["probe.degraded"]["kind"] == "instant"


def _load_schema_checker():
    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "check_bench_schema.py"
    )
    spec = importlib.util.spec_from_file_location("check_bench_schema", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chrome_trace_round_trip_and_schema(tmp_path):
    tr = obs_trace.Tracer(clock=FakeClock())
    with tr.span("serve.submit", tenant=0):
        with tr.span("queue.flush"):
            with tr.span("kernel.klms_chunk", dtype=jnp.float32.dtype):
                pass
        tr.instant("snapshot.publish", version=1)
    path = tmp_path / "trace.json"
    payload = tr.to_chrome_trace(str(path))
    loaded = json.load(open(path))
    assert loaded == json.loads(json.dumps(payload))  # file == return value
    for ev in loaded["traceEvents"]:
        if ev["ph"] == "X":
            assert ev["dur"] > 0
        else:
            assert ev["ph"] == "i"
        json.dumps(ev["args"])  # attrs stayed JSON-able (dtype stringified)
    checker = _load_schema_checker()
    assert checker.check_trace(str(path)) == []
    # And the validator actually bites: drop the kernel span.
    loaded["traceEvents"] = [
        e for e in loaded["traceEvents"] if not e["name"].startswith("kernel.")
    ]
    bad = tmp_path / "bad.json"
    json.dump(loaded, open(bad, "w"))
    errs = checker.check_trace(str(bad))
    assert any("kernel" in e for e in errs)


def test_ambient_helpers_noop_without_active_tracer():
    assert obs_trace.current_tracer() is None
    with obs_trace.span("serve.submit") as sp:
        assert sp is None  # shared null context — untraced fast path
    assert obs_trace.instant("snapshot.publish") is None
    tr = obs_trace.Tracer(clock=FakeClock())
    with obs_trace.activate(None):  # no-op activation needs no branching
        assert obs_trace.current_tracer() is None
    with obs_trace.activate(tr):
        assert obs_trace.current_tracer() is tr
        with obs_trace.span("serve.submit"):
            obs_trace.instant("snapshot.publish")
    assert obs_trace.current_tracer() is None
    assert {s.name for s in tr.spans()} == {
        "serve.submit", "snapshot.publish",
    }


# ---------------------------------------------------------------------------
# Probes: stats_tap vs numpy oracles, monitor thresholds
# ---------------------------------------------------------------------------


def _tap_state(seed=0, poison=False):
    rng = np.random.default_rng(seed)
    theta = rng.normal(size=(3, 8)).astype(np.float32)
    pmat = rng.normal(size=(3, 8, 8)).astype(np.float32)
    pmat = pmat + np.swapaxes(pmat, -1, -2)  # symmetric base
    pmat += 1e-3 * rng.normal(size=pmat.shape).astype(np.float32)
    if poison:
        theta[1, 2] = np.nan
    return {
        "theta": jnp.asarray(theta),
        "pmat": jnp.asarray(pmat),
        "steps": jnp.arange(3, dtype=jnp.int32),  # int leaf: skipped
    }


def test_stats_tap_matches_numpy_oracles():
    state = _tap_state()
    stats = jax.jit(obs_probes.stats_tap)(state)
    theta = np.asarray(state["theta"], np.float64).astype(np.float32)
    pmat = np.asarray(state["pmat"], np.float32)
    assert float(stats["finite"]) == 1.0
    np.testing.assert_allclose(
        float(stats["theta.max_abs"]), np.abs(theta).max(), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(stats["theta.norm_max"]),
        np.sqrt((theta.astype(np.float64) ** 2).sum(-1)).max(),
        rtol=1e-5,
    )
    asym = np.abs(pmat - np.swapaxes(pmat, -1, -2)).max()
    scale = np.abs(pmat).max()
    np.testing.assert_allclose(
        float(stats["pmat.asym_rel"]), asym / scale, rtol=1e-5
    )
    diag = np.abs(np.diagonal(pmat, axis1=-2, axis2=-1))
    np.testing.assert_allclose(
        float(stats["pmat.diag_min"]), diag.min(), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(stats["pmat.cond_proxy"]), diag.max() / diag.min(), rtol=1e-5
    )
    assert not any(k.startswith("steps") for k in stats)  # int leaf skipped


def test_stats_tap_latches_nonfinite():
    stats = obs_probes.stats_tap(_tap_state(poison=True))
    assert float(stats["finite"]) == 0.0


def test_default_thresholds_are_pinned():
    # The documented degradation floors — moving them is an API change.
    t = obs_probes.DEFAULT_THRESHOLDS
    assert t["finite"] == ("min", 1.0)
    assert t["theta.norm_max"] == ("max", 1e6)
    assert t["pmat.asym_rel"] == ("max", 1e-2)
    assert t["pmat.cond_proxy"] == ("max", 1e12)
    assert t["bf16_read_error"] == ("max", 2e-2)


def test_monitor_fires_events_at_pinned_thresholds():
    reg = MetricsRegistry()
    mon = obs_probes.ProbeMonitor(registry=reg)
    tr = obs_trace.Tracer(clock=FakeClock())
    with obs_trace.activate(tr):
        fired = mon.update(
            {"finite": 0.0, "theta.norm_max": 2e6, "pmat.asym_rel": 1e-4},
            tick=7,
        )
    assert {e.probe for e in fired} == {"finite", "theta.norm_max"}
    by_probe = {e.probe: e for e in fired}
    assert by_probe["finite"].direction == "below"
    assert by_probe["theta.norm_max"].direction == "above"
    assert by_probe["theta.norm_max"].threshold == 1e6
    assert by_probe["theta.norm_max"].tick == 7
    assert not mon.healthy() and mon.total_events == 2
    assert reg.count("probe.degraded", probe="finite") == 1
    # Breaches also land as instant events in the active trace.
    marks = [s for s in tr.spans() if s.name == "probe.degraded"]
    assert {m.attrs["probe"] for m in marks} == {"finite", "theta.norm_max"}
    # Healthy update: nothing fires, stats still recorded.
    assert mon.update({"finite": 1.0, "theta.norm_max": 3.0}) == []
    assert mon.last_stats["theta.norm_max"] == 3.0
    assert mon.total_events == 2


def test_monitor_staleness_bf16_and_override_forms():
    mon = obs_probes.ProbeMonitor(
        thresholds={"staleness_ticks": 3, "bf16_read_error": ("max", 1e-3)},
    )
    fired = mon.update({}, staleness=5, bf16_err=5e-4)
    assert [e.probe for e in fired] == ["staleness_ticks"]
    fired = mon.update({}, staleness=1, bf16_err=2e-3)
    assert [e.probe for e in fired] == ["bf16_read_error"]
    state = mon.state()
    assert state["total_events"] == 2 and not state["healthy"]
    assert state["thresholds"]["staleness_ticks"]["value"] == 3.0
    # inf-bounded probes are omitted from the exported threshold table.
    assert "staleness_ticks" in state["thresholds"]


def test_monitor_event_buffer_caps_but_total_keeps_counting():
    mon = obs_probes.ProbeMonitor(max_events=4)
    for i in range(10):
        mon.update({"finite": 0.0}, tick=i)
    assert mon.total_events == 10
    assert len(mon.events) == 4
    assert [e.tick for e in mon.events] == [6, 7, 8, 9]


# ---------------------------------------------------------------------------
# Metrics: frexp bucketing, percentiles, merge
# ---------------------------------------------------------------------------


def test_histogram_sub_unit_observations_resolve_into_distinct_buckets():
    h = Histogram()
    # The old int(v).bit_length() rule put ALL of these in bucket 0.
    for v in (1e-3, 2e-3, 0.1, 0.5):
        assert h._bucket(v) > 0, v
    assert h._bucket(1e-3) != h._bucket(2e-3)
    assert h._bucket(0.1) != h._bucket(0.5)
    assert h._bucket(0.0) == 0
    # Bucket bounds bracket the value (the interpolation contract).
    for v in (1e-3, 0.37, 1.0, 3.5, 1e6):
        lo, hi = h._bucket_range(h._bucket(v))
        assert lo <= v <= hi or math.isclose(v, hi)


def test_histogram_percentile_semantics_pinned():
    h = Histogram()
    for _ in range(50):
        h.observe(1.0)
    for _ in range(50):
        h.observe(100.0)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    # One-octave resolution: p50 lands at the top of 1.0's [1, 2) octave;
    # p95/p99 interpolate past 100 and clamp to the exact observed max.
    assert s["p50"] == 2.0
    assert s["p95"] == 100.0 and s["p99"] == 100.0
    assert s["p50"] <= s["p95"] <= s["p99"]


def test_histogram_merge_equals_single_stream():
    rng = np.random.default_rng(3)
    a_vals = rng.lognormal(0.0, 2.0, 200)
    b_vals = rng.lognormal(1.0, 1.0, 300)
    ha, hb, hall = Histogram(), Histogram(), Histogram()
    for v in a_vals:
        ha.observe(v)
        hall.observe(v)
    for v in b_vals:
        hb.observe(v)
        hall.observe(v)
    merged = ha.merge(hb)
    assert merged is ha
    assert merged.counts == hall.counts
    ms, hs = merged.summary(), hall.summary()
    for k in ("count", "min", "max", "p50", "p95", "p99"):
        assert ms[k] == hs[k], k
    assert ms["mean"] == pytest.approx(hs["mean"])  # summation order
    with pytest.raises(ValueError, match="bucket mismatch"):
        Histogram(max_buckets=8).merge(Histogram(max_buckets=16))


def test_registry_labels_and_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("kernel.launches", op="klms_chunk").inc(3)
    b.counter("kernel.launches", op="klms_chunk").inc(2)
    b.counter("kernel.launches", op="krls_chunk").inc()
    a.set_gauge("kernel.bytes_moved", 10.0, op="klms_chunk")
    b.set_gauge("kernel.bytes_moved", 20.0, op="klms_chunk")
    a.histogram("latency.write_us").observe(4.0)
    b.histogram("latency.write_us").observe(16.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["counters"]["kernel.launches{op=klms_chunk}"] == 5
    assert snap["counters"]["kernel.launches{op=krls_chunk}"] == 1
    assert snap["gauges"]["kernel.bytes_moved{op=klms_chunk}"] == 20.0
    assert snap["histograms"]["latency.write_us"]["count"] == 2


# ---------------------------------------------------------------------------
# Dispatch telemetry: live vs traced counting, bytes gauges
# ---------------------------------------------------------------------------


def _chunk_operands(bank=2, tlen=10, seed=0):
    rng = np.random.default_rng(seed)
    theta = jnp.zeros((bank, D_FEAT), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(bank, tlen, D_IN)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(bank, tlen)), jnp.float32)
    return theta, xs, ys


def test_live_dispatch_counts_launches_and_remainder():
    obs_telemetry.reset()
    theta, xs, ys = _chunk_operands(bank=2, tlen=10)
    ops.rff_klms_bank_chunk(theta, xs, ys, RFF.omega, RFF.bias, 0.2, chunk=4)
    reg = obs_telemetry.registry()
    # T=10 at chunk 4 -> 3 launches, the last one a masked remainder.
    assert reg.count("kernel.launches", op="klms_chunk") == 3
    assert reg.count("kernel.remainder_launches", op="klms_chunk") == 1
    assert reg.count("kernel.traces", op="klms_chunk") == 0
    bm = obs_telemetry.klms_chunk_bytes(2, D_IN, D_FEAT, 4)
    expect = bm["launch_bytes"] * 3 + bm["stream_bytes_per_tick"] * 10
    assert reg.gauge("kernel.bytes_moved", op="klms_chunk") == expect


def test_dispatch_under_enclosing_jit_counts_as_trace_not_launch():
    obs_telemetry.reset()
    theta, xs, ys = _chunk_operands(bank=2, tlen=10, seed=1)

    @jax.jit
    def program(th, x, y):
        th, preds, errs = ops.rff_klms_bank_chunk(
            th, x, y, RFF.omega, RFF.bias, 0.2, chunk=4
        )
        return th, preds, errs

    program(theta, xs, ys)
    program(theta, xs, ys)  # second call: cached program, no re-trace
    reg = obs_telemetry.registry()
    assert reg.count("kernel.traces", op="klms_chunk") == 1
    assert reg.count("kernel.launches", op="klms_chunk") == 0


def test_dispatch_spans_carry_shape_attrs():
    obs_telemetry.reset()
    tr = obs_trace.Tracer(clock=FakeClock())
    theta, xs, ys = _chunk_operands(bank=2, tlen=10, seed=2)
    with obs_trace.activate(tr):
        ops.rff_klms_bank_chunk(theta, xs, ys, RFF.omega, RFF.bias, 0.2, chunk=4)
    (sp,) = [s for s in tr.spans() if s.name == "kernel.klms_chunk"]
    assert sp.attrs["shape"] == [2, 10, D_IN]
    assert sp.attrs["dfeat"] == D_FEAT
    assert sp.attrs["launches"] == 3
    assert sp.attrs["traced"] is False
    assert sp.attrs["chunk"] == 4


# ---------------------------------------------------------------------------
# Server integration: bitwise purity, span coverage, overhead, export
# ---------------------------------------------------------------------------


def _drive(srv, traffic, read_every=5):
    for i, (t, x, y) in enumerate(traffic):
        if i % read_every == read_every - 1:
            srv.predict(t, x)
        else:
            srv.submit(t, x, y)
    srv.drain()


@pytest.mark.parametrize(
    "learner,hp",
    [
        ("klms", dict(mu=0.3)),
        ("krls", dict(beta=0.999, lam=0.1)),
    ],
)
def test_traced_probed_server_is_bitwise_identical_to_untraced(learner, hp):
    traffic = ragged_traffic(tenants=3, n=24, seed=4)
    plain = api.make_server(
        learner, feature_map=RFF, bank=3, chunk=4, **hp
    )
    traced = api.make_server(
        learner, feature_map=RFF, bank=3, chunk=4, trace=True, probe=True,
        **hp,
    )
    _drive(plain, traffic)
    _drive(traced, traffic)
    assert_trees_bitwise(plain.queue.state, traced.queue.state)
    # The observer actually observed: spans from the serve tiers...
    by_name = traced.tracer.summary()["by_name"]
    assert any(n.startswith("serve.") for n in by_name)
    assert any(n.startswith("queue.") for n in by_name)
    assert any(n.startswith("snapshot.") for n in by_name)
    # ...and the probe tap read real state at flush boundaries.
    assert traced.probe.updates > 0
    assert traced.probe.last_stats["finite"] == 1.0
    if learner == "krls":
        assert "pmat.asym_rel" in traced.probe.last_stats


def test_observability_export_schema_and_read_contract():
    srv = api.make_server(
        "klms", feature_map=RFF, bank=2, chunk=4, mu=0.3,
        trace=True, probe=True,
    )
    _drive(srv, ragged_traffic(tenants=2, n=16, seed=7))
    xq = np.ones((2, 3, D_IN), np.float32)
    err = srv.check_read_contract(xq)
    assert isinstance(err, float) and 0.0 <= err < 0.05
    assert srv.probe.last_stats["bf16_read_error"] == err
    out = srv.observability()
    assert set(out) == {"metrics", "dispatch", "probes", "trace"}
    assert "histograms" in out["metrics"]
    assert out["metrics"]["counters"]["requests.write"] > 0
    assert any(
        k.startswith("dispatch.launches") for k in out["dispatch"]["counters"]
    )
    assert out["probes"]["healthy"] in (True, False)
    assert out["trace"]["spans"] > 0 and "by_name" in out["trace"]
    json.dumps(out)  # the whole export is JSON-able as documented


def test_untraced_server_has_no_observability_overheads_wired():
    srv = api.make_server("klms", feature_map=RFF, bank=2, chunk=4, mu=0.3)
    assert srv.tracer is None and srv.probe is None
    out = srv.observability()
    assert out["probes"] is None and out["trace"] is None


def test_traced_flush_overhead_within_pinned_factor():
    def build(**obs_kw):
        return api.make_server(
            "klms", feature_map=RFF, bank=2, chunk=4, mu=0.3, **obs_kw
        )

    def cycle(srv, n=40):
        x = np.ones(D_IN, np.float32)
        t0 = time.perf_counter()
        for i in range(n):
            srv.submit(i % 2, x, 1.0)
            srv.flush()
        return time.perf_counter() - t0

    plain, traced = build(), build(trace=True, probe=True)
    cycle(plain, n=8)  # warm both (compile paths, allocator)
    cycle(traced, n=8)
    dt_plain = min(cycle(plain) for _ in range(3))
    dt_traced = min(cycle(traced) for _ in range(3))
    # Generous pin: spans + probe materialization must stay the same order
    # of magnitude as the flush itself, not multiply it.
    assert dt_traced < dt_plain * 20 + 0.05


# ---------------------------------------------------------------------------
# Write-path phase spans and the export-time backlog gauge
# ---------------------------------------------------------------------------

_PHASES = ["queue.batch", "queue.launch", "queue.wait", "queue.results"]
_WRITE_CASES = [
    ("klms", dict(mu=0.3)),
    ("krls", dict(beta=0.999, lam=0.1)),
]


def _watermark_server(learner, hp, trace=None):
    return api.make_server(
        learner, feature_map=RFF, bank=3, chunk=4, size_watermark=4,
        trace=trace, **hp,
    )


def _span_tree(spans, parent_id=None):
    """Nested ``(name, children)`` of the completed spans, children in
    start order; trace-time ``kernel.*`` spans (first call only) left
    out."""
    kids = sorted(
        (s for s in spans
         if s.kind == "span" and s.parent_id == parent_id
         and not s.name.startswith("kernel.")),
        key=lambda s: s.t0,
    )
    return [(s.name, _span_tree(spans, s.span_id)) for s in kids]


@pytest.mark.parametrize("learner,hp", _WRITE_CASES)
def test_write_path_phase_spans_nest_under_submit(learner, hp):
    srv = _watermark_server(learner, hp, obs_trace.Tracer(clock=FakeClock()))
    x = np.ones(D_IN, np.float32)
    for _ in range(2):  # two size-watermark flushes, the second one warm
        for i in range(4):
            srv.submit(1, x * i, float(i))
    spans = srv.tracer.spans()
    quiet = ("serve.submit", [("snapshot.watermark", [])])
    flushing = ("serve.submit", [
        ("snapshot.watermark", []),
        ("queue.flush", [(name, []) for name in _PHASES]),
    ])
    assert _span_tree(spans) == [quiet] * 3 + [flushing] + [quiet] * 3 + [
        flushing]
    by_id = {s.span_id: s for s in spans}
    flushes = [s for s in spans if s.name == "queue.flush"]
    assert len(flushes) == 2
    for fl in flushes:
        # A direct child of serve.submit: the watermark span closes before
        # the flush it triggers opens.
        assert by_id[fl.parent_id].name == "serve.submit"
        assert set(fl.attrs) == {"tenants", "chunk", "adaptive", "ticks",
                                 "active"}
        assert fl.attrs["ticks"] == 4 and fl.attrs["active"] == 1
        phases = sorted(
            (s for s in spans if s.parent_id == fl.span_id),
            key=lambda s: s.t0,
        )
        assert [s.name for s in phases] == _PHASES
        assert fl.t0 < phases[0].t0
        assert all(a.t1 < b.t0 for a, b in zip(phases, phases[1:]))
        assert phases[-1].t1 < fl.t1
        assert phases[1].attrs == {"bytes": 3 * 4 * (D_IN + 2) * 4}
    for s in spans:
        if s.name.startswith("kernel."):
            assert by_id[s.parent_id].name == "queue.launch"


@pytest.mark.parametrize("learner,hp", _WRITE_CASES)
def test_phase_spans_leave_a_watermark_server_bitwise_unchanged(learner, hp):
    traffic = ragged_traffic(tenants=3, n=30, seed=11)
    plain = _watermark_server(learner, hp)
    traced = _watermark_server(learner, hp, obs_trace.Tracer(clock=FakeClock()))
    for srv in (plain, traced):
        for t, x, y in traffic:
            srv.submit(t, x, y)
    assert plain.queue.flushes == traced.queue.flushes > 0
    assert plain.queue.backlog() == traced.queue.backlog()
    assert_trees_bitwise(plain.queue.state, traced.queue.state)
    assert_trees_bitwise(plain.snapshot.state, traced.snapshot.state)
    plain.drain()
    traced.drain()
    assert_trees_bitwise(plain.queue.state, traced.queue.state)


@pytest.mark.parametrize("learner,hp", _WRITE_CASES)
def test_backlog_gauge_is_set_at_export_not_per_arrival(learner, hp):
    srv = _watermark_server(learner, hp)
    x = np.ones(D_IN, np.float32)

    def exported():
        return srv.observability()["metrics"]["gauges"]["queue.backlog"]

    for t in (0, 1, 1, 2, 2, 2):
        srv.submit(t, x, 1.0)
    assert srv.metrics.gauge("queue.backlog", default=-1.0) == -1.0
    assert exported() == sum(srv.queue.backlog()) == 6
    srv.submit(2, x, 1.0)  # tenant 2 reaches the size watermark: flush
    assert srv.queue.flushes == 1
    assert exported() == sum(srv.queue.backlog()) == 0
    for t in (0, 0, 1):
        srv.submit(t, x, 1.0)
    assert exported() == sum(srv.queue.backlog()) == 3
    srv.evict(0)
    assert exported() == sum(srv.queue.backlog()) == 1


def test_bf16_read_error_probe_is_small_on_trained_state():
    srv = api.make_server("krls", feature_map=RFF, bank=2, chunk=4,
                          beta=0.999, lam=0.1)
    _drive(srv, ragged_traffic(tenants=2, n=16, seed=9))
    err = obs_probes.bf16_read_error(
        srv.queue.state, RFF, np.ones((2, 4, D_IN), np.float32)
    )
    # bf16 mantissa floor on a tiny trained state (the serving-shape
    # contract at the default 2e-2 threshold is pinned by the Zipf bench
    # probes; here we only require the probe itself to be sane).
    assert 0.0 <= err < 0.05
