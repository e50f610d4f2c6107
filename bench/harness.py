"""Drive one cell: set up, warm up, measure a window, check the answers.

The program is driven only through its public entry: ``repro.serve.
make_server``, then ``Server.submit`` / ``maybe_flush`` / ``predict`` /
``drain``. The client keeps its own ledger of every request: when it was
due, when the call went out, when a write was published (the return of the
call whose flush published it) and what each read returned. A flush's
prior predictions are taken where the queue produces them (its ``flush``
return value, which ``submit`` does not pass on).

A server with a slot policy (``make_server(policy=...)``) runs more tenants
than slots; the path is chosen once per run. Its flushes are keyed by slot,
mapped to tenants through the slot's occupant, which the client learns at
each admission (``policy.lookup`` before and after a write). An admission
rebuilds the tenant from its log and publishes, so the client's pending
writes of that tenant are then published, with no prior. A read of a tenant
that is not resident is served cold (from a fresh state).
"""
from __future__ import annotations

import gc
import glob
import json
import math
import os
import shutil
import tempfile
import time
from collections import defaultdict, deque
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from bench import check, readers, trace_reduce, traffic, work
from bench.cells import Cell

clock = time.perf_counter
PROFILE_SHARE = 0.25  # the traced stretch: the window's last quarter
TRACE_SPANS = 1 << 20  # spans the tracer's ring keeps
GRACE_S = 30.0  # an open loop stops issuing this long after the window
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileInWindow(RuntimeError):
    """A program was traced or compiled inside the measured window. The
    run's ``result`` and ``details`` ride along; the run is not valid."""

    def __init__(self, counts: dict, result: dict, details: dict):
        super().__init__(json.dumps(counts))
        self.result, self.details = result, details


class CompileCounter:
    """Counts JAX lowerings and backend compiles, process-wide."""

    def __init__(self):
        import jax

        self.lowerings = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == LOWERING:
            self.lowerings += 1
        elif name == BACKEND_COMPILE:
            self.compiles += 1

    def mark(self) -> tuple[int, int]:
        return self.lowerings, self.compiles

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class GcWatch:
    """Times the interpreter's garbage collections while ``active``."""

    def __init__(self):
        self.active = False
        self.pauses: list = []  # (generation, seconds)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.active:
            return
        if phase == "start":
            self._t0 = clock()
        elif self._t0 is not None:
            self.pauses.append((info["generation"], clock() - self._t0))
            self._t0 = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on)
        full = [s for g, s in self.pauses if g == 2]
        return {"collections": len(self.pauses), "full": len(full),
                "full_max_ms": 1e3 * max(full, default=0.0),
                "total_ms": 1e3 * sum(s for _, s in self.pauses)}


class Ledger:
    """The client's record of every request of one run.

    ``owner`` (slot -> tenant) is None where a tenant is its slot; with a
    slot policy it is kept per admission (:meth:`install`)."""

    def __init__(self, sched: traffic.Schedule, tenants: int,
                 owner: Optional[dict] = None):
        self.issued: list = []
        self.done: list = []  # read: value on the host; write: published
        self.value: list = []  # read: its value; write: the prior
        self.pub: list = []  # read: its tenant's published writes when served
        self.grow(len(sched))
        self.owner = owner
        # With a policy most tenants may never write: their queues are made
        # on first use.
        self.pending = ([deque() for _ in range(tenants)] if owner is None
                        else defaultdict(deque))
        self.published = [0] * tenants
        self.flushed: list = []  # queue.flush results not yet settled
        self.flushes: list = []  # (t0, t1, active slots, ticks, published at)
        self.rebuilds: list = []  # (published at, writes) of admissions
        self.rebuilt: list = []  # writes an admission's rebuild published
        self.raised = 0
        self.errors: list = []

    def grow(self, n: int) -> None:
        """Make room for requests up to ``n`` (lists grow in place)."""
        more = n - len(self.issued)
        self.issued.extend([math.nan] * more)
        self.done.extend([math.nan] * more)
        self.value.extend([math.nan] * more)
        self.pub.extend([0] * more)

    def settle(self, now: float) -> None:
        """Mark the writes of the flushes since the last call published."""
        owner = self.owner
        for t0, t1, res in self.flushed:
            ticks = 0
            for slot, outs in res.items():
                tenant = slot if owner is None else owner[slot]
                pend = self.pending[tenant]
                for pred, _err in outs:
                    i = pend.popleft()
                    self.value[i] = pred
                    self.done[i] = now
                self.published[tenant] += len(outs)
                ticks += len(outs)
            self.flushes.append((t0, t1, len(res), ticks, now))
        self.flushed.clear()

    def install(self, tenant: int, slot: int, now: float, keep: int = 0) -> None:
        """``tenant`` now occupies ``slot``: an admission rebuilt it from its
        log and published, which trains every pending write of it but the
        last ``keep`` (the write being submitted, queued after the rebuild).
        Such a write gets no prior."""
        self.owner[slot] = tenant
        pend = self.pending[tenant]
        n = len(pend) - keep
        for _ in range(n):
            i = pend.popleft()
            self.done[i] = now
            self.rebuilt.append(i)
        if n > 0:
            self.published[tenant] += n
            self.rebuilds.append((now, n))

    def fail(self, i: int, exc: BaseException) -> None:
        self.raised += 1
        if len(self.errors) < 5:
            self.errors.append(f"request {i}: {exc!r}")


def capture_flushes(server, led: Ledger) -> None:
    """Record each queue flush's result (per-tenant prior predictions)."""
    queue = server.queue
    flush = queue.flush

    def recorded_flush():
        t0 = clock()
        res = flush()
        if res:
            led.flushed.append((t0, clock(), res))
        return res

    queue.flush = recorded_flush


def lifecycle(server) -> dict:
    """The slot policy's counts of evictions and of readmissions (rebuilds
    from the log); empty without a policy."""
    if server.policy is None:
        return {}
    return {k: server.metrics.count(k) for k in ("evictions", "readmissions")}


def wait_until(t: float) -> None:
    gap = t - clock()
    if gap > 2e-3:
        time.sleep(gap - 1e-3)
    while clock() < t:
        pass


def drive(server, led: Ledger, sched: traffic.Schedule, lo: int,
          hi: Optional[int], deadline: float, t_open: Optional[float]) -> int:
    """Issue requests ``lo..hi-1`` (``hi`` None: as many as the schedule
    makes) until ``deadline``: back to back (closed loop) when ``t_open`` is
    None, else each at ``t_open + due``, calling ``maybe_flush`` once in
    each wait. Returns the next request's index."""
    submit, predict, maybe_flush = server.submit, server.predict, server.maybe_flush
    key, y, x, is_read = sched.key_l, sched.y_l, sched.x, sched.read_l
    due = sched.due_l if t_open is not None else None
    issued, done, value, pub = led.issued, led.done, led.value, led.pub
    pending, published, flushed = led.pending, led.published, led.flushed
    i = lo
    while hi is None or i < hi:
        now = clock()
        if now >= deadline:
            break
        if i == len(key):
            if not sched.extend():
                break
            led.grow(len(key))
        if due is not None:
            t_due = t_open + due[i]
            if now < t_due:
                maybe_flush()
                if flushed:
                    led.settle(clock())
                wait_until(t_due)
                now = clock()
        k = key[i]
        issued[i] = now
        try:
            if is_read[i]:
                pub[i] = published[k]
                value[i] = float(predict(k, x[i]))
                done[i] = clock()
            else:
                pending[k].append(i)
                submit(k, x[i], y[i])
        except Exception as e:  # counted as failed; the run goes on
            if not is_read[i] and pending[k] and pending[k][-1] == i:
                pending[k].pop()
            led.fail(i, e)
        if flushed:
            led.settle(clock())
        i += 1
    return i


def drive_policy(server, led: Ledger, sched: traffic.Schedule, lo: int,
                 hi: Optional[int], deadline: float,
                 t_open: Optional[float]) -> int:
    """:func:`drive` for a server with a slot policy. A write whose tenant
    goes from not resident to resident settles what the admission's rebuild
    published; a read of a tenant that is not resident has seen no write."""
    submit, predict, maybe_flush = server.submit, server.predict, server.maybe_flush
    lookup = server.policy.lookup
    key, y, x, is_read = sched.key_l, sched.y_l, sched.x, sched.read_l
    due = sched.due_l if t_open is not None else None
    issued, done, value, pub = led.issued, led.done, led.value, led.pub
    pending, published, flushed = led.pending, led.published, led.flushed
    i = lo
    while hi is None or i < hi:
        now = clock()
        if now >= deadline:
            break
        if i == len(key):
            if not sched.extend():
                break
            led.grow(len(key))
        if due is not None:
            t_due = t_open + due[i]
            if now < t_due:
                maybe_flush()
                if flushed:
                    led.settle(clock())
                wait_until(t_due)
                now = clock()
        k = key[i]
        issued[i] = now
        try:
            if is_read[i]:
                pub[i] = published[k] if lookup(k) is not None else 0
                value[i] = float(predict(k, x[i]))
                done[i] = clock()
            else:
                was = lookup(k)
                pending[k].append(i)
                submit(k, x[i], y[i])
                if was is None:
                    slot = lookup(k)
                    if slot is not None:
                        led.install(k, slot, clock(), keep=1)
        except Exception as e:  # counted as failed; the run goes on
            if not is_read[i] and pending[k] and pending[k][-1] == i:
                pending[k].pop()
            led.fail(i, e)
        if flushed:
            led.settle(clock())
        i += 1
    return i


def checked_rows(server, led: Ledger, ids: np.ndarray) -> dict:
    """With a slot policy: each checked tenant's row, pulled from its slot
    one tenant at a time. A tenant that is not resident is first readmitted,
    which rebuilds it from its log and so publishes its pending writes."""
    lookup = server.policy.lookup
    rows = []
    for t in ids.tolist():
        if lookup(t) is None:
            server.readmit(t)
            led.install(t, lookup(t), clock())
        slot = lookup(t)
        rows.append({f: np.asarray(a[slot])
                     for f, a in server.queue.state._asdict().items()})
    return {f: np.stack([r[f] for r in rows]) for f in rows[0]}


def untrained(got: dict, ref: dict, published: list) -> int:
    """Acknowledged writes the step counters disagree with. Each counter
    (of the tenants ``got["stepped"]``) is held against the writes the
    configuration trains: the reference's ``step`` of the checked tenants
    where it gives one, else the client's count of published writes."""
    want = np.array(published)
    if "step" in ref:
        want[got["ids"]] = ref["step"]
    return int(np.abs(got["step"] - want[got["stepped"]]).sum())


@dataclass
class RunView:
    """What the reference sees of a run: the bench-made feature map and the
    requests exactly as submitted, nothing the program made."""

    cfg: dict
    seed: int
    w: np.ndarray
    b: np.ndarray
    write_key: np.ndarray
    write_x: np.ndarray
    write_y: np.ndarray
    read_key: np.ndarray
    read_x: np.ndarray
    read_pub: np.ndarray


def device_seed(seed: int) -> int:
    """A 32-bit key for JAX from any whole-number seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def make_feature_map(cfg: dict, seed: int):
    """The shared RFF map, drawn on the device in one jitted call:
    ``W ~ N(0, I/sigma^2)`` (d, D), ``b ~ U[0, 2 pi)`` (D,)."""
    import jax
    import jax.numpy as jnp

    d, dfeat, sigma = cfg["input_dim"], cfg["num_features"], cfg["sigma"]

    @jax.jit
    def draw(key):
        k_w, k_b = jax.random.split(key)
        w = jax.random.normal(k_w, (d, dfeat), jnp.float32) / sigma
        b = jax.random.uniform(k_b, (dfeat,), jnp.float32, 0.0, 2.0 * jnp.pi)
        return w, b

    return draw(jax.random.PRNGKey(device_seed(seed)))


def build_server(cfg: dict, mix: dict, w, b, tracer=None):
    """The system under test: ``make_server`` as the configuration and the
    mix state it (``hp`` may name a slot policy and its log)."""
    from repro.core.rff import RFF
    from repro.serve import make_server

    return make_server(
        cfg["learner"],
        feature_map=RFF(omega=w, bias=b),
        bank=cfg["slots"],
        chunk=cfg["chunk"],
        publish_every=cfg["publish_every"],
        size_watermark=mix["size_watermark"],
        age_watermark=mix["age_watermark"],
        trace=tracer,
        **cfg["hp"],
    )


def percentile(values, q: float) -> Optional[float]:
    """Percentile of latencies; a request with no answer (NaN) counts as
    later than any other."""
    v = np.nan_to_num(np.asarray(values, np.float64), nan=np.inf)
    return float(np.percentile(v, q)) if len(v) else None


def numbers(view: RunView, got: dict, ref: dict, flushed: np.ndarray) -> dict:
    """The compared numbers: per tenant, the norm-relative gap of the final
    state, of its writes' prior predictions (those a flush returned:
    ``flushed``, over the checked tenants' writes) and of its reads."""
    ids = got["ids"]
    out = {}
    for leaf in ("theta", "pmat"):
        if leaf in ref:
            out[leaf] = max(
                (check.rel(g, r) for g, r in zip(got[leaf], ref[leaf])),
                default=0.0,
            )
    wk = view.write_key[np.isin(view.write_key, ids)]
    rk = view.read_key[np.isin(view.read_key, ids)]
    out["prior"] = check.worst_group(
        got["prior"][flushed], ref["prior"][flushed], wk[flushed])
    out["read"] = check.worst_group(got["read"], ref["read"], rk)
    out["untrained"] = got["untrained"]
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False, *,
             t_start: Optional[float] = None, rate: Optional[float] = None,
             control: bool = False, require_tpu: bool = True,
             save_trace: Optional[str] = None,
             inject: Optional[Callable] = None) -> tuple[dict, dict]:
    """One run of ``cell``. Returns ``(result, details)``: the result line
    (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
    ``breakdown``, ``checks``) and the numbers behind it."""
    t_start = clock() if t_start is None else t_start
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices ({e})") from e
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)"
        )
    from repro.obs.trace import Tracer

    counter = CompileCounter()
    cfg, mix = cell.cfg, cell.mix
    parts = {"to_devices": clock() - t_start}
    t = clock()
    w, b = make_feature_map(cfg, seed)
    sched = traffic.build(mix, cfg, seed, seconds, rate)
    parts["streams"] = clock() - t
    t = clock()
    tracer = Tracer(capacity=TRACE_SPANS, jax_annotations=True) if trace else None
    server = build_server(cfg, mix, w, b, tracer)
    jax.block_until_ready(server.queue.state)
    parts["server"] = clock() - t
    t = clock()
    pol = server.policy
    owner = None if pol is None else {s: k for k, s in pol.resident.items()}
    led = Ledger(sched, cfg["tenants"], owner)
    run = drive if pol is None else drive_policy
    capture_flushes(server, led)
    if inject is not None:
        inject(server)

    # Set-up: the mix's warm-up requests, closed loop, then a drain. They
    # compile (or load) every program the window runs, and the reference
    # replays them like any other request.
    n_warm = run(server, led, sched, 0, sched.warmup, math.inf, None)
    server.drain()
    led.settle(clock())
    jax.block_until_ready(server.queue.state)
    # Collect set-up's garbage now, not in a full collection inside the
    # window (one such pause stalled the client for 1-2 s in some runs).
    gc.collect()
    parts["warmup"] = clock() - t
    setup_s = clock() - t_start
    gcw = GcWatch()

    lo = sched.warmup
    hi = len(sched) if sched.open_loop else None
    prof_s = PROFILE_SHARE * seconds if trace else 0.0
    mark0 = counter.mark()
    lifecycle0 = lifecycle(server)
    gcw.active = True
    t_open = clock()
    t_close = t_open + seconds
    open_loop = sched.open_loop
    stop = t_close + GRACE_S if open_loop else t_close
    if open_loop:
        split = lo + int(np.searchsorted(sched.due[lo:], seconds - prof_s))
    else:
        split = None
    i = run(server, led, sched, lo, split, t_close - prof_s if not open_loop
            else stop, t_open if open_loop else None)
    prof = None
    if trace:
        prof = _Profile(tracer)
        i = run(server, led, sched, i, hi, stop, t_open if open_loop else None)
        prof.stop()
    t_end = clock()
    mark1 = counter.mark()
    lifecycle1 = lifecycle(server)
    counter.close()
    gc_window = gcw.close()

    # After the window: publish what is pending (an open loop waits on the
    # age watermark, as a client would), then drain. A flush publishes no
    # write of a tenant that is not resident.
    if pol is None:
        def waiting():
            return any(led.pending)
    else:
        def waiting():
            return any(p and pol.lookup(k) is not None
                       for k, p in led.pending.items())
    if open_loop:
        limit = clock() + 60.0
        while waiting() and clock() < limit:
            server.maybe_flush()
            led.settle(clock())
            time.sleep(1e-3)
    server.drain()
    led.settle(clock())

    dev = devs[0]
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs
    )
    # The checked tenants' state, pulled to the host; then the program's
    # state is let go before the reference runs.
    is_read, s_key, s_x, s_y = sched.arrays(i)
    w_idx = np.flatnonzero(~is_read)
    r_idx = np.flatnonzero(is_read)
    view = RunView(
        cfg=cfg, seed=seed, w=np.asarray(w), b=np.asarray(b),
        write_key=s_key[w_idx], write_x=s_x[w_idx],
        write_y=s_y[w_idx], read_key=s_key[r_idx],
        read_x=s_x[r_idx], read_pub=np.asarray(led.pub)[r_idx],
    )
    ids = np.asarray(cell.reference.tenants(view))
    snapshot_behind = server.snapshot.tick != server.queue.ticks_served
    policy_details = {}
    if pol is None:
        unpublished = sum(len(p) for p in led.pending)
        state = server.queue.state
        got = {"theta": np.asarray(state.theta)[ids],
               "step": np.asarray(state.step)}
        if hasattr(state, "pmat"):
            got["pmat"] = np.asarray(state.pmat[ids])
        got["stepped"] = np.arange(len(got["step"]))
        del state
    else:
        # A write still pending whose tenant is not resident was dropped at
        # its eviction or rejected: it is in the tenant's log, not lost.
        unpublished = not_resident = 0
        for k, p in led.pending.items():
            if pol.lookup(k) is None:
                not_resident += len(p)
            else:
                unpublished += len(p)
        policy_details = {
            "logged_not_resident": not_resident,
            "rebuilt_writes": len(led.rebuilt),
            "checked_not_resident": sum(pol.lookup(t) is None for t in ids.tolist()),
            **{f"{k}_in_window": lifecycle1[k] - lifecycle0[k] for k in lifecycle0},
        }
        got = {**checked_rows(server, led, ids), "stepped": ids}
    value = np.asarray(led.value)
    checked_w = np.isin(view.write_key, ids)
    got.update(ids=ids, prior=value[w_idx][checked_w],
               read=value[r_idx][np.isin(view.read_key, ids)])
    no_prior = np.zeros(len(is_read), bool)
    no_prior[led.rebuilt] = True
    flushed = ~no_prior[w_idx][checked_w]
    del server, pol  # the policy's cost function holds the server
    gc.collect()  # the bank (GBs of P for KRLS) goes before a next seed's
    t_ref = clock()
    ref = cell.reference.replay(view, ids, "f64")
    got["untrained"] = untrained(got, ref, led.published)
    nums = numbers(view, got, ref, flushed)
    ok, checks = check.judge(nums, cfg["limits"])
    ref_s = clock() - t_ref
    details = {
        "cell": cell.name, "seed": seed, "seconds": seconds, "trace": trace,
        "setup_s": setup_s, "setup_parts": parts, "window_s": t_end - t_open,
        "reference_s": ref_s,
        "warmup_requests": n_warm, "window_requests": i - lo,
        "unissued": hi - i if open_loop else 0,
        "compiles_in_window": {"lowerings": mark1[0] - mark0[0],
                               "backend": mark1[1] - mark0[1]},
        "flushes": len(led.flushes), "raised": led.raised,
        "errors": led.errors, "unpublished": unpublished,
        "snapshot_behind": snapshot_behind, "memory_peak_bytes": peak,
        "checked_tenants": len(ids), "gc_in_window": gc_window,
        **policy_details,
    }
    if control:
        ctl = cell.reference.replay(view, ids, "bf16")
        ctl_nums = numbers(view, {**ctl, "ids": ids, "untrained": 0}, ref,
                           flushed)
        details["control"] = check.judge(ctl_nums, cfg["limits"])[1]

    # End-to-end metrics, from the client's ledger.
    issued = np.asarray(led.issued)
    done = np.asarray(led.done)
    if open_loop:
        is_read = sched.arrays()[0]
        wins = np.arange(lo, hi)
        w_win = wins[~is_read[lo:hi]]
        r_win = wins[is_read[lo:hi]]
        due = t_open + sched.due
        lat_w = (done[w_win] - due[w_win]) * 1e3
        lat_r = (done[r_win] - due[r_win]) * 1e3
        late = (issued[lo:i] - due[lo:i]) * 1e3
        details.update(
            write_ms={"n": len(lat_w), "p50": percentile(lat_w, 50),
                      "p95": percentile(lat_w, 95), "p99": percentile(lat_w, 99)},
            read_ms={"n": len(lat_r), "p50": percentile(lat_r, 50),
                     "p95": percentile(lat_r, 95), "p99": percentile(lat_r, 99)},
            late_ms={"p50": percentile(late, 50), "p99": percentile(late, 99),
                     "max": float(np.max(late)) if len(late) else None,
                     # A generator that falls behind lags more and more:
                     "p50_first_quarter": percentile(late[: len(late) // 4], 50),
                     "p50_last_quarter": percentile(late[-(len(late) // 4):], 50),
                     "max_at_s": float(sched.due[lo + int(np.argmax(late))])
                     if len(late) else None},
        )
    # Arrivals published per second over the whole publish cycles inside
    # the window (first to last publish in it): a publish lands a whole
    # flush at once, so counting up to the window's edge would step the
    # rate by a flush's worth of arrivals.
    pubs = sorted(
        [(t, n) for _, _, _, n, t in led.flushes if t_open < t <= t_close]
        + [(t, n) for t, n in led.rebuilds if t_open < t <= t_close],
        key=lambda p: p[0])
    if len(pubs) >= 2 and pubs[-1][0] > pubs[0][0]:
        details["ingest_rate"] = (
            sum(n for _, n in pubs[1:]) / (pubs[-1][0] - pubs[0][0]))
    details["publishes_in_window"] = len(pubs)
    failed = led.raised + unpublished + details["unissued"]
    values = {
        "setup_s": setup_s,
        "ingest_rate": details.get("ingest_rate"),
        "write_p95_ms": details.get("write_ms", {}).get("p95"),
        "read_p95_ms": details.get("read_ms", {}).get("p95"),
    }
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs), "memory_peak_bytes": int(peak),
    }
    result = {"correct": ok and not snapshot_behind,
              "attempted": i - lo + details["unissued"],
              "failed": failed, "metrics": {}, "device": device}
    if not trace:
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None and math.isfinite(values[m["name"]]):
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    else:
        obs, dtrace = prof.observation(cell, dev, led)
        device.update(busy_s=dtrace.busy_s, window_s=dtrace.window_s)
        for m, reader in cell.per_layer:
            v = reader.read(obs)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": trace_reduce.top(dtrace.ops),
            "idle_gaps": trace_reduce.top(dtrace.idle),
        }
        details["trace"] = {"gaps": dtrace.gaps, "spans": len(obs.spans),
                            "flushes": len(obs.flushes),
                            "kernel_s": dtrace.ops.get(readers.KRLS_CHUNK_KERNEL)}
        if save_trace:
            shutil.copy(prof.xplane, save_trace)
        prof.cleanup()
    result["checks"] = checks
    if any(x for x in details["compiles_in_window"].values()):
        raise CompileInWindow(details["compiles_in_window"], result, details)
    return result, details


class _Profile:
    """A ``jax.profiler`` trace of the rest of the window, with the
    ``bench.window`` annotation and tracer marks at both ends."""

    def __init__(self, tracer):
        import jax

        self.jax, self.tracer = jax, tracer
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # annotations, not the runtime's own events
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.window.__enter__()
        self.t0 = clock()
        self.m0 = tracer.instant("bench.window_open")

    def stop(self) -> None:
        self.m1 = self.tracer.instant("bench.window_close")
        self.t1 = clock()
        self.window.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        self.xplane = found[0]

    def observation(self, cell: Cell, dev, led: Ledger):
        chip = work.peak(dev.device_kind)
        dtrace = trace_reduce.reduce(trace_reduce.load(self.xplane))
        spans = self.tracer.spans()
        lo, hi = self.m0.t0, self.m1.t0
        if self.tracer.dropped and (not spans or spans[0].t0 > lo):
            raise RuntimeError("the tracer's ring dropped spans of the window")
        obs = readers.Observation(
            cfg=cell.cfg,
            peak=chip,
            spans=[s for s in spans if s.kind == "span" and s.t0 >= lo and s.t1 <= hi],
            flushes=[(a, t) for t0, t1, a, t, _ in led.flushes
                     if t0 >= self.t0 and t1 <= self.t1],
            device=dtrace,
        )
        return obs, dtrace

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
