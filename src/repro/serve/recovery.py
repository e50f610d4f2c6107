"""Self-healing tier: probe-triggered repair and durable checkpoint/restore.

PR 9's :class:`~repro.obs.probes.ProbeMonitor` *detects* degraded state
(non-finite leaves, theta blow-up, KRLS P asymmetry / conditioning drift)
but nothing in the stack acts on an event, and every byte of state is
process memory — one crash loses every tenant. This module closes the
loop; obs/faults.py manufactures the failures that drive it in tests:

* :class:`RecoveryPolicy` — subscribes to the monitor, localizes each
  degradation to a bank slot (per-slot :func:`~repro.obs.probes.slot_stats`
  on the rare event path; the hot path keeps the one fused bank-global
  tap), **quarantines** the offending tenant (reads served from its last
  healthy snapshot row, arrivals logged-not-trained — the cold-tenant
  path reused), then repairs by escalation::

      re-symmetrize P  ->  scan-rebuild from ReplayLog  ->  O(1) reset

  with bounded retries, per-tenant exponential backoff, and every action
  traced/counted through ``obs``. The paper's fixed-size state is what
  makes the ladder cheap: a tenant is O(D) to snapshot, O(log T) to
  rebuild (PR 6 scan replay), O(1) to reset. A rebuild is attempted only
  when the replay log is complete *and* finite — an overflowed ring
  (windowed history) or a corrupted entry falls straight through to
  reset rather than silently installing partial state as full history.
* :class:`DurableLog` — a JSONL write-ahead log of raw arrivals.
  Observations round-trip bitwise (f32 -> double -> shortest-repr JSON
  -> f32); a torn final line (crash mid-append) is tolerated and
  ignored on read.
* :func:`save_checkpoint` / :func:`restore_checkpoint` — crash-consistent
  serialization of a full ``serve.api.Server`` (bank state, queue
  counters and pending buffers, replica version, router state, replay
  log, feature-map params) as atomically-renamed ``gen_N.ckpt`` files
  with generation numbers. Restore validates the config and the feature
  map bitwise, installs every leaf, and replays the WAL suffix recorded
  after the checkpoint through the ordinary submit path — so
  kill-at-arbitrary-flush -> restore matches the never-killed control
  bitwise on all state leaves (chaos-tested).

Quarantine and in-flight recovery episodes are deliberately NOT
checkpointed: a restore re-detects any surviving degradation from the
probes on the next flush, which is simpler and strictly safer than
trusting persisted judgments about state that the crash may have changed.
"""
from __future__ import annotations

import json
import os
import pickle
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core.bank import resymmetrize_tenant, tenant_row
from repro.obs import telemetry as _telemetry
from repro.obs import trace as _trace
from repro.obs.probes import slot_stats

__all__ = [
    "CKPT_FORMAT",
    "DurableLog",
    "RecoveryPolicy",
    "save_checkpoint",
    "restore_checkpoint",
]

CKPT_FORMAT = "repro.server.ckpt/v2"

# The escalation ladder, cheapest repair first. ``resymmetrize`` is only
# offered to true RLS banks (a (B, D, D) P next to a theta row); every
# other reason starts at ``rebuild``.
LADDER = ("resymmetrize", "rebuild", "reset")

# Probes that are global to the server rather than attributable to one
# bank slot. ``clock_skew`` has a dedicated repair; the rest are operator
# signals, recorded but not acted on.
_GLOBAL_PROBES = ("clock_skew", "staleness_ticks", "bf16_read_error")


def _is_rls_bank(state) -> bool:
    return hasattr(state, "pmat") and not hasattr(state, "centers")


# ---------------------------------------------------------------------------
# Write-ahead log
# ---------------------------------------------------------------------------


class DurableLog:
    """Append-only JSONL write-ahead log of raw ``(tenant, x, y)`` arrivals.

    One line per arrival: ``{"s": seq, "t": tenant, "x": [...], "y": y}``.
    Floats are written as Python doubles — an f32 observation widens
    exactly and JSON's shortest-round-trip repr preserves the double, so
    the f32 read back after restore is bitwise the one submitted (NaN/Inf
    use the JSON-extension literals Python emits and accepts). Sequence
    numbers are contiguous from 0 and resume past the highest complete
    line of an existing file; a torn final line (crash mid-append) is
    detected by its parse failure and ignored.

    ``fsync=True`` makes every append durable against power loss at the
    cost of one fsync per arrival; the default flushes to the OS only
    (durable against process crash, the failure mode the chaos tests
    exercise).
    """

    def __init__(self, path, *, fsync: bool = False):
        self.path = str(path)
        self.fsync = fsync
        self.seq = -1
        if os.path.exists(self.path):
            # Scan for the resume seq and truncate a torn tail — appending
            # after an unterminated fragment would weld the next record
            # onto it and corrupt that one too.
            good_end = 0
            with open(self.path, "rb") as fh:
                for line in fh:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        break
                    self.seq = rec["s"]
                    good_end += len(line)
            if good_end < os.path.getsize(self.path):
                with open(self.path, "ab") as fh:
                    fh.truncate(good_end)
        self._fh = open(self.path, "a", encoding="utf-8")

    def _scan(self):
        with open(self.path, "r", encoding="utf-8") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    break  # torn tail: everything after is garbage
                yield rec

    def append(self, tenant: int, x, y) -> int:
        """Durably record one arrival; returns its sequence number."""
        self.seq += 1
        rec = {
            "s": self.seq,
            "t": int(tenant),
            "x": [float(v) for v in np.asarray(x).ravel()],
            "y": float(y),
        }
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()
        if self.fsync:
            os.fsync(self._fh.fileno())
        _telemetry.record_wal_append()
        return self.seq

    def entries(self, after: int = -1) -> list[dict]:
        """All complete records with ``seq > after``, in order."""
        return [rec for rec in self._scan() if rec["s"] > after]

    def close(self) -> None:
        self._fh.close()


# ---------------------------------------------------------------------------
# Probe-triggered recovery
# ---------------------------------------------------------------------------


@dataclass
class _Episode:
    """One tenant's open quarantine: where it is on the ladder and what
    to serve its reads from while it heals."""

    tenant: int
    slot: int
    reason: str
    rung: int
    attempts: int = 0
    backoff_until: float = 0.0
    gave_up: bool = False
    healthy_row: Any = None
    actions: list = field(default_factory=list)


class RecoveryPolicy:
    """Quarantine-and-repair controller bound to one ``serve.api.Server``.

    The server's probe monitor pushes degradation events into this policy
    (``ProbeMonitor.subscribe``); the subscriber only *records* them, and
    the server calls :meth:`process` right after each probe fold — so all
    state mutation happens at a well-defined point outside the monitor
    update, never mid-probe.

    ``process`` localizes each event to a slot via the per-slot
    diagnostics, maps the slot to its tenant, captures the tenant's last
    healthy replica row, and quarantines it: the server serves the
    tenant's reads from the captured row and appends (but never trains)
    its arrivals until the episode closes. Repair walks :data:`LADDER`
    from a reason-dependent starting rung; each attempt is verified
    against the monitor's own thresholds on the repaired slot, a failed
    attempt escalates one rung and backs off exponentially
    (``backoff_base * backoff_factor ** attempts``), and after
    ``max_retries`` failed attempts the policy gives up — the slot is
    parked on a fresh row so the bank-global probes stop firing, and the
    tenant stays quarantined for the operator (healthy reads still
    served).

    ``reference_clock`` (optional) arms the clock-skew probe: the policy
    captures the offset between the snapshot tier's clock and the
    reference at bind time, the server reports ``|drift|`` from that
    baseline as the ``clock_skew`` stat, and the ``reclock`` repair
    re-bases the snapshot clock on the reference and re-stamps pending
    arrival times. Metrics: ``recovery.quarantines`` / ``recovery.repairs
    {action=...}`` / ``recovery.releases`` / ``recovery.gave_up``.
    """

    def __init__(
        self,
        *,
        max_retries: int = 3,
        backoff_base: float = 0.0,
        backoff_factor: float = 2.0,
        clock: Callable[[], float] = time.monotonic,
        reference_clock: Optional[Callable[[], float]] = None,
    ):
        self.max_retries = max_retries
        self.backoff_base = backoff_base
        self.backoff_factor = backoff_factor
        self.clock = clock
        self.reference_clock = reference_clock
        self._server = None
        self._pending_events: list = []
        self._episodes: dict[int, _Episode] = {}
        self.history: list[dict] = []
        self._last_healthy = None  # (replica state, resident map)
        self._clock_baseline = 0.0

    # -- wiring --------------------------------------------------------------

    def bind(self, server) -> "RecoveryPolicy":
        """Attach to a server (subscribes to its probe monitor)."""
        if server.probe is None:
            raise ValueError("recovery needs the server's probe monitor")
        if self._server is not None:
            raise RuntimeError("recovery policy already bound")
        self._server = server
        server.probe.subscribe(self._pending_events.append)
        if self.reference_clock is not None:
            self._clock_baseline = (
                server.snapshot_server._clock() - self.reference_clock()
            )
        return self

    @property
    def quarantined(self) -> frozenset[int]:
        """Tenants currently quarantined (reads from healthy snapshot)."""
        return frozenset(self._episodes)

    def healthy_row(self, tenant: int):
        """The quarantined tenant's captured healthy state row (or None —
        the tenant was never seen healthy; reads then serve cold)."""
        ep = self._episodes.get(tenant)
        return ep.healthy_row if ep is not None else None

    def measure_skew(self) -> float:
        """|drift| of the snapshot clock from the reference baseline."""
        inner = self._server.snapshot_server
        return abs(
            (inner._clock() - self.reference_clock()) - self._clock_baseline
        )

    # -- the control loop ----------------------------------------------------

    def process(self) -> None:
        """Act on events recorded since the last call (the server invokes
        this right after every probe fold)."""
        if self._server is None:
            return
        # Drain in place: the monitor's subscriber is this exact list's
        # bound ``append`` — rebinding would orphan it.
        events = list(self._pending_events)
        self._pending_events.clear()
        if not events:
            if not self._episodes:
                # Event-free fold: remember this replica as last-healthy.
                # A poisoned flush can never land here — publish precedes
                # the probe fold, so its events arrive in the same call.
                self._last_healthy = (
                    self._server.snapshot.state,
                    dict(self._server.resident),
                )
            self._repair_due()
            return
        for ev in events:
            self._ingest(ev)
        self._repair_due()

    def _ingest(self, ev) -> None:
        if ev.probe == "clock_skew":
            self._repair_clock(ev)
            return
        if ev.probe in _GLOBAL_PROBES:
            self.history.append(
                {"event": ev.probe, "action": "ignored", "tick": ev.tick}
            )
            return
        slots = self._diagnose(ev.probe, ev.threshold)
        by_slot = {s: t for t, s in self._server.resident.items()}
        for slot in slots:
            tenant = by_slot.get(slot)
            if tenant is None:
                continue  # unowned slot: nothing to quarantine
            ep = self._episodes.get(tenant)
            if ep is not None:
                # Re-degrade inside an open episode: the failed attempt
                # already escalated the rung; just note the recurrence.
                ep.actions.append({"event": ev.probe, "redegrade": True})
                continue
            self._quarantine(tenant, slot, ev.probe)

    def _diagnose(self, probe: str, threshold: float) -> list[int]:
        """Slots breaching ``probe``'s threshold, per-slot."""
        server = self._server
        if probe == "ticks_lag":
            lags = server._slot_lags()
            return [s for s, lag in enumerate(lags) if lag > threshold]
        stats = {
            k: np.asarray(v)
            for k, v in slot_stats(server.queue.state).items()
        }
        if probe == "finite":
            mask = stats["finite"] < 1.0
        elif probe == "theta.norm_max":
            if "theta.norm" not in stats:
                return []
            mask = stats["theta.norm"] > threshold
        elif probe in ("pmat.asym_rel", "pmat.cond_proxy"):
            if probe not in stats:
                return []
            mask = stats[probe] > threshold
        else:
            return []
        return [int(s) for s in np.nonzero(mask)[0]]

    def _quarantine(self, tenant: int, slot: int, reason: str) -> None:
        server = self._server
        healthy_row = None
        if self._last_healthy is not None:
            hstate, hres = self._last_healthy
            hslot = hres.get(tenant)
            if hslot is not None:
                healthy_row = tenant_row(hstate, hslot)
        start = (
            0
            if reason.startswith("pmat.")
            and _is_rls_bank(server.queue.state)
            else 1
        )
        ep = _Episode(
            tenant=tenant,
            slot=slot,
            reason=reason,
            rung=start,
            healthy_row=healthy_row,
        )
        self._episodes[tenant] = ep
        server.metrics.counter("recovery.quarantines").inc()
        _trace.instant(
            "recovery.quarantine", tenant=tenant, slot=slot, reason=reason,
            start_action=LADDER[start],
        )

    def _repair_due(self) -> None:
        now = self.clock()
        for tenant in list(self._episodes):
            ep = self._episodes.get(tenant)
            if ep is None or ep.gave_up or ep.backoff_until > now:
                continue
            self._attempt(ep)

    # -- repairs -------------------------------------------------------------

    def _attempt(self, ep: _Episode) -> None:
        server = self._server
        action = LADDER[ep.rung]
        if action == "rebuild":
            ok, why = self._check_log(ep)
            if not ok:
                # Pre-check failure is not a repair attempt: fall straight
                # through to reset, no retry budget spent, no backoff.
                ep.actions.append(
                    {"action": "rebuild", "outcome": "fallthrough",
                     "reason": why}
                )
                self.history.append(
                    {"tenant": ep.tenant, "action": "rebuild",
                     "outcome": "fallthrough", "reason": why}
                )
                ep.rung = len(LADDER) - 1
                action = LADDER[ep.rung]
        with _trace.span(
            "recovery.repair", tenant=ep.tenant, slot=ep.slot, action=action,
            attempt=ep.attempts,
        ):
            if action == "resymmetrize":
                inner = server.snapshot_server
                inner.queue.state = resymmetrize_tenant(
                    inner.queue.state, ep.slot
                )
                inner.publish()
            elif action == "rebuild":
                self._rebuild(ep)
            else:
                server.reset_tenant(ep.tenant)
        server.metrics.counter("recovery.repairs", action=action).inc()
        verified = self._verify(ep)
        ep.actions.append({"action": action, "verified": verified})
        self.history.append(
            {"tenant": ep.tenant, "action": action, "verified": verified}
        )
        if verified:
            del self._episodes[ep.tenant]
            server.metrics.counter("recovery.releases").inc()
            _trace.instant(
                "recovery.release", tenant=ep.tenant, action=action,
                attempts=ep.attempts,
            )
            return
        ep.attempts += 1
        if ep.attempts > self.max_retries:
            # Park a fresh row so the bank-global probes stop firing, but
            # keep the tenant quarantined: healthy reads keep flowing and
            # the operator decides what happens next.
            server.reset_tenant(ep.tenant)
            ep.gave_up = True
            ep.backoff_until = float("inf")
            server.metrics.counter("recovery.gave_up").inc()
            _trace.instant(
                "recovery.gave_up", tenant=ep.tenant, attempts=ep.attempts
            )
            return
        ep.rung = min(ep.rung + 1, len(LADDER) - 1)
        ep.backoff_until = self.clock() + self.backoff_base * (
            self.backoff_factor ** ep.attempts
        )

    def _check_log(self, ep: _Episode) -> tuple[bool, str]:
        """A rebuild may only install state that is the tenant's *full*,
        *finite* history — anything else resets instead."""
        log = self._server.log
        if log is None or log.size(ep.tenant) == 0:
            return False, "no_log"
        if not log.complete(ep.tenant):
            return False, "incomplete_log"
        xs, ys = log.arrays(ep.tenant)
        if not (np.isfinite(xs).all() and np.isfinite(ys).all()):
            return False, "corrupt_log"
        return True, ""

    def _rebuild(self, ep: _Episode) -> None:
        # Pending arrivals are already in the tenant's log; drop the slot's
        # backlog and replay the whole history into the slot — the row an
        # operator's evict + readmit would write.
        server = self._server
        inner = server.snapshot_server
        inner.drop_backlog(ep.slot)
        inner.write_slot(ep.slot, server.log.arrays(ep.tenant))
        server._expected[ep.slot] = server.log.size(ep.tenant)

    def _verify(self, ep: _Episode) -> bool:
        """Check the repaired slot against the monitor's own thresholds."""
        server = self._server
        thr = server.probe.thresholds
        stats = {
            k: np.asarray(v)
            for k, v in slot_stats(server.queue.state).items()
        }
        s = ep.slot
        if float(stats["finite"][s]) < 1.0:
            return False
        for skey, tkey in (
            ("theta.norm", "theta.norm_max"),
            ("pmat.asym_rel", "pmat.asym_rel"),
            ("pmat.cond_proxy", "pmat.cond_proxy"),
        ):
            if skey in stats and tkey in thr:
                direction, bound = thr[tkey]
                value = float(stats[skey][s])
                if direction == "max" and value > bound:
                    return False
        if "ticks_lag" in thr:
            _, bound = thr["ticks_lag"]
            if server._slot_lags()[s] > bound:
                return False
        return True

    def _repair_clock(self, ev) -> None:
        server = self._server
        inner = server.snapshot_server
        if self.reference_clock is None:  # pragma: no cover - stat is only
            return  # reported when a reference exists
        with _trace.span("recovery.repair", action="reclock"):
            ref, base = self.reference_clock, self._clock_baseline
            inner._clock = lambda: ref() + base
            now = inner._clock()
            # The skewed clock stamped bogus arrival ages; re-stamp the
            # surviving positions in the trusted domain.
            inner._arrival_times = [
                deque((pos, now) for pos, _ in times)
                for times in inner._arrival_times
            ]
        server.metrics.counter("recovery.repairs", action="reclock").inc()
        self.history.append(
            {"event": "clock_skew", "action": "reclock", "skew": ev.value}
        )


# ---------------------------------------------------------------------------
# Durable checkpoint / restore
# ---------------------------------------------------------------------------


def _ckpt_name(gen: int) -> str:
    return f"gen_{gen:08d}.ckpt"


def _list_generations(directory: str) -> list[tuple[int, str]]:
    """(generation, path) pairs present in ``directory``, newest first."""
    out = []
    for name in os.listdir(directory):
        if name.startswith("gen_") and name.endswith(".ckpt"):
            try:
                gen = int(name[4:-5])
            except ValueError:
                continue
            out.append((gen, os.path.join(directory, name)))
    return sorted(out, reverse=True)


def _atomic_write(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        fh.write(data)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


def _log_payload(log) -> Optional[dict]:
    if log is None:
        return None
    return {
        "capacity": log.capacity,
        "tenants": {
            int(t): {
                "entries": [
                    (np.asarray(x), float(y)) for x, y in log._buf[t]
                ],
                "appended": log._appended.get(t, 0),
            }
            for t in log.tenants()
        },
    }


def _load_log(log, payload: Optional[dict]) -> None:
    if log is None:
        return
    log.clear()
    if payload is None:
        return
    for t, rec in payload["tenants"].items():
        t = int(t)
        for x, y in rec["entries"]:
            log.append(t, x, y)
        # Restore the overflow counter so complete() keeps telling the
        # truth about windowed history across a restore.
        log._appended[t] = int(rec["appended"])


def save_checkpoint(server, directory, *, keep: int = 3) -> str:
    """Write one crash-consistent checkpoint generation of ``server``.

    The payload covers everything a fresh identically-configured server
    needs to resume bitwise: bank-state leaves, queue counters and
    pending buffers, replica version/tick, the router's state (the slot
    policy's decisions, or the tenants put out without one), the replay
    log (with its ring-overflow counters), the facade's expected-ticks
    ledger, and the WAL high-water mark.
    The feature map's leaves ride along for bitwise validation at restore
    (the map itself is rebuilt by the caller's ``make_server``).

    Write protocol: serialize -> temp file -> fsync -> ``os.replace`` to
    ``gen_N.ckpt`` (atomic on POSIX), then update the ``LATEST`` marker
    the same way. A crash at any point leaves either the old or the new
    generation fully intact, never a torn file; generations beyond
    ``keep`` are garbage-collected oldest-first. Returns the path.
    """
    os.makedirs(directory, exist_ok=True)
    gens = _list_generations(directory)
    gen = gens[0][0] + 1 if gens else 0
    inner = server.snapshot_server
    queue = inner.queue
    with _trace.span("recovery.checkpoint", generation=gen):
        state_leaves, _ = jax.tree_util.tree_flatten(queue.state)
        fm_leaves = (
            [np.asarray(a) for a in jax.tree_util.tree_leaves(
                server.feature_map)]
            if server.feature_map is not None
            else None
        )
        payload = {
            "format": CKPT_FORMAT,
            "generation": gen,
            "config": {
                "learner": server.learner,
                "slots": server.slots,
                "chunk": queue.chunk,
                "hp": dict(server._hp),
            },
            "state": [np.asarray(a) for a in jax.device_get(state_leaves)],
            "feature_map": fm_leaves,
            "queue": {
                "ticks_served": queue.ticks_served,
                "flushes": queue.flushes,
                "arrivals": list(queue.arrivals),
                "pending": [
                    [(np.asarray(x), float(y)) for x, y in q]
                    for q in queue._pending
                ],
            },
            "snapshot": {
                "version": inner._snapshot.version,
                "tick": inner._snapshot.tick,
            },
            "router": server._router.state_dict(),
            "log": _log_payload(server.log),
            "expected": dict(server._expected),
            "wal_seq": server.wal.seq if server.wal is not None else -1,
        }
        data = pickle.dumps(payload)
        path = os.path.join(directory, _ckpt_name(gen))
        _atomic_write(path, data)
        _atomic_write(
            os.path.join(directory, "LATEST"),
            (_ckpt_name(gen) + "\n").encode(),
        )
        for old_gen, old_path in gens[max(keep - 1, 0):]:
            os.remove(old_path)
    _telemetry.record_checkpoint(bytes_written=len(data))
    return path


def _validate(payload: dict, server) -> None:
    if payload.get("format") != CKPT_FORMAT:
        raise ValueError(
            f"unrecognized checkpoint format {payload.get('format')!r}"
        )
    cfg = payload["config"]
    mine = {
        "learner": server.learner,
        "slots": server.slots,
        "chunk": server.queue.chunk,
        "hp": dict(server._hp),
    }
    for key in ("learner", "chunk", "hp"):
        if cfg[key] != mine[key]:
            raise ValueError(
                f"checkpoint config mismatch on {key!r}: "
                f"saved {cfg[key]!r} != server {mine[key]!r}"
            )
    if payload["feature_map"] is not None:
        fresh = [
            np.asarray(a)
            for a in jax.tree_util.tree_leaves(server.feature_map)
        ]
        saved = payload["feature_map"]
        if len(fresh) != len(saved) or not all(
            a.shape == b.shape and np.array_equal(a, b, equal_nan=True)
            for a, b in zip(fresh, saved)
        ):
            raise ValueError(
                "checkpoint feature map does not match the server's "
                "(same seed/family required for a bitwise restore)"
            )


def _install(payload: dict, server) -> None:
    import jax.numpy as jnp

    from repro.serve.snapshot import StateSnapshot

    inner = server.snapshot_server
    queue = inner.queue
    if server.slots != payload["config"]["slots"]:
        # Bank geometry is restored by resize, which needs a policy;
        # without one the caller must build the server at the saved size.
        server.resize(payload["config"]["slots"])
    _, treedef = jax.tree_util.tree_flatten(queue.state)
    state = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(a) for a in payload["state"]]
    )
    queue.state = state
    q = payload["queue"]
    queue.ticks_served = int(q["ticks_served"])
    queue.flushes = int(q["flushes"])
    queue.arrivals = [int(a) for a in q["arrivals"]]
    queue.restore_pending(q["pending"])
    now = inner._clock()
    inner._arrival_times = [
        deque((i, now) for i in range(len(pend)))
        for pend in q["pending"]
    ]
    inner._snapshot = StateSnapshot(
        state=state,
        version=int(payload["snapshot"]["version"]),
        tick=int(payload["snapshot"]["tick"]),
    )
    server._router.load_state(payload["router"])
    _load_log(server.log, payload["log"])
    server._expected = {
        int(k): int(v) for k, v in payload["expected"].items()
    }


def restore_checkpoint(
    server,
    directory,
    *,
    replay_wal: bool = True,
) -> dict:
    """Restore ``server`` (freshly built with the same ``make_server``
    arguments) from the newest loadable generation in ``directory``.

    Generations are tried newest-first: a torn or unpicklable file (crash
    mid-GC, disk corruption) is skipped with a trace mark and the next
    one is tried — only when *no* generation loads does restore raise.
    Config and feature map are validated before anything is mutated.

    When the server has a WAL and ``replay_wal`` is True, every WAL entry
    recorded after the checkpoint's high-water mark is re-fed through the
    ordinary ``submit`` path (appends suspended so replay is idempotent
    across repeated restores). Deterministic flush cadence then makes the
    restored server bitwise the never-killed control. Returns a summary
    dict (generation, replayed count).
    """
    gens = _list_generations(directory)
    if not gens:
        raise FileNotFoundError(f"no checkpoints in {directory!r}")
    payload = None
    errors = []
    for gen, path in gens:
        try:
            with open(path, "rb") as fh:
                candidate = pickle.load(fh)
            _validate(candidate, server)
        except (ValueError, TypeError, EOFError, pickle.UnpicklingError,
                KeyError) as exc:
            if isinstance(exc, ValueError) and "mismatch" in str(exc):
                raise  # config mismatch is a caller bug, not corruption
            errors.append((path, repr(exc)))
            _trace.instant("recovery.restore_skip", path=path, error=repr(exc))
            continue
        payload = candidate
        break
    if payload is None:
        raise ValueError(
            f"no loadable checkpoint in {directory!r}: {errors}"
        )
    with _trace.span(
        "recovery.restore", generation=payload["generation"]
    ):
        _install(payload, server)
        replayed = 0
        if replay_wal and server.wal is not None:
            suffix = server.wal.entries(after=int(payload["wal_seq"]))
            server._wal_suspended = True
            try:
                for rec in suffix:
                    server.submit(rec["t"], rec["x"], rec["y"])
                    _telemetry.record_wal_append(replayed=True)
                    replayed += 1
            finally:
                server._wal_suspended = False
    _telemetry.record_checkpoint(bytes_written=0, restore=True)
    return {
        "generation": payload["generation"],
        "replayed": replayed,
        "wal_seq": int(payload["wal_seq"]),
    }
