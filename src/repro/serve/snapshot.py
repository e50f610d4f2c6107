"""Snapshot-decoupled serving: train on the live state, read a frozen replica.

The micro-batch queue (serve/queue.py) made the *write* path cheap, but its
bank state is the only copy — a predict issued mid-flush would race the
trainer. This module splits the two: the queue keeps mutating its live
state, and a :class:`SnapshotServer` publishes an immutable read replica
every ``publish_every`` update-ticks. Reads (the fused query-block kernel,
``ops.rff_bank_predict``) only ever see a published replica, so

* **no torn reads** — a replica is one pytree reference captured at a flush
  boundary; JAX arrays are immutable and CPython reference assignment is
  atomic, so a concurrent reader sees the whole old replica or the whole
  new one, never a mix of flushes (property-tested);
* **bounded staleness** — publication happens at the first flush boundary
  where at least ``publish_every`` ticks have accumulated, so between
  flushes a reader lags the live state by fewer than ``publish_every``
  ticks (plus whatever the current flush is consuming);
* **deferred write-flush is safe** — because reads never touch the live
  state, flushes can wait for the age/size watermarks (the ROADMAP
  background-flush item) without blocking or corrupting the read path.

Everything stays host-side and synchronous like the queue itself (submit /
flush / predict compose with any outer event loop; watermarks are checked
on ``submit`` and via ``maybe_flush`` rather than from a thread).

Tenant lifecycle rides on the same machinery: when a ``log_capacity`` is
set, every arrival is also appended to a per-tenant :class:`ReplayLog`
ring buffer, so ``evict(tenant)`` can release the slot as one O(1) row
write (``core.bank.evict_tenant``) and ``readmit(tenant)`` reconstructs
the state by replaying the log through the parallel-in-time engine
(``core.bank.rebuild_tenant`` over core/scan.py) instead of keeping a cold
copy of the ``(D,)``/``(D, D)`` state around. While evicted, a tenant's
arrivals are *logged but not trained* — readmission folds them in.
"""
from __future__ import annotations

import time
from collections import deque
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bank import bank_predict_block, evict_tenant, rebuild_tenant
from repro.features.base import FeatureLike
from repro.obs import telemetry as _telemetry
from repro.obs import trace as _trace
from repro.serve.queue import MicroBatchQueue

__all__ = [
    "ReplayLog",
    "StateSnapshot",
    "SnapshotServer",
    "predict_row",
    "klms_snapshot_server",
    "krls_snapshot_server",
]


class ReplayLog:
    """Per-tenant ring buffer of raw ``(x, y)`` arrivals for slot rebuilds.

    Capacity bounds host memory: a tenant whose history outgrows the ring
    loses its oldest ticks, and a rebuild from the log then reconstructs
    the *windowed* state (fresh init + last ``capacity`` ticks) rather than
    the full-history one — ``complete(tenant)`` tells callers which
    contract they are getting. Buffers are plain numpy (host-side, like the
    queue's pending deques); ``arrays`` materializes one ``(n, d)``/``(n,)``
    pair for the replay engine.

    Keys are arbitrary ints materialized on first append — slot indices on
    the snapshot tier, unbounded tenant *ids* on the policy tier
    (serve/api.py), which is why storage is a dict rather than a
    slot-indexed list. ``num_tenants`` is accepted for signature
    compatibility but no longer pre-sizes anything.
    """

    def __init__(self, num_tenants: int = 0, capacity: int = 256,
                 dtype=np.float32):
        if capacity < 1:
            raise ValueError("log capacity must be >= 1")
        self.capacity = capacity
        self._dtype = np.dtype(dtype)
        self._buf: dict[int, deque] = {}
        self._appended: dict[int, int] = {}

    def append(self, tenant: int, x, y) -> None:
        """Record one arrival (evicts the oldest entry when full)."""
        buf = self._buf.get(tenant)
        if buf is None:
            buf = self._buf[tenant] = deque(maxlen=self.capacity)
        self._appended[tenant] = self._appended.get(tenant, 0) + 1
        buf.append((np.asarray(x, self._dtype), self._dtype.type(y)))

    def tenants(self) -> list[int]:
        """Keys with any recorded history."""
        return list(self._buf)

    def size(self, tenant: int) -> int:
        """Entries currently held for ``tenant`` (<= capacity)."""
        buf = self._buf.get(tenant)
        return len(buf) if buf is not None else 0

    def dropped(self, tenant: int) -> int:
        """Arrivals lost to ring overflow since the last ``clear``."""
        return self._appended.get(tenant, 0) - self.size(tenant)

    def complete(self, tenant: int) -> bool:
        """True iff the log still holds the tenant's entire history, i.e.
        a rebuild from it matches the never-evicted state."""
        return self.dropped(tenant) == 0

    def arrays(self, tenant: int) -> tuple[np.ndarray, np.ndarray]:
        """Materialize the log as ``xs (n, d)``, ``ys (n,)`` in arrival
        order (empty logs yield ``(0, 0)``/``(0,)`` shapes)."""
        buf = self._buf.get(tenant)
        if not buf:
            return (
                np.zeros((0, 0), self._dtype),
                np.zeros((0,), self._dtype),
            )
        xs = np.stack([x for x, _ in buf])
        ys = np.asarray([y for _, y in buf], self._dtype)
        return xs, ys

    def move(self, src: int, dst: int) -> None:
        """Re-key one tenant's history (bank-compaction hook): ``dst``
        takes over ``src``'s buffer and overflow counter, including when
        ``src`` has none (``dst`` is then cleared)."""
        self.clear(dst)
        buf = self._buf.pop(src, None)
        if buf is not None:
            self._buf[dst] = buf
            self._appended[dst] = self._appended.pop(src)

    def clear(self, tenant: Optional[int] = None) -> None:
        """Forget one tenant's history — including the overflow counter,
        so the tenant reads ``complete()`` again — or every tenant's when
        None."""
        if tenant is None:
            self._buf.clear()
            self._appended.clear()
        else:
            self._buf.pop(tenant, None)
            self._appended.pop(tenant, None)


class StateSnapshot(NamedTuple):
    """A published read replica of the bank state.

    Attributes:
      state: the bank-state pytree at a flush boundary (immutable arrays).
      version: publish counter (0 = the initial, untrained state).
      tick: cumulative update-ticks folded into this replica — readers can
        bound their own staleness as ``queue.ticks_served - tick``.
    """

    state: Any
    version: int
    tick: int


class _Row(NamedTuple):
    """One-tenant view of a bank state (theta row) for the predict path."""

    theta: jax.Array


@partial(jax.jit, static_argnames=("mode", "precision"))
def _predict_block_jit(state, xq, fm, mode, precision):
    return bank_predict_block(state, xq, fm, mode=mode, precision=precision)


def predict_row(theta, xq, rff, *, mode: str = "auto",
                precision: Optional[str] = None) -> jax.Array:
    """Fused predict from one bare ``(D,)`` theta row: ``xq (Q, d)`` ->
    ``(Q,)``. The quarantine read path (serve/recovery.py) serves a
    tenant's captured last-healthy row through this without needing the
    row to live in any bank."""
    return _predict_block_jit(
        _Row(theta=jnp.asarray(theta)[None]),
        jnp.asarray(xq)[None],
        rff,
        mode=mode,
        precision=precision,
    )[0]


class SnapshotServer:
    """Double-buffered serving front end over a :class:`MicroBatchQueue`.

    Args:
      queue: the micro-batch queue owning the live (train) state.
      rff: the bank's shared feature map (any repro.features family).
      publish_every: publish a fresh read replica at the first flush
        boundary where this many update-ticks have accumulated since the
        last publish. 1 = publish after every flush (freshest reads);
        larger values amortize replica turnover at bounded staleness.
      mode / precision: read-path knobs forwarded to the fused predict
        kernel (``precision="bf16"`` = mixed-precision featurize, contract
        in kernels/ref.py). Training precision is untouched.
      age_watermark: seconds — flush when the oldest queued observation has
        waited this long (checked on ``submit`` / ``maybe_flush``).
      size_watermark: observations — flush when any tenant's backlog
        reaches this depth.
      clock: injectable monotonic clock (tests pass a fake).
      log_capacity: entries per tenant in the :class:`ReplayLog` ring
        buffer. None (default) disables logging — ``evict`` still works
        (the slot parks a fresh row) but ``readmit`` can only restart the
        tenant cold.
      evict_fn: ``(state, tenant) -> state`` releasing one slot; defaults
        to ``core.bank.evict_tenant`` with its family-inferred fresh row.
      rebuild_fn: ``(state, tenant, xs, ys) -> state`` replaying a log
        into one slot; the factories wire ``core.bank.rebuild_tenant``
        closures carrying the family hyperparameters and replay mode.
    """

    def __init__(
        self,
        queue: MicroBatchQueue,
        rff: FeatureLike,
        publish_every: int = 1,
        *,
        mode: str = "auto",
        precision: Optional[str] = None,
        age_watermark: Optional[float] = None,
        size_watermark: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        log_capacity: Optional[int] = None,
        evict_fn: Optional[Callable] = None,
        rebuild_fn: Optional[Callable] = None,
    ):
        if publish_every < 1:
            raise ValueError("publish_every must be >= 1")
        self.queue = queue
        self.rff = rff
        self.publish_every = publish_every
        self.mode = mode
        self.precision = precision
        self.age_watermark = age_watermark
        self.size_watermark = size_watermark
        self._clock = clock
        self._arrival_times = [deque() for _ in range(queue.num_tenants)]
        self._snapshot = StateSnapshot(state=queue.state, version=0, tick=0)
        self.log = (
            ReplayLog(queue.num_tenants, log_capacity, queue._dtype)
            if log_capacity is not None
            else None
        )
        self._evict_fn = evict_fn if evict_fn is not None else evict_tenant
        self._rebuild_fn = rebuild_fn
        self._evicted: set[int] = set()

    # -- read path ---------------------------------------------------------

    @property
    def snapshot(self) -> StateSnapshot:
        """The current read replica (grab once per request for consistency)."""
        return self._snapshot

    @property
    def staleness(self) -> int:
        """Update-ticks the read replica lags the live (train) state."""
        return self.queue.ticks_served - self._snapshot.tick

    def predict(self, tenant: int, xs) -> jax.Array:
        """Serve queries for one tenant from the frozen replica.

        ``xs`` is ``(d,)`` for one query (returns a scalar) or ``(Q, d)``
        for a query block (returns ``(Q,)``) — either way the fused
        predict-only path, never the live training state.
        """
        snap = self._snapshot  # one grab = one consistent replica
        xq = jnp.asarray(xs)
        single = xq.ndim == 1
        if single:
            xq = xq[None]
        row = _Row(theta=snap.state.theta[tenant][None])
        pred = _predict_block_jit(
            row, xq[None], self.rff, mode=self.mode, precision=self.precision
        )[0]
        return pred[0] if single else pred

    def predict_block(self, xq) -> jax.Array:
        """Serve a ``(B, Q, d)`` query block for the whole bank in one
        launch from the frozen replica -> ``(B, Q)``."""
        snap = self._snapshot
        return _predict_block_jit(
            snap.state,
            jnp.asarray(xq),
            self.rff,
            mode=self.mode,
            precision=self.precision,
        )

    # -- write path --------------------------------------------------------

    def submit(self, tenant: int, x, y) -> None:
        """Enqueue one observation; flush if a watermark trips.

        Every arrival is also appended to the replay log (when one is
        configured). An *evicted* tenant's arrivals stop here: they are
        logged but never queued, so the released slot stays untrained
        until :meth:`readmit` folds the whole log back in.
        """
        if self.log is not None:
            self.log.append(tenant, x, y)
        if tenant in self._evicted:
            return
        # Tag the arrival with its backlog position, not just a count:
        # observations submitted straight to the queue (legal; they opt out
        # of the age watermark) occupy positions too, and a flush must
        # consume exactly the timestamps of the positions it served.
        pos = len(self.queue._pending[tenant])
        self._arrival_times[tenant].append((pos, self._clock()))
        self.queue.submit(tenant, x, y)
        self.maybe_flush()

    def _consume_arrival_times(self, tenant: int, served: int) -> None:
        times = self._arrival_times[tenant]
        while times and times[0][0] < served:
            times.popleft()
        self._arrival_times[tenant] = deque(
            (pos - served, t) for pos, t in times
        )

    def maybe_flush(self) -> dict:
        """Background-flush hook: flush when the age or size watermark
        trips. Call from an outer event loop for purely time-driven
        flushes; ``submit`` calls it after every arrival.

        The ``snapshot.watermark`` span covers the test alone, so a flush
        it triggers is a sibling span, not a child. Each such flush counts
        once in ``snapshot.watermark_flushes{reason=size|age}``."""
        with _trace.span("snapshot.watermark"):
            reason = self._watermark_due()
        if reason is None:
            return {}
        _telemetry.registry().counter(
            "snapshot.watermark_flushes", reason=reason
        ).inc()
        return self.flush()

    def _watermark_due(self) -> Optional[str]:
        """Which watermark trips now: ``"size"``, ``"age"`` or None.

        The size test reads the queue's running counts, so it costs O(1)
        per arrival; the age test still walks the slots' arrival times,
        and runs only when nothing is due by size."""
        queue = self.queue
        if not queue.pending_total:
            return None
        if (
            self.size_watermark is not None
            and queue.max_backlog() >= self.size_watermark
        ):
            return "size"
        if self.age_watermark is not None:
            oldest = min(
                (t[0][1] for t in self._arrival_times if t), default=None
            )
            if oldest is not None and (
                self._clock() - oldest >= self.age_watermark
            ):
                return "age"
        return None

    def flush(self) -> dict:
        """One chunked train launch on the live state; publish when due.

        Due-ness is derived from :attr:`staleness` (replica tick vs
        ``queue.ticks_served``), not a local counter — so ticks applied by
        calling ``queue.flush()`` directly still count toward the bound.
        """
        res = self.queue.flush()
        for tenant, served in res.items():
            self._consume_arrival_times(tenant, len(served))
        if self.staleness >= self.publish_every:
            self.publish()
        return res

    def drain(self) -> dict:
        """Flush until every backlog is empty; merge per-tenant results."""
        merged: dict = {}
        while self.queue.pending_total:
            for tenant, served in self.flush().items():
                merged.setdefault(tenant, []).extend(served)
        return merged

    # -- tenant lifecycle --------------------------------------------------

    @property
    def evicted(self) -> frozenset[int]:
        """Tenants whose slots are currently released."""
        return frozenset(self._evicted)

    def evict(self, tenant: int) -> int:
        """Release one bank slot: drop the tenant's pending observations,
        park a fresh row in the slot (O(1) — ``core.bank.evict_tenant``),
        and publish so readers stop seeing the old weights immediately.

        The replay log is *kept*: it is the only record :meth:`readmit`
        rebuilds from. Returns the number of pending observations dropped
        (they were logged on submit, so readmission still replays them).
        """
        dropped = self.queue.drop_pending(tenant)
        self._arrival_times[tenant].clear()
        self.queue.state = self._evict_fn(self.queue.state, tenant)
        self._evicted.add(tenant)
        self.publish()
        return dropped

    def readmit(self, tenant: int, mode: Optional[str] = None) -> int:
        """Re-admit an evicted tenant by replaying its log into the slot.

        The rebuild runs through ``rebuild_fn`` (the factories wire
        ``core.bank.rebuild_tenant`` -> core/scan.py, so the slot is
        reconstructed in O(log T) scan depth rather than T sequential
        ticks), then a fresh replica is published. With no log or an empty
        one the tenant simply restarts cold on the parked fresh row.
        Returns the number of ticks replayed. If the ring overflowed
        (``log.complete(tenant)`` is False) the rebuilt state is the
        windowed one — fresh init + the last ``capacity`` ticks.
        """
        if tenant not in self._evicted:
            raise ValueError(f"tenant {tenant} is not evicted")
        replayed = 0
        if self.log is not None and self.log.size(tenant):
            if self._rebuild_fn is None:
                raise ValueError(
                    "readmit with a non-empty log needs a rebuild_fn "
                    "(use the klms/krls factories or pass one)"
                )
            xs, ys = self.log.arrays(tenant)
            with _trace.span(
                "snapshot.rebuild",
                tenant=tenant,
                ticks=len(ys),
                complete=self.log.complete(tenant),
            ):
                self.queue.state = self._rebuild_fn(
                    self.queue.state, tenant, xs, ys
                )
            replayed = len(ys)
        self._evicted.discard(tenant)
        self.publish()
        return replayed

    def release_slot(self, slot: int) -> int:
        """Release one bank slot *without* entering the evicted set (the
        policy tier's eviction hook): drop its pending observations, clear
        its arrival times, park a fresh row, publish. Unlike
        :meth:`evict`, subsequent submits to this slot train normally —
        the policy immediately reassigns the slot to another tenant, and
        per-tenant history lives in the policy tier's id-keyed log, not
        the slot-keyed one. Returns the dropped pending count."""
        dropped = self.queue.drop_pending(slot)
        self._arrival_times[slot].clear()
        self.queue.state = self._evict_fn(self.queue.state, slot)
        self._evicted.discard(slot)
        self.publish()
        return dropped

    def reset_tenant(self, tenant: int) -> int:
        """Reset ONE tenant to a fresh slot: drop its pending
        observations, clear its arrival times AND its replay-log history
        — including the ring-overflow counter, so the slot reads
        ``log.complete()`` again instead of inheriting the previous
        occupant's stale truncation flag — park a fresh row, and leave
        the evicted set. Returns the dropped pending count."""
        dropped = self.queue.drop_pending(tenant)
        self._arrival_times[tenant].clear()
        if self.log is not None:
            self.log.clear(tenant)
        self.queue.state = self._evict_fn(self.queue.state, tenant)
        self._evicted.discard(tenant)
        self.publish()
        return dropped

    def move_slot(self, src: int, dst: int) -> None:
        """Transfer slot-local bookkeeping from ``src`` to ``dst`` (bank
        compaction; the caller moves the state row itself): pending
        backlog, arrival counters and timestamps, evicted membership, and
        slot-keyed log history. ``src`` is left empty."""
        self.queue.move_slot(src, dst)
        self._arrival_times[dst] = self._arrival_times[src]
        self._arrival_times[src] = deque()
        if src in self._evicted:
            self._evicted.discard(src)
            self._evicted.add(dst)
        else:
            self._evicted.discard(dst)
        if self.log is not None:
            self.log.move(src, dst)

    def adopt_resized(self, state) -> None:
        """Adopt a grown/shrunk bank state (the policy tier's resize):
        resize the queue's per-slot buffers and the arrival-time ledger,
        drop lifecycle bookkeeping for truncated slots (which must be
        empty — compact first), and publish."""
        old = self.queue.num_tenants
        self.queue.adopt(state)
        new = self.queue.num_tenants
        if new >= old:
            self._arrival_times.extend(
                deque() for _ in range(new - old)
            )
        else:
            self._arrival_times = self._arrival_times[:new]
            self._evicted = {s for s in self._evicted if s < new}
            if self.log is not None:
                for t in self.log.tenants():
                    if t >= new:
                        self.log.clear(t)
        self.publish()

    def reset(self, state) -> None:
        """Restart both buffers on a fresh bank state (tenant-eviction /
        benchmark hook): the live queue state AND the published replica
        drop to version 0, and per-tenant lifecycle bookkeeping (arrival
        counters, replay logs with their truncation flags, the evicted
        set) is wiped with them. Pending observations must be drained
        first."""
        if self.queue.pending_total:
            raise RuntimeError("reset with pending observations; drain first")
        self.queue.state = state
        self.queue.ticks_served = 0
        self.queue.arrivals = [0] * self.queue.num_tenants
        self._arrival_times = [deque() for _ in range(self.queue.num_tenants)]
        self._snapshot = StateSnapshot(state=state, version=0, tick=0)
        if self.log is not None:
            self.log.clear()
        self._evicted.clear()

    def publish(self) -> StateSnapshot:
        """Swap the read replica to the live state (atomic: one reference
        assignment of an immutable pytree)."""
        self._snapshot = StateSnapshot(
            state=self.queue.state,
            version=self._snapshot.version + 1,
            tick=self.queue.ticks_served,
        )
        _trace.instant(
            "snapshot.publish",
            version=self._snapshot.version,
            tick=self._snapshot.tick,
        )
        return self._snapshot


def klms_snapshot_server(
    rff: FeatureLike,
    num_tenants: int,
    mu: Union[float, jax.Array] = 0.5,
    chunk: int = 16,
    publish_every: int = 1,
    mode: str = "auto",
    precision: Optional[str] = None,
    adaptive: bool = False,
    rebuild_mode: str = "scan",
    **kw,
) -> SnapshotServer:
    """Deprecated: use ``repro.serve.make_server(learner="klms", ...)``.

    Thin shim preserving the historical contract (returns the bare
    :class:`SnapshotServer`; per-tenant ``(B,)`` ``mu`` honored)."""
    from repro.serve import api

    api._deprecated(
        "klms_snapshot_server", 'make_server(learner="klms", ...)'
    )
    queue = api.make_queue(
        "klms", rff, num_tenants, chunk=chunk, mode=mode,
        adaptive=adaptive, mu=mu,
    )
    kw.setdefault(
        "rebuild_fn",
        lambda state, tenant, xs, ys: rebuild_tenant(
            state, tenant, rff, xs, ys, mu=mu, mode=rebuild_mode
        ),
    )
    return SnapshotServer(
        queue, rff, publish_every, mode=mode, precision=precision, **kw
    )


def krls_snapshot_server(
    rff: FeatureLike,
    num_tenants: int,
    lam: Union[float, jax.Array] = 1e-4,
    beta: Union[float, jax.Array] = 0.9995,
    chunk: int = 16,
    publish_every: int = 1,
    mode: str = "auto",
    precision: Optional[str] = None,
    adaptive: bool = False,
    rebuild_mode: str = "scan",
    **kw,
) -> SnapshotServer:
    """Deprecated: use ``repro.serve.make_server(learner="krls", ...)``.

    Thin shim preserving the historical contract (returns the bare
    :class:`SnapshotServer`; per-tenant ``(B,)`` ``lam``/``beta``
    honored)."""
    from repro.serve import api

    api._deprecated(
        "krls_snapshot_server", 'make_server(learner="krls", ...)'
    )
    queue = api.make_queue(
        "krls", rff, num_tenants, chunk=chunk, mode=mode,
        adaptive=adaptive, lam=lam, beta=beta,
    )
    kw.setdefault(
        "evict_fn",
        lambda state, tenant: evict_tenant(state, tenant, lam=lam),
    )
    kw.setdefault(
        "rebuild_fn",
        lambda state, tenant, xs, ys: rebuild_tenant(
            state, tenant, rff, xs, ys, lam=lam, beta=beta, mode=rebuild_mode
        ),
    )
    return SnapshotServer(
        queue, rff, publish_every, mode=mode, precision=precision, **kw
    )
