"""Micro-batching serve queue: ragged tenant arrivals -> masked (B, T) chunks.

The lockstep servers in serve/bank_loop.py assume every tenant delivers
exactly one observation per tick — real traffic doesn't. This module is the
ROADMAP "async serving over the filter bank" item, landed as the natural
consumer of the chunked kernels: arrivals are enqueued per tenant at any
rate, and each ``flush()`` coalesces up to ``chunk`` pending observations
per tenant into ONE time-blocked kernel launch — a ``(B, T, d)`` batch with
a per-(tenant, tick) validity mask covering both idle tenants (empty rows)
and short backlogs (partial rows).

Why this is safe: the paper's fixed-size state means a tenant that missed k
flushes needs no catch-up bookkeeping — its next chunk simply replays its
queued samples in arrival order, and masked slots are proven no-ops
(tests/test_chunked.py). Per-flush cost is one dispatch for the whole bank
instead of ``sum(backlog)`` per-tick dispatches; the dispatch-amortization
math is in README "Throughput model".

The queue is deliberately host-side and synchronous (submit/flush), so it
composes with any outer event loop; it owns the jitted chunk step and the
bank state, and always launches the same ``(B, chunk)`` shape so the step
traces exactly once.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Optional, Union

import jax
import numpy as np

from repro.core.bank import set_tenant_row
from repro.features.base import FeatureLike
from repro.obs import telemetry as _telemetry
from repro.obs import trace as _trace

__all__ = [
    "MicroBatchQueue",
    "make_chunked_bank_server",
    "make_chunked_krls_bank_server",
    "klms_micro_batch_queue",
    "krls_micro_batch_queue",
]


def make_chunked_bank_server(
    rff: FeatureLike,
    mu: Union[float, jax.Array],
    mode: str = "auto",
) -> Callable:
    """Deprecated: use ``repro.serve.make_chunk_step("klms", ...)``."""
    from repro.serve import api

    api._deprecated(
        "make_chunked_bank_server", 'make_chunk_step("klms", ...)'
    )
    return api.make_chunk_step("klms", rff, mode=mode, mu=mu)


def make_chunked_krls_bank_server(
    rff: FeatureLike,
    beta: Union[float, jax.Array] = 0.9995,
    mode: str = "auto",
) -> Callable:
    """Deprecated: use ``repro.serve.make_chunk_step("krls", ...)``."""
    from repro.serve import api

    api._deprecated(
        "make_chunked_krls_bank_server", 'make_chunk_step("krls", ...)'
    )
    return api.make_chunk_step("krls", rff, mode=mode, beta=beta)


class MicroBatchQueue:
    """Coalesce ragged per-tenant arrivals into masked ``(B, T)`` chunks.

    Args:
      chunk_step: jitted ``(state, xs, ys, mask) -> (state, StepOut)`` —
        from :func:`make_chunked_bank_server` or the KRLS variant.
      state: initial bank state (owned and advanced by the queue).
      input_dim: ``d`` of the feature space.
      chunk: T — the time-block cap every flush launches (constant shape
        by default, so the server compiles exactly once).
      adaptive: pick each flush's T from backlog depth (next power of two,
        capped at ``chunk``) instead of the global constant — the
        per-tenant chunk-size-adaptation ROADMAP item. At most
        log2(chunk)+1 shapes ever trace; ragged-stream equivalence is
        unchanged (tested). ``arrivals`` tracks cumulative per-tenant
        arrival counts as the adaptation/monitoring signal.
      stale_after: watchdog age bound in ``clock`` units. Under adaptive
        flush a quiet bank can strand a minority tenant's ticks
        indefinitely (nothing ever trips the size watermark); with a
        bound set, :meth:`has_stale` reports any arrival pending longer
        than this and :meth:`maybe_flush` force-flushes it, counting
        ``queue.stale_flush``. ``None`` (default) disables the watchdog.
      clock: injectable time source for the watchdog (tests pin it).

    ``submit`` enqueues one observation; ``flush`` processes up to T queued
    observations per tenant in arrival order and returns
    ``{tenant: [(prediction, prior_error), ...]}`` for what it consumed;
    ``drain`` flushes until every backlog is empty.
    """

    def __init__(self, chunk_step: Callable, state, input_dim: int,
                 chunk: int = 16, adaptive: bool = False,
                 stale_after: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._base_chunk_step = chunk_step
        self._chunk_step = chunk_step
        self.state = state
        self.input_dim = input_dim
        self.chunk = chunk
        self.adaptive = adaptive
        self.stale_after = stale_after
        self._clock = clock
        lead = jax.tree.leaves(state)[0]
        self.num_tenants = int(lead.shape[0])
        # Buffers take the bank's working precision (f64 banks under x64
        # must not round-trip observations through f32).
        self._dtype = np.dtype(lead.dtype)
        self._pending = [deque() for _ in range(self.num_tenants)]
        # Running backlog counts, so the snapshot tier's per-arrival
        # watermark test walks no slot: ``_depth_slots[k]`` slots hold
        # exactly k pending observations (k >= 1; index 0 unused),
        # ``_total`` over all slots, and ``_max`` bounds the deepest backlog
        # from above (:meth:`max_backlog` lowers it lazily). Every method
        # that changes ``_pending`` keeps them exact.
        self._depth_slots = [0]
        self._total = 0
        self._max = 0
        # Watchdog ledger: when each slot's *oldest* pending arrival was
        # enqueued (None = empty backlog). Set on the 0 -> 1 transition,
        # kept across partial flushes (the residual head is older than any
        # new arrival), cleared when the backlog empties.
        self._first_pending_at: list[Optional[float]] = (
            [None] * self.num_tenants
        )
        self.arrivals = [0] * self.num_tenants
        self.ticks_served = 0
        self.flushes = 0
        self.stale_flushes = 0
        self.last_probe: Optional[dict] = None

    def attach_probe(self, probe_fn: Callable) -> None:
        """Fuse a numerics tap into the flush program (obs/probes.py).

        ``probe_fn(state) -> {name: 0-d array}`` is composed *after* the
        chunk step inside one jitted program, so flush stays a single
        launch — the tap's reductions ride along instead of re-reading the
        state from HBM in a second dispatch. The latest readout lands in
        ``last_probe`` as device scalars; hosts (the serve facade's probe
        monitor) materialize it only at flush boundaries. Pass ``None``
        to detach and restore the bare step.
        """
        if probe_fn is None:
            self._chunk_step = self._base_chunk_step
            self.last_probe = None
            return
        base = self._base_chunk_step

        @jax.jit
        def probed_step(state, xs, ys, mask):
            state, out = base(state, xs, ys, mask)
            return state, out, probe_fn(state)

        self._chunk_step = probed_step

    def submit(self, tenant: int, x, y) -> None:
        """Enqueue one ``(x, y)`` observation for ``tenant``."""
        self.arrivals[tenant] += 1
        q = self._pending[tenant]
        depth = len(q)
        if not depth and self.stale_after is not None:
            self._first_pending_at[tenant] = self._clock()
        q.append((np.asarray(x, self._dtype), self._dtype.type(y)))
        self._count(depth, depth + 1)

    def _count(self, old: int, new: int) -> None:
        """Move one slot from backlog depth ``old`` to ``new`` in the
        running counts."""
        slots = self._depth_slots
        if old:
            slots[old] -= 1
        if new:
            if new >= len(slots):
                slots.extend([0] * (new + 1 - len(slots)))
            slots[new] += 1
            if new > self._max:
                self._max = new
        self._total += new - old

    @property
    def pending_total(self) -> int:
        """Pending observations over all slots (O(1))."""
        return self._total

    def max_backlog(self) -> int:
        """The deepest per-slot backlog (0 when nothing is pending).

        Amortized O(1): the bound steps down one empty bucket at a time,
        and each step was paid for by the submit that raised it.
        """
        slots, top = self._depth_slots, self._max
        while top and not slots[top]:
            top -= 1
        self._max = top
        return top

    def backlog(self) -> list[int]:
        """Pending observation count per tenant."""
        return [len(q) for q in self._pending]

    def restore_pending(self, pending) -> None:
        """Install every slot's pending ``(x, y)`` list, in arrival order
        (checkpoint restore), and recount. Watchdog stamps restart empty."""
        self._pending = [
            deque((np.asarray(x, self._dtype), self._dtype.type(y))
                  for x, y in pend)
            for pend in pending
        ]
        self._first_pending_at = [None] * self.num_tenants
        self._depth_slots, self._total, self._max = [0], 0, 0
        for q in self._pending:
            self._count(0, len(q))

    def drop_pending(self, tenant: int) -> int:
        """Discard ``tenant``'s queued observations (eviction hook).

        Returns the number dropped. Other tenants' backlogs, the bank
        state, and the served/arrival counters are untouched — a dropped
        observation was never folded into the state, so no counter lies.
        """
        dropped = len(self._pending[tenant])
        self._pending[tenant].clear()
        self._first_pending_at[tenant] = None
        self._count(dropped, 0)
        return dropped

    def move_slot(self, src: int, dst: int) -> None:
        """Transfer one slot's pending backlog and arrival counter to
        another slot (bank-compaction hook — the state row itself moves
        via ``tenant_row``/``set_tenant_row``). ``src`` is left empty."""
        if src == dst:
            return
        self._count(len(self._pending[dst]), 0)  # dst's own backlog goes
        self._pending[dst] = self._pending[src]
        self._pending[src] = deque()
        self._first_pending_at[dst] = self._first_pending_at[src]
        self._first_pending_at[src] = None
        self.arrivals[dst] = self.arrivals[src]
        self.arrivals[src] = 0

    def adopt(self, state) -> None:
        """Adopt a resized bank state (``core.bank.resize_bank``):
        re-derive B and grow/shrink the per-slot buffers with it. Slots
        being truncated must have empty backlogs — compact first."""
        new_b = int(jax.tree.leaves(state)[0].shape[0])
        if any(len(q) for q in self._pending[new_b:]):
            raise RuntimeError(
                "resize would drop pending observations; compact or drain"
            )
        self.state = state
        if new_b >= self.num_tenants:
            grow = new_b - self.num_tenants
            self._pending.extend(deque() for _ in range(grow))
            self._first_pending_at.extend([None] * grow)
            self.arrivals.extend([0] * grow)
        else:
            self._pending = self._pending[:new_b]
            self._first_pending_at = self._first_pending_at[:new_b]
            self.arrivals = self.arrivals[:new_b]
        self.num_tenants = new_b

    def replace_tenant(self, tenant: int, row) -> None:
        """Overwrite one tenant's slot of the live bank state in place
        (readmission hook — ``row`` is a single-tenant state pytree, e.g.
        from ``core.bank.rebuild_tenant``'s replay or ``tenant_row``)."""
        self.state = set_tenant_row(self.state, tenant, row)

    def _flush_chunk(self) -> int:
        """T for the next flush. Fixed mode always launches ``chunk`` (one
        trace ever); adaptive mode sizes T to the deepest backlog, rounded
        up to a power of two so only log2(chunk) shapes ever trace — a
        mostly-idle bank pays for a (B, 1) launch instead of a (B, chunk)
        one, and bursty tenants still get the full chunk."""
        if not self.adaptive:
            return self.chunk
        depth = max(1, self.max_backlog())
        return min(self.chunk, 1 << (depth - 1).bit_length())

    def has_stale(self) -> bool:
        """True when some arrival has been pending past ``stale_after``.

        Always False with the watchdog disabled (``stale_after=None``).
        """
        if self.stale_after is None:
            return False
        now = self._clock()
        return any(
            t0 is not None and now - t0 >= self.stale_after
            for t0 in self._first_pending_at
        )

    def maybe_flush(self) -> dict[int, list[tuple[float, float]]]:
        """Watchdog flush: launch only if some backlog has gone stale.

        The stranded-tenant guard for adaptive/externally-paced flushing —
        a minority tenant whose arrivals never trip the caller's size
        watermark still gets trained within ``stale_after``. Each forced
        launch increments ``stale_flushes`` and the ``queue.stale_flush``
        counter.
        """
        if not self.has_stale():
            return {}
        self.stale_flushes += 1
        _telemetry.registry().counter("queue.stale_flush").inc()
        return self.flush()

    def flush(self) -> dict[int, list[tuple[float, float]]]:
        """One chunked launch over up to T queued ticks per tenant."""
        bsz, tlen, d = self.num_tenants, self._flush_chunk(), self.input_dim
        if not self._total:
            _trace.instant("queue.flush.skip", tenants=bsz)
            return {}
        # The four phase spans cover the whole body, so they sum to the
        # flush.
        with _trace.span(
            "queue.flush", tenants=bsz, chunk=tlen, adaptive=self.adaptive
        ) as sp:
            with _trace.span("queue.batch"):
                xs = np.zeros((bsz, tlen, d), self._dtype)
                ys = np.zeros((bsz, tlen), self._dtype)
                mask = np.zeros((bsz, tlen), self._dtype)
                counts = []
                for b, q in enumerate(self._pending):
                    take = min(len(q), tlen)
                    for t in range(take):
                        x, y = q.popleft()
                        xs[b, t] = x
                        ys[b, t] = y
                        mask[b, t] = 1.0
                    counts.append(take)
                    if not q:
                        self._first_pending_at[b] = None
                    # Residual backlog keeps its stamp: the surviving head
                    # is at least as old as the arrival that set it.
                # Every slot drops by min(depth, T): depth k + T lands on k.
                slots = [0] + self._depth_slots[tlen + 1:]
                self._depth_slots = slots
                self._total = sum(k * n for k, n in enumerate(slots))
                self._max = max(self._max - tlen, 0)
            with _trace.span(
                "queue.launch", bytes=xs.nbytes + ys.nbytes + mask.nbytes
            ):
                result = self._chunk_step(self.state, xs, ys, mask)
                if len(result) == 3:
                    self.state, out, self.last_probe = result
                else:
                    self.state, out = result
            with _trace.span("queue.wait"):
                preds = np.asarray(out.prediction)
                errs = np.asarray(out.error)
            with _trace.span("queue.results"):
                self.flushes += 1
                served = sum(counts)
                self.ticks_served += served
                # One compiled-program execution per flush: the live launch
                # count for the serve path (the in-program kernel
                # dispatches were counted at trace time under
                # kernel.traces).
                _telemetry.registry().counter(
                    "dispatch.launches", site="queue.flush"
                ).inc()
                if sp is not None:
                    sp.attrs["ticks"] = served
                    sp.attrs["active"] = sum(1 for c in counts if c)
                return {
                    b: [
                        (float(preds[b, t]), float(errs[b, t]))
                        for t in range(c)
                    ]
                    for b, c in enumerate(counts)
                    if c
                }

    def drain(self) -> dict[int, list[tuple[float, float]]]:
        """Flush until all backlogs are empty; merge per-tenant results."""
        merged: dict[int, list[tuple[float, float]]] = {}
        while self._total:
            for b, res in self.flush().items():
                merged.setdefault(b, []).extend(res)
        return merged


def klms_micro_batch_queue(
    rff: FeatureLike,
    num_tenants: int,
    mu: Union[float, jax.Array] = 0.5,
    chunk: int = 16,
    mode: str = "auto",
    state=None,
    adaptive: bool = False,
) -> MicroBatchQueue:
    """Deprecated: use ``repro.serve.make_queue("klms", ...)``."""
    from repro.serve import api

    api._deprecated(
        "klms_micro_batch_queue", 'make_queue("klms", ...)'
    )
    return api.make_queue(
        "klms", rff, num_tenants, chunk=chunk, mode=mode, state=state,
        adaptive=adaptive, mu=mu,
    )


def krls_micro_batch_queue(
    rff: FeatureLike,
    num_tenants: int,
    lam: Union[float, jax.Array] = 1e-4,
    beta: Union[float, jax.Array] = 0.9995,
    chunk: int = 16,
    mode: str = "auto",
    state=None,
    adaptive: bool = False,
) -> MicroBatchQueue:
    """Deprecated: use ``repro.serve.make_queue("krls", ...)``."""
    from repro.serve import api

    api._deprecated(
        "krls_micro_batch_queue", 'make_queue("krls", ...)'
    )
    return api.make_queue(
        "krls", rff, num_tenants, chunk=chunk, mode=mode, state=state,
        adaptive=adaptive, lam=lam, beta=beta,
    )
