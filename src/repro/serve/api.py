"""Unified serving facade: one learner-parameterized entry point.

Historically every serving tier grew a parallel factory per learner family
(``make_bank_server`` / ``make_krls_bank_server``, ``klms_micro_batch_queue``
/ ``krls_micro_batch_queue``, ...), which scales as tiers x families. This
module collapses them into ONE parameterized surface:

* :func:`make_server` — the facade. Returns a :class:`Server` wrapping the
  whole write path (micro-batch queue -> chunked kernels), read path
  (snapshot-decoupled fused predict), tenant lifecycle (evict / readmit
  over replay logs), and — new in this tier — the **slot policy**
  (serve/policy.py) that manages the bank as a cache of hot tenants when
  tenant ids outnumber slots, plus a metrics registry (serve/metrics.py)
  instrumenting every request.
* :func:`make_tick` / :func:`make_chunk_step` / :func:`run_stream` /
  :func:`make_queue` / :func:`reset_slots` — the learner-parameterized
  building blocks the facade (and benchmarks) compose; these replace the
  per-family factories, which remain importable as deprecation shims.

Learner families: ``"klms"`` / ``"nklms"`` / ``"krls"`` ride the fused
Pallas bank kernels and the fused block-predict read path (KLMS/KRLS) or a
generic masked scan (NKLMS — no fused chunk kernel exists for the
normalized update); ``"qklms"`` / ``"ald"`` are the growing-dictionary
baselines, driven through the same queue/snapshot machinery by vmapping
their ``OnlineLearner`` step, with dictionary-aware predict and
sequential-replay rebuilds.

Policy mode: pass ``policy=`` ("lru" / "lfu" / "cost", a config dict, or a
:class:`~repro.serve.policy.SlotPolicy`) and tenant ids become *unbounded*
— the Server maintains a tenant->slot cache over a B-slot bank: misses
admit (possibly evicting the coldest incumbent, subject to the admission
floor), rejected arrivals are logged-not-trained, readmissions rebuild
from the per-tenant replay log through the parallel-in-time engine, and
``resize`` grows/shrinks the bank in pow2 steps with bitwise row
migration. Without a policy, tenant ids ARE slot indices (the pre-policy
contract, equivalence-tested against the deprecated factories).
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bank import (
    bank_init,
    bank_run,
    bank_size,
    bank_step,
    evict_tenant,
    klms_bank_chunk_step,
    klms_bank_init,
    klms_bank_run,
    klms_bank_step,
    krls_bank_chunk_step,
    krls_bank_init,
    krls_bank_run,
    krls_bank_step,
    resize_bank,
    set_tenant_row,
    tenant_row,
)
from repro.core.klms import LMSState, StepOut
from repro.core.krls import RLSState
from repro.core.learner import (
    OnlineLearner,
    ald_krls_learner,
    klms_learner,
    krls_learner,
    nklms_learner,
    qklms_learner,
)
from repro.features.base import FeatureLike
from repro.features.base import input_dim as fm_input_dim
from repro.obs import probes as _probes
from repro.obs import telemetry as _telemetry
from repro.obs import trace as _obtrace
from repro.serve.metrics import MetricsRegistry
from repro.serve.policy import SlotPolicy
from repro.serve.queue import MicroBatchQueue
from repro.serve.recovery import DurableLog, RecoveryPolicy, save_checkpoint
from repro.serve.snapshot import ReplayLog, SnapshotServer, predict_row

__all__ = [
    "LEARNER_FAMILIES",
    "Server",
    "make_server",
    "make_tick",
    "make_chunk_step",
    "run_stream",
    "make_queue",
    "reset_slots",
]

LEARNER_FAMILIES = ("klms", "nklms", "qklms", "krls", "ald")

# Families whose per-tenant state is a (D,) theta row sharing one feature
# map — they ride the fused read path; the rest carry dictionaries.
_THETA_FAMILIES = frozenset({"klms", "nklms", "krls"})

# One defaults table for every family; families read only their own knobs.
_HP_DEFAULTS = dict(
    mu=0.5,        # klms / nklms / qklms step size
    eps=1e-6,      # nklms normalizer
    lam=1e-4,      # krls init regularizer (P_0 = I/lam)
    beta=0.9995,   # krls forgetting factor
    sigma=1.0,     # qklms / ald kernel bandwidth
    quant_eps=0.1, # qklms quantization radius
    nu=5e-4,       # ald novelty threshold
    capacity=256,  # qklms / ald dictionary capacity
)


# ---------------------------------------------------------------------------
# Deprecation shims — the old per-family factory names wrap this helper.
# ---------------------------------------------------------------------------

_DEPRECATION_FIRED: set[str] = set()


def _deprecated(name: str, replacement: str) -> None:
    """Emit one DeprecationWarning per old factory name per process."""
    if name in _DEPRECATION_FIRED:
        return
    _DEPRECATION_FIRED.add(name)
    warnings.warn(
        f"repro.serve.{name} is deprecated; use {replacement}",
        DeprecationWarning,
        stacklevel=3,
    )


def _reset_deprecation_state() -> None:
    """Testing hook: re-arm the once-per-name deprecation latches."""
    _DEPRECATION_FIRED.clear()


# ---------------------------------------------------------------------------
# Learner construction
# ---------------------------------------------------------------------------


def _check_learner(learner: str) -> None:
    if learner not in LEARNER_FAMILIES:
        raise ValueError(
            f"unknown learner {learner!r}; pick from {LEARNER_FAMILIES}"
        )


def _resolve_hp(hp: dict) -> dict:
    unknown = set(hp) - set(_HP_DEFAULTS)
    if unknown:
        raise TypeError(
            f"unknown hyperparameters {sorted(unknown)}; "
            f"known: {sorted(_HP_DEFAULTS)}"
        )
    return {**_HP_DEFAULTS, **hp}


def _resolve_input_dim(
    learner: str, feature_map, input_dim: Optional[int]
) -> int:
    if feature_map is not None:
        return fm_input_dim(feature_map)
    if input_dim is not None:
        return input_dim
    raise ValueError(
        f"learner {learner!r} needs feature_map= or input_dim="
    )


def build_learner(
    learner: str,
    feature_map: Optional[FeatureLike] = None,
    input_dim: Optional[int] = None,
    **hp,
) -> OnlineLearner:
    """The :class:`OnlineLearner` bundle for one family (shared by the
    facade's predict/rebuild closures and the generic queue path)."""
    _check_learner(learner)
    h = _resolve_hp(hp)
    if learner in _THETA_FAMILIES and feature_map is None:
        raise ValueError(f"learner {learner!r} requires feature_map=")
    if learner == "klms":
        return klms_learner(feature_map, h["mu"])
    if learner == "nklms":
        return nklms_learner(feature_map, h["mu"], h["eps"])
    if learner == "krls":
        return krls_learner(feature_map, lam=h["lam"], beta=h["beta"])
    d = _resolve_input_dim(learner, feature_map, input_dim)
    if learner == "qklms":
        return qklms_learner(
            d, h["sigma"], h["mu"], h["quant_eps"], capacity=h["capacity"]
        )
    return ald_krls_learner(
        d, h["sigma"], nu=h["nu"], capacity=h["capacity"]
    )


# ---------------------------------------------------------------------------
# Per-tick and chunked step factories (the old make_*_server family)
# ---------------------------------------------------------------------------


def make_tick(
    learner: str,
    feature_map: Optional[FeatureLike] = None,
    *,
    mode: str = "auto",
    input_dim: Optional[int] = None,
    **hp,
) -> Callable:
    """Jitted lockstep tick for any family: ``(state, xs (B, d), ys (B,))
    -> (state, StepOut)``. KLMS/KRLS dispatch to the fused bank kernels;
    the rest vmap their ``OnlineLearner`` step."""
    _check_learner(learner)
    h = _resolve_hp(hp)
    if learner == "klms":

        @jax.jit
        def tick(state, xs, ys):
            return klms_bank_step(state, xs, ys, feature_map, h["mu"],
                                  mode=mode)

        return tick
    if learner == "krls":

        @jax.jit
        def tick(state, xs, ys):
            return krls_bank_step(state, xs, ys, feature_map, h["beta"],
                                  mode=mode)

        return tick
    lrn = build_learner(learner, feature_map, input_dim, **hp)

    @jax.jit
    def tick(state, xs, ys):
        return bank_step(lrn, state, xs, ys)

    return tick


def _gate_leaf(mask_b: jax.Array, new, old):
    m = mask_b.reshape(mask_b.shape + (1,) * (new.ndim - 1))
    return jnp.where(m > 0, new, old)


def _generic_chunk_server(lrn: OnlineLearner) -> Callable:
    """Masked chunked server over a vmapped ``OnlineLearner`` step.

    Same contract as the fused chunk factories: ``(state, xs (B, T, d),
    ys (B, T), mask (B, T)) -> (state, StepOut (B, T))``; masked ticks
    leave every state leaf untouched (per-leaf ``where`` gate), so ragged
    micro-batches stay exact for dictionary learners too."""

    @jax.jit
    def step(state, xs, ys, mask):
        def tick(s, xym):
            x_t, y_t, m_t = xym
            s2, out = jax.vmap(lrn.step_fn)(s, x_t, y_t)
            s3 = jax.tree.map(functools.partial(_gate_leaf, m_t), s2, s)
            return s3, out

        xs_t = jnp.swapaxes(xs, 0, 1)
        ys_t = jnp.swapaxes(ys, 0, 1)
        mask_t = jnp.swapaxes(mask, 0, 1)
        state, outs = jax.lax.scan(tick, state, (xs_t, ys_t, mask_t))
        return state, jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), outs)

    return step


def make_chunk_step(
    learner: str,
    feature_map: Optional[FeatureLike] = None,
    *,
    mode: str = "auto",
    input_dim: Optional[int] = None,
    **hp,
) -> Callable:
    """Jitted chunked server for any family: ``(state, xs (B, T, d),
    ys (B, T), mask (B, T)) -> (state, StepOut)`` — one launch per chunk
    (the micro-batch queue's step)."""
    _check_learner(learner)
    h = _resolve_hp(hp)
    if learner == "klms":

        @jax.jit
        def step(state, xs, ys, mask):
            return klms_bank_chunk_step(
                state, xs, ys, feature_map, h["mu"], mask, mode=mode
            )

        return step
    if learner == "krls":

        @jax.jit
        def step(state, xs, ys, mask):
            return krls_bank_chunk_step(
                state, xs, ys, feature_map, h["beta"], mask, mode=mode
            )

        return step
    return _generic_chunk_server(
        build_learner(learner, feature_map, input_dim, **hp)
    )


# ---------------------------------------------------------------------------
# Whole-stream drives and slot resets (the old serve_*_stream / reset_*)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode", "chunk"))
def _klms_stream(rff, xs, ys, mu, state=None, mode="auto", chunk=None):
    return klms_bank_run(rff, xs, ys, mu, state=state, mode=mode, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("mode", "chunk"))
def _krls_stream(
    rff, xs, ys, lam=1e-4, beta=0.9995, state=None, mode="auto", chunk=None
):
    return krls_bank_run(
        rff, xs, ys, lam=lam, beta=beta, state=state, mode=mode, chunk=chunk
    )


def run_stream(
    learner: str,
    feature_map: Optional[FeatureLike],
    xs: jax.Array,
    ys: jax.Array,
    *,
    state=None,
    mode: str = "auto",
    chunk: Optional[int] = None,
    input_dim: Optional[int] = None,
    **hp,
):
    """Serve B lockstep tenant streams ``xs (B, n, d)``, ``ys (B, n)`` in
    one jit for any family (the old ``serve_bank_stream`` /
    ``serve_krls_bank_stream``, learner-parameterized). ``chunk=T`` picks
    the time-blocked kernel schedule for the fused families."""
    _check_learner(learner)
    h = _resolve_hp(hp)
    if learner == "klms":
        return _klms_stream(
            feature_map, xs, ys, h["mu"], state=state, mode=mode, chunk=chunk
        )
    if learner == "krls":
        return _krls_stream(
            feature_map, xs, ys, lam=h["lam"], beta=h["beta"], state=state,
            mode=mode, chunk=chunk,
        )
    lrn = build_learner(learner, feature_map, input_dim, **hp)
    if state is None:
        state = bank_init(lrn, xs.shape[0])
    return jax.jit(lambda s, x, y: bank_run(lrn, s, x, y))(state, xs, ys)


def reset_slots(state, slots, *, learner: Optional[str] = None,
                lam: Union[float, jax.Array] = 1e-4):
    """Re-admit tenants into bank ``slots`` (an int array of indices) on a
    fresh row — O(1) per slot. The family is inferred from the state
    (``learner=`` overrides): LMS rows zero, RLS rows re-seed
    ``P_0 = I/lam``, dictionary rows zero their buffers."""
    if learner is None:
        learner = "krls" if isinstance(state, RLSState) else "klms"
    if learner == "krls":
        dfeat = state.theta.shape[-1]
        return RLSState(
            theta=state.theta.at[slots].set(0.0),
            pmat=state.pmat.at[slots].set(
                jnp.eye(dfeat, dtype=state.pmat.dtype) / lam
            ),
            step=state.step.at[slots].set(0),
        )
    if isinstance(state, LMSState):
        return LMSState(
            theta=state.theta.at[slots].set(0.0),
            step=state.step.at[slots].set(0),
        )
    return jax.tree.map(lambda a: a.at[slots].set(jnp.zeros_like(a[slots])),
                        state)


# ---------------------------------------------------------------------------
# Queue factory (the old *_micro_batch_queue pair)
# ---------------------------------------------------------------------------


def make_queue(
    learner: str = "klms",
    feature_map: Optional[FeatureLike] = None,
    bank: int = 8,
    *,
    chunk: int = 16,
    mode: str = "auto",
    adaptive: bool = False,
    state=None,
    input_dim: Optional[int] = None,
    **hp,
) -> MicroBatchQueue:
    """Ready-to-serve micro-batch queue for any family: fresh bank state
    plus the jitted chunk server, coalescing ragged arrivals into masked
    ``(B, T)`` launches."""
    _check_learner(learner)
    h = _resolve_hp(hp)
    if state is None:
        if learner in ("klms", "nklms"):
            state = klms_bank_init(feature_map, bank)
        elif learner == "krls":
            state = krls_bank_init(feature_map, bank, h["lam"])
        else:
            state = bank_init(
                build_learner(learner, feature_map, input_dim, **hp), bank
            )
    d = _resolve_input_dim(learner, feature_map, input_dim)
    return MicroBatchQueue(
        make_chunk_step(
            learner, feature_map, mode=mode, input_dim=input_dim, **hp
        ),
        state,
        d,
        chunk=chunk,
        adaptive=adaptive,
    )


# ---------------------------------------------------------------------------
# The Server facade
# ---------------------------------------------------------------------------


class Server:
    """One serving object per bank: write path, read path, lifecycle,
    policy, and metrics behind a single learner-agnostic surface.

    Built by :func:`make_server`. Without a policy, ``tenant`` arguments
    are bank-slot indices in ``[0, slots)`` — exactly the pre-facade
    :class:`~repro.serve.snapshot.SnapshotServer` contract. With a policy,
    ``tenant`` is an arbitrary id; the Server runs the bank as a cache
    (see module docstring) and ``resize`` manages capacity in pow2 steps.

    Metrics (``self.metrics``): counters ``requests.write`` /
    ``requests.read`` / ``bank.hits`` / ``bank.misses`` / ``evictions`` /
    ``readmissions`` / ``admission.rejects`` / ``read.cold`` /
    ``resizes``, gauge ``queue.backlog`` (set when :meth:`observability`
    exports, not per arrival), histograms ``latency.write_us`` /
    ``latency.read_us``.

    Observability (``make_server(trace=..., probe=...)``): a Tracer
    records nested ``serve.*`` / ``queue.*`` / ``snapshot.*`` /
    ``kernel.*`` spans for every request (it is *activated* around each
    public method, so the deeper tiers' spans land on it without API
    threading); a :class:`~repro.obs.probes.ProbeMonitor` rides the
    queue's fused in-jit numerics tap and raises degradation events.
    :meth:`observability` exports metrics + dispatch telemetry + probe
    state + trace summary as one plain dict (schema in README
    "Observability").
    """

    def __init__(
        self,
        inner: SnapshotServer,
        *,
        learner: str,
        lrn: OnlineLearner,
        feature_map: Optional[FeatureLike],
        hp: dict,
        policy: Optional[SlotPolicy] = None,
        metrics: Optional[MetricsRegistry] = None,
        log_capacity: Optional[int] = None,
        auto_resize: bool = False,
        latency_clock: Callable[[], float] = time.perf_counter,
        tracer: Optional[_obtrace.Tracer] = None,
        probe: Union[bool, dict, None] = None,
        recovery: Optional[RecoveryPolicy] = None,
        wal: Optional[DurableLog] = None,
    ):
        self._inner = inner
        self.learner = learner
        self._lrn = lrn
        self.feature_map = feature_map
        self._hp = hp
        self.policy = policy
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.auto_resize = auto_resize
        self._lat = latency_clock
        self._theta_family = learner in _THETA_FAMILIES
        self.tracer = tracer
        self.wal = wal
        self._wal_suspended = False
        # Expected-ticks ledger, slot-keyed: observations this facade put
        # on the queue that the bank is on the hook to train. The
        # ``ticks_lag`` probe compares it against backlog + the state's
        # step counters — a positive gap means arrivals were acknowledged
        # but silently lost between queue and bank.
        self._expected: dict[int, int] = {}
        self._probe_folded_flush = -1
        if probe:
            self.probe = _probes.ProbeMonitor(
                probe if isinstance(probe, dict) else None,
                registry=self.metrics,
            )
            inner.queue.attach_probe(_probes.stats_tap)
        else:
            self.probe = None
        if policy is not None:
            # Tenant-ID-keyed logs (ids are unbounded in policy mode); the
            # inner slot-indexed log stays disabled.
            self.log = ReplayLog(0, log_capacity or 256, inner.queue._dtype)
            if policy.cost_fn is None:
                policy.cost_fn = self._rebuild_cost
        else:
            self.log = inner.log
        # A pristine row captured before any training: the pad row for
        # bank growth (theta 0 / P_0 = I/lam / zeroed dictionaries).
        self._fresh_row = tenant_row(inner.queue.state, 0)
        self.recovery = recovery
        if recovery is not None:
            recovery.bind(self)
        if not self._theta_family:
            pf = lrn.predict_fn
            self._row_predict = jax.jit(
                lambda row, xq: jax.vmap(lambda x: pf(row, x))(xq)
            )
            self._block_predict = jax.jit(
                lambda state, xq: jax.vmap(
                    lambda s, q: jax.vmap(lambda x: pf(s, x))(q)
                )(state, xq)
            )

    # -- introspection -------------------------------------------------------

    @property
    def queue(self) -> MicroBatchQueue:
        return self._inner.queue

    @property
    def snapshot(self):
        return self._inner.snapshot

    @property
    def staleness(self) -> int:
        return self._inner.staleness

    @property
    def slots(self) -> int:
        return self._inner.queue.num_tenants

    @property
    def resident(self) -> dict:
        """tenant -> slot map (identity without a policy)."""
        if self.policy is None:
            return {t: t for t in range(self.slots)}
        return self.policy.resident

    @property
    def evicted(self):
        return self._inner.evicted

    @property
    def snapshot_server(self) -> SnapshotServer:
        """The underlying snapshot tier (slot-indexed)."""
        return self._inner

    def hit_rate(self) -> float:
        """Resident-lookup hit fraction over all reads + writes so far."""
        hits = self.metrics.count("bank.hits")
        misses = self.metrics.count("bank.misses")
        return hits / (hits + misses) if hits + misses else 1.0

    # -- observability -------------------------------------------------------

    def _act(self):
        """Activate this server's tracer (no-op context when untraced)."""
        return _obtrace.activate(self.tracer)

    def _slot_lags(self) -> list[int]:
        """Per-slot expected-minus-trained tick gap: the facade's ledger
        against queue backlog plus the state's own step counters. A
        positive entry means observations this server queued were never
        folded into the bank (the ``ticks_lag`` probe / a dropped flush);
        negative entries (someone fed the queue directly, bypassing the
        facade) are legal and never fire."""
        step = np.asarray(self._inner.queue.state.step)
        backlog = self._inner.queue.backlog()
        return [
            self._expected.get(s, 0) - backlog[s] - int(step[s])
            for s in range(self.slots)
        ]

    def _note_queued(self, slot: int) -> None:
        self._expected[slot] = self._expected.get(slot, 0) + 1

    def _probe_update(self) -> None:
        """Fold the queue's latest in-jit tap readout into the monitor —
        once per flush (the tap only changes at flush boundaries, and
        re-folding a stale readout would re-fire its events), then let
        the recovery policy act on anything that fired."""
        if self.probe is None:
            return
        queue = self._inner.queue
        tap = queue.last_probe
        if tap is None or queue.flushes == self._probe_folded_flush:
            if self.recovery is not None:
                self.recovery.process()  # backoff retries between flushes
            return
        self._probe_folded_flush = queue.flushes
        stats = {k: float(v) for k, v in tap.items()}
        stats["ticks_lag"] = float(max(self._slot_lags(), default=0))
        if (
            self.recovery is not None
            and self.recovery.reference_clock is not None
        ):
            stats["clock_skew"] = self.recovery.measure_skew()
        self.probe.update(
            stats,
            tick=queue.ticks_served,
            staleness=self._inner.staleness,
        )
        if self.recovery is not None:
            self.recovery.process()

    def check_read_contract(self, xq) -> float:
        """Measure the bf16 read-contract error vs the f32 path on a
        sampled ``(B, Q, d)`` query block against the current replica, and
        fold it into the probe monitor (when one is configured). Returns
        the max relative error. Theta families only."""
        if not self._theta_family:
            raise ValueError(
                "bf16 read contract applies to the fused theta families"
            )
        with self._act(), _obtrace.span("serve.read_contract"):
            err = _probes.bf16_read_error(
                self._inner.snapshot.state,
                self.feature_map,
                jnp.asarray(xq),
                mode=self._inner.mode,
            )
            if self.probe is not None:
                tap = {
                    k: v
                    for k, v in self.probe.last_stats.items()
                    if k not in ("staleness_ticks", "bf16_read_error",
                                 "ticks_lag", "clock_skew")
                }
                self.probe.update(
                    tap,
                    tick=self._inner.queue.ticks_served,
                    staleness=self._inner.staleness,
                    bf16_err=err,
                )
        return err

    def observability(self) -> dict:
        """One plain-dict export of everything observable about this
        server::

            {"metrics": MetricsRegistry.snapshot(),
             "dispatch": repro.obs.telemetry.snapshot(),   # process-wide
             "probes": ProbeMonitor.state() | None,
             "trace": Tracer.summary() | None}

        Stable schema (validated by scripts/check_bench_schema.py for the
        records the Zipf bench embeds); see README "Observability".
        The ``queue.backlog`` gauge is set here, at export.
        """
        self.metrics.set_gauge(
            "queue.backlog", float(self._inner.queue.pending_total)
        )
        return {
            "metrics": self.metrics.snapshot(),
            "dispatch": _telemetry.snapshot(),
            "probes": self.probe.state() if self.probe is not None else None,
            "trace": (
                self.tracer.summary() if self.tracer is not None else None
            ),
        }

    # -- write path ----------------------------------------------------------

    def submit(self, tenant: int, x, y) -> None:
        """Enqueue one observation for ``tenant`` (admitting / evicting /
        rejecting through the policy when one is configured)."""
        t0 = self._lat()
        with self._act(), _obtrace.span("serve.submit", tenant=tenant):
            self.metrics.counter("requests.write").inc()
            if self.wal is not None and not self._wal_suspended:
                self.wal.append(tenant, x, y)
            if (
                self.recovery is not None
                and tenant in self.recovery.quarantined
            ):
                self._quarantined_submit(tenant, x, y)
            elif self.policy is None:
                if tenant not in self._inner._evicted:
                    self._note_queued(tenant)
                self._inner.submit(tenant, x, y)
            else:
                self._policy_submit(tenant, x, y)
            self._probe_update()
            self.metrics.histogram("latency.write_us").observe(
                (self._lat() - t0) * 1e6
            )
            if self.policy is not None and self.auto_resize:
                target = self.policy.suggest_size()
                if target != self.slots:
                    self.resize(target)

    def _policy_submit(self, tenant: int, x, y) -> None:
        pol = self.policy
        pol.touch(tenant)
        slot = pol.lookup(tenant)
        if slot is not None:
            self.metrics.counter("bank.hits").inc()
        else:
            self.metrics.counter("bank.misses").inc()
            decision = pol.admit(tenant)
            if decision.action == "reject":
                # Logged, not trained: the history is intact for a later
                # admission, but the bank spends nothing on this tenant.
                self.metrics.counter("admission.rejects").inc()
                self.log.append(tenant, x, y)
                return
            if decision.action == "evict":
                self.metrics.counter("evictions").inc()
                self._inner.release_slot(decision.slot)
                self._expected[decision.slot] = 0
            slot = decision.slot
            self._install(tenant, slot)
        self.log.append(tenant, x, y)
        self._note_queued(slot)
        self._inner.submit(slot, x, y)

    def _quarantined_submit(self, tenant: int, x, y) -> None:
        """A quarantined tenant's arrivals are logged, never trained —
        a rebuild repair replays them; a reset forfeits them with the
        rest of the history. The policy clock still ticks so admission
        ordering stays deterministic across the episode."""
        self.metrics.counter("recovery.deferred").inc()
        if self.policy is not None:
            self.policy.touch(tenant)
            self.log.append(tenant, x, y)
        elif self.log is not None:
            self.log.append(tenant, x, y)

    def _install(self, tenant: int, slot: int) -> int:
        """Rebuild ``tenant``'s state from its log into ``slot``."""
        n = self.log.size(tenant)
        if n:
            with _obtrace.span(
                "serve.install", tenant=tenant, slot=slot, ticks=n
            ):
                xs, ys = self.log.arrays(tenant)
                self._inner.queue.state = self._inner._rebuild_fn(
                    self._inner.queue.state, slot, xs, ys
                )
                self.metrics.counter("readmissions").inc()
                self._inner.publish()
        self._expected[slot] = n
        return n

    def flush(self) -> dict:
        with self._act(), _obtrace.span("serve.flush"):
            res = self._inner.flush()
            self._probe_update()
            return res

    def maybe_flush(self) -> dict:
        with self._act():
            res = self._inner.maybe_flush()
            if res:
                self._probe_update()
            return res

    def drain(self) -> dict:
        with self._act(), _obtrace.span("serve.drain"):
            res = self._inner.drain()
            self._probe_update()
            return res

    # -- read path -----------------------------------------------------------

    def _slot_predict(self, slot: int, xs) -> jax.Array:
        if self._theta_family:
            return self._inner.predict(slot, xs)
        snap = self._inner.snapshot
        xq = jnp.asarray(xs)
        single = xq.ndim == 1
        if single:
            xq = xq[None]
        row = tenant_row(snap.state, slot)
        pred = self._row_predict(row, xq)
        return pred[0] if single else pred

    def predict(self, tenant: int, xs) -> jax.Array:
        """Serve queries for one tenant from the frozen read replica.

        ``xs`` is ``(d,)`` (scalar out) or ``(Q, d)`` (``(Q,)`` out). In
        policy mode a non-resident tenant gets the *cold* prediction
        (fresh-state zeros) — reads never admit, so the read path stays
        O(1) regardless of replay-log depth.
        """
        t0 = self._lat()
        with self._act(), _obtrace.span("serve.predict", tenant=tenant):
            self.metrics.counter("requests.read").inc()
            if (
                self.recovery is not None
                and tenant in self.recovery.quarantined
            ):
                pred = self._quarantined_predict(tenant, xs)
            elif self.policy is None:
                pred = self._slot_predict(tenant, xs)
            else:
                self.policy.touch(tenant)
                slot = self.policy.lookup(tenant)
                if slot is None:
                    self.metrics.counter("bank.misses").inc()
                    self.metrics.counter("read.cold").inc()
                    xq = np.asarray(xs)
                    shape = () if xq.ndim == 1 else (xq.shape[0],)
                    pred = jnp.zeros(shape, self._inner.queue._dtype)
                else:
                    self.metrics.counter("bank.hits").inc()
                    pred = self._slot_predict(slot, xs)
            self.metrics.histogram("latency.read_us").observe(
                (self._lat() - t0) * 1e6
            )
            return pred

    def _quarantined_predict(self, tenant: int, xs) -> jax.Array:
        """Serve a quarantined tenant's reads from the captured
        last-healthy replica row (cold zeros if it was never seen
        healthy) — the degraded slot is never read."""
        self.metrics.counter("read.quarantined").inc()
        if self.policy is not None:
            self.policy.touch(tenant)
        row = self.recovery.healthy_row(tenant)
        xq = jnp.asarray(xs)
        single = xq.ndim == 1
        if single:
            xq = xq[None]
        if row is None:
            pred = jnp.zeros((xq.shape[0],), self._inner.queue._dtype)
        elif self._theta_family:
            pred = predict_row(
                row.theta, xq, self.feature_map,
                mode=self._inner.mode, precision=self._inner.precision,
            )
        else:
            pred = self._row_predict(row, xq)
        return pred[0] if single else pred

    def predict_block(self, xq) -> jax.Array:
        """Serve a ``(B, Q, d)`` query block over the whole bank (slot
        space) in one launch from the frozen replica -> ``(B, Q)``."""
        t0 = self._lat()
        with self._act(), _obtrace.span("serve.predict_block"):
            self.metrics.counter("requests.read").inc()
            if self._theta_family:
                pred = self._inner.predict_block(xq)
            else:
                pred = self._block_predict(
                    self._inner.snapshot.state, jnp.asarray(xq)
                )
            self.metrics.histogram("latency.read_us").observe(
                (self._lat() - t0) * 1e6
            )
            return pred

    # -- lifecycle -----------------------------------------------------------

    def evict(self, tenant: int) -> int:
        """Release ``tenant``'s slot. Returns dropped pending count."""
        with self._act(), _obtrace.span("serve.evict", tenant=tenant):
            if self.policy is None:
                dropped = self._inner.evict(tenant)
                self._expected[tenant] = 0
            else:
                slot = self.policy.release(tenant)
                if slot is None:
                    return 0
                dropped = self._inner.release_slot(slot)
                self._expected[slot] = 0
            self.metrics.counter("evictions").inc()
            return dropped

    def readmit(self, tenant: int) -> int:
        """Re-admit ``tenant``, rebuilding its state from the replay log.

        Policy mode bypasses the admission floor (an explicit readmit is
        an operator decision), evicting the coldest incumbent if the bank
        is full. Returns the number of replayed ticks.
        """
        with self._act(), _obtrace.span("serve.readmit", tenant=tenant):
            if self.policy is None:
                n = self._inner.readmit(tenant)
                self._expected[tenant] = n
                self.metrics.counter("readmissions").inc()
                return n
            pol = self.policy
            if pol.lookup(tenant) is not None:
                return 0
            pol.touch(tenant)
            decision = pol.admit(tenant, force=True)
            if decision.action == "evict":
                self.metrics.counter("evictions").inc()
                self._inner.release_slot(decision.slot)
                self._expected[decision.slot] = 0
            return self._install(tenant, decision.slot)

    def reset_tenant(self, tenant: int) -> int:
        """Reset ONE tenant to a fresh row — the O(1) last rung of the
        recovery ladder, also useful as an operator action. The tenant's
        replay history (and its ring-overflow flag) is forgotten with the
        state; in policy mode a resident tenant keeps its slot. Returns
        the dropped pending count."""
        with self._act(), _obtrace.span("serve.reset_tenant", tenant=tenant):
            self.metrics.counter("resets").inc()
            if self.policy is None:
                dropped = self._inner.reset_tenant(tenant)
                self._expected[tenant] = 0
                return dropped
            self.log.clear(tenant)
            slot = self.policy.lookup(tenant)
            if slot is None:
                return 0
            inner = self._inner
            dropped = inner.queue.drop_pending(slot)
            inner._arrival_times[slot].clear()
            inner.queue.state = inner._evict_fn(inner.queue.state, slot)
            inner.publish()
            self._expected[slot] = 0
            return dropped

    def checkpoint(self, directory, *, keep: int = 3) -> str:
        """Write one durable checkpoint generation of this server's full
        state (serve/recovery.py); returns the checkpoint path."""
        with self._act():
            return save_checkpoint(self, directory, keep=keep)

    def reset(self, state=None) -> None:
        """Restart on a fresh bank state: queue, replica, logs, residency
        and policy clocks all drop to zero. Drain pending first."""
        if state is None:
            state = resize_bank(
                jax.tree.map(lambda a: a[:1], self._inner.queue.state),
                self.slots,
                fresh_row=self._fresh_row,
            )
            state = set_tenant_row(state, 0, self._fresh_row)
        self._inner.reset(state)
        self._expected.clear()
        if self.policy is not None:
            self.log.clear()
            pol = self.policy
            pol.clock = 0
            pol.last_touch.clear()
            pol.touches.clear()
            pol._resident.clear()
            pol.set_slots(bank_size(state))

    # -- capacity ------------------------------------------------------------

    def resize(self, new_slots: int) -> None:
        """Grow or shrink the bank to ``new_slots`` (a power of two).

        Growth appends fresh rows; resident tenants are bitwise-untouched.
        Shrink first evicts the coldest residents until the survivors fit,
        then compacts remaining residents into ``[0, new_slots)`` via
        ``tenant_row``/``set_tenant_row`` — surviving rows are
        bitwise-preserved (tested) — and slices the bank.
        """
        if self.policy is None:
            raise ValueError("resize requires a policy tier")
        if new_slots < 1 or (new_slots & (new_slots - 1)):
            raise ValueError(f"new_slots must be a power of two, got {new_slots}")
        cur = self.slots
        if new_slots == cur:
            return
        with self._act(), _obtrace.span(
            "serve.resize", slots=cur, new_slots=new_slots
        ):
            self.metrics.counter("resizes").inc()
            pol, inner = self.policy, self._inner
            if new_slots < cur:
                while pol.occupancy > new_slots:
                    self.evict(pol.victim())
                state = inner.queue.state
                used = set(pol.resident.values())
                free_low = [s for s in range(new_slots) if s not in used]
                for tenant, slot in sorted(
                    pol.resident.items(), key=lambda kv: kv[1]
                ):
                    if slot < new_slots:
                        continue
                    dst = free_low.pop(0)
                    state = set_tenant_row(
                        state, dst, tenant_row(state, slot)
                    )
                    inner.move_slot(slot, dst)
                    self._expected[dst] = self._expected.pop(slot, 0)
                    pol.move(tenant, dst)
                inner.queue.state = state
            new_state = resize_bank(
                inner.queue.state, new_slots, fresh_row=self._fresh_row
            )
            inner.adopt_resized(new_state)
            self._expected = {
                s: v for s, v in self._expected.items() if s < new_slots
            }
            pol.set_slots(new_slots)

    # -- policy support ------------------------------------------------------

    def _rebuild_cost(self, tenant: int) -> float:
        """Rebuild-cost estimate for the cost-aware scorer: replay-log
        length x per-tick family cost, plus the fixed solve for KRLS.

        KLMS-family replays are O(D) affine scans per tick; a KRLS replay
        pays O(D^2) per tick plus one (D, D) solve; the dictionary
        baselines replay sequentially over their capacity-M buffers
        (QKLMS O(M d), ALD O(M^2) per tick).
        """
        n = max(1, self.log.size(tenant))
        hp = self._hp
        if self._theta_family:
            dfeat = self.feature_map.num_features
            if self.learner == "krls":
                return float(n) * dfeat * dfeat + float(dfeat) ** 3
            return float(n) * dfeat
        cap = hp["capacity"]
        if self.learner == "ald":
            return float(n) * cap * cap
        return float(n) * cap


def _resolve_policy(policy, bank: int) -> Optional[SlotPolicy]:
    if policy is None:
        return None
    if isinstance(policy, SlotPolicy):
        if policy.slots != bank:
            raise ValueError(
                f"policy manages {policy.slots} slots but bank={bank}"
            )
        return policy
    if isinstance(policy, str):
        return SlotPolicy(bank, scorer=policy)
    if isinstance(policy, dict):
        return SlotPolicy(bank, **policy)
    raise TypeError(f"policy must be None, str, dict or SlotPolicy; got {policy!r}")


def make_server(
    learner: str = "klms",
    *,
    feature_map: Optional[FeatureLike] = None,
    bank: int = 8,
    chunk: int = 16,
    mode: str = "auto",
    adaptive: bool = False,
    precision: Optional[str] = None,
    publish_every: int = 1,
    age_watermark: Optional[float] = None,
    size_watermark: Optional[int] = None,
    clock: Callable[[], float] = time.monotonic,
    log_capacity: Optional[int] = None,
    rebuild_mode: str = "scan",
    policy=None,
    auto_resize: bool = False,
    metrics: Optional[MetricsRegistry] = None,
    input_dim: Optional[int] = None,
    state=None,
    trace: Union[None, bool, int, _obtrace.Tracer] = None,
    probe: Union[bool, dict, None] = None,
    recovery: Union[None, bool, dict, RecoveryPolicy] = None,
    wal: Union[None, str, DurableLog] = None,
    **hp,
) -> Server:
    """The serving facade: one :class:`Server` for any learner family.

    Args:
      learner: ``"klms"`` / ``"nklms"`` / ``"qklms"`` / ``"krls"`` /
        ``"ald"``.
      feature_map: any :mod:`repro.features` family (required for the
        theta families; the dictionary baselines take ``input_dim=``).
      bank: number of bank slots B.
      chunk / mode / adaptive: micro-batch queue knobs (serve/queue.py).
      precision / publish_every / age_watermark / size_watermark / clock:
        snapshot-tier knobs (serve/snapshot.py).
      log_capacity: per-tenant replay-log ring size. Policy mode defaults
        it to 256; without a policy, None disables the lifecycle log (the
        old snapshot-server contract).
      rebuild_mode: replay schedule for readmissions ("scan" / "blocked"
        / "sequential"; dictionary learners always replay sequentially).
      policy: None (tenant == slot), a scorer name ("lru" / "lfu" /
        "cost"), a ``SlotPolicy`` kwargs dict, or a ready instance.
      auto_resize: apply the policy's pow2 ``suggest_size`` after submits.
      metrics: a shared :class:`MetricsRegistry` (fresh one by default).
      state: initial bank state (fresh init by default).
      trace: request tracing — ``True`` for a fresh default
        :class:`~repro.obs.trace.Tracer`, an int for a fresh tracer with
        that ring capacity, or a ready (possibly shared) instance. The
        tracer lands on ``server.tracer`` (export via ``to_chrome_trace``
        / ``to_jsonl``); every public server method activates it, so
        queue / snapshot / kernel-dispatch spans nest under the request.
      probe: in-jit numerics probes — ``True`` fuses the
        :func:`~repro.obs.probes.stats_tap` into the flush program and
        monitors it against :data:`~repro.obs.probes.DEFAULT_THRESHOLDS`;
        a dict overrides thresholds (``{"name": value}`` or
        ``{"name": ("min"|"max", value)}``). Monitor lands on
        ``server.probe``; export via :meth:`Server.observability`.
      recovery: probe-triggered self-healing (serve/recovery.py) —
        ``True`` for a default :class:`~repro.serve.recovery
        .RecoveryPolicy`, a kwargs dict (``max_retries`` /
        ``backoff_base`` / ``backoff_factor`` / ``clock`` /
        ``reference_clock``), or a ready instance. Implies ``probe=True``
        when probes were not requested; the policy lands on
        ``server.recovery``.
      wal: durable write-ahead log — a JSONL path or a ready
        :class:`~repro.serve.recovery.DurableLog`. Every ``submit`` is
        appended before it is queued; ``Server.checkpoint`` +
        ``restore_checkpoint`` replay the post-checkpoint suffix so a
        killed server restores bitwise (README "Robustness").
      **hp: family hyperparameters — ``mu``, ``eps``, ``lam``, ``beta``,
        ``sigma``, ``quant_eps``, ``nu``, ``capacity`` (scalars; the
        per-tenant (B,) sweeps stay on the core tiers).
    """
    _check_learner(learner)
    h = _resolve_hp(hp)
    lrn = build_learner(learner, feature_map, input_dim, **hp)
    queue = make_queue(
        learner, feature_map, bank, chunk=chunk, mode=mode,
        adaptive=adaptive, state=state, input_dim=input_dim, **hp,
    )

    def rebuild_fn(bank_state, slot, xs, ys):
        row = lrn.rebuild(
            jnp.asarray(xs), jnp.asarray(ys), mode=rebuild_mode
        )
        return set_tenant_row(bank_state, slot, row)

    if learner == "krls":
        def evict_fn(bank_state, slot):
            return evict_tenant(bank_state, slot, lam=h["lam"])
    elif learner in ("qklms", "ald"):
        def evict_fn(bank_state, slot):
            fresh = jax.tree.map(
                jnp.zeros_like, tenant_row(bank_state, slot)
            )
            return set_tenant_row(bank_state, slot, fresh)
    else:
        evict_fn = evict_tenant

    rec: Optional[RecoveryPolicy] = None
    if recovery:
        if isinstance(recovery, RecoveryPolicy):
            rec = recovery
        elif isinstance(recovery, dict):
            rec = RecoveryPolicy(**recovery)
        else:
            rec = RecoveryPolicy()
        if not probe:
            probe = True
    if wal is None or isinstance(wal, DurableLog):
        wal_log = wal
    else:
        wal_log = DurableLog(wal)
    pol = _resolve_policy(policy, bank)
    inner = SnapshotServer(
        queue,
        feature_map,
        publish_every,
        mode=mode,
        precision=precision,
        age_watermark=age_watermark,
        size_watermark=size_watermark,
        clock=clock,
        log_capacity=None if pol is not None else log_capacity,
        evict_fn=evict_fn,
        rebuild_fn=rebuild_fn,
    )
    if isinstance(trace, _obtrace.Tracer):
        tracer = trace
    elif isinstance(trace, bool) or trace is None:
        tracer = _obtrace.Tracer() if trace else None
    else:
        tracer = _obtrace.Tracer(capacity=int(trace))
    return Server(
        inner,
        learner=learner,
        lrn=lrn,
        feature_map=feature_map,
        hp=h,
        policy=pol,
        metrics=metrics,
        log_capacity=log_capacity,
        auto_resize=auto_resize,
        tracer=tracer,
        probe=probe,
        recovery=rec,
        wal=wal_log,
    )
