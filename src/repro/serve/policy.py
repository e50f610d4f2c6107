"""Slot-lifecycle policy: the bank as a cache of hot tenants.

PR 6 shipped the eviction *mechanism* — O(1) row park plus replay-log
rebuild — but at tenants ≫ slots the scarce resource is the bank itself,
and something must decide **who lives in a slot**. This module is that
policy tier. It is deliberately pure host-side bookkeeping (no jax): the
facade (serve/api.py) asks it questions — "which slot serves tenant 17?",
"who do I evict to admit tenant 40961?" — and performs the actual state
movement through ``core.bank``'s ``tenant_row``/``set_tenant_row``/
``evict_tenant``/``rebuild_tenant`` primitives. Keeping the policy free of
array code makes eviction order unit-testable and bitwise-irrelevant: the
policy can never corrupt a resident row, only choose one.

Three pluggable eviction scores (LOWER = colder = evicted first):

* ``lru``  — score is the logical clock of the tenant's last touch.
* ``lfu``  — score is the lifetime touch count (kept across evictions, so
  a returning heavy hitter outranks a one-hit wonder immediately).
* ``cost`` — score = recency x rebuild-cost. Recency decays as
  ``1 / (1 + clock - last_touch)``; the rebuild cost comes from a
  caller-supplied ``cost_fn`` estimating what re-admitting this tenant
  would pay (the facade derives it from replay-log length and learner
  family — a KRLS rebuild pays a ``(D, D)`` solve per replay plus O(D^2)
  per tick, KLMS a cheap O(D) affine scan), so the policy preferentially
  keeps tenants that are expensive to bring back.

Admission control: when the bank is full, a new tenant is admitted only if
the coldest incumbent scores strictly *below* the candidate (the incumbent
floor). Ties keep the incumbent. Under LRU the floor always passes (a
fresh touch outranks any past touch — classic always-admit LRU); under
``lfu``/``cost`` a burst of one-off tail tenants stops flushing the hot
set, which is exactly the Zipf-tail scenario ``benchmarks/zipf_bench.py``
measures.

Capacity management: ``suggest_size()`` proposes pow2 grow/shrink targets
from occupancy and recent admission rejects; the facade applies them by
migrating live rows (compaction) through the bank primitives.

A server without a policy asks the same questions of an
:class:`IdentityRouter` (tenant ``t`` is slot ``t``), so the facade runs
one tenant lifecycle either way.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

__all__ = ["AdmitDecision", "IdentityRouter", "SlotPolicy", "SCORERS"]


class AdmitDecision(NamedTuple):
    """Outcome of one admission request.

    ``action`` is one of ``"hit"`` (already resident), ``"admit"`` (placed
    in a free slot), ``"evict"`` (placed in ``slot`` after evicting
    ``victim``), or ``"reject"`` (bank full and no incumbent scored below
    the candidate — the arrival should be logged, not trained).
    """

    action: str
    slot: Optional[int] = None
    victim: Optional[int] = None


def _lru_score(policy: "SlotPolicy", tenant: int) -> float:
    return float(policy.last_touch.get(tenant, 0))


def _lfu_score(policy: "SlotPolicy", tenant: int) -> float:
    return float(policy.touches.get(tenant, 0))


def _cost_score(policy: "SlotPolicy", tenant: int) -> float:
    recency = 1.0 / (1.0 + policy.clock - policy.last_touch.get(tenant, 0))
    cost = policy.cost_fn(tenant) if policy.cost_fn is not None else 1.0
    return recency * cost


SCORERS: dict[str, Callable[["SlotPolicy", int], float]] = {
    "lru": _lru_score,
    "lfu": _lfu_score,
    "cost": _cost_score,
}


class SlotPolicy:
    """Decide which tenants occupy the bank's ``slots`` slots.

    Args:
      slots: number of bank slots currently under management.
      scorer: ``"lru"`` / ``"lfu"`` / ``"cost"`` or a callable
        ``(policy, tenant) -> float`` (lower = evicted first).
      cost_fn: ``tenant -> float`` rebuild-cost estimate consumed by the
        ``cost`` scorer (the facade wires replay-log length x family
        cost). Ignored by the other scorers.
      min_slots / max_slots: pow2 bounds for ``suggest_size``.
      grow_rejects: admission rejects since the last resize that trigger a
        grow suggestion.
      shrink_occupancy: occupancy fraction at or below which a shrink (one
        pow2 step) is suggested.

    Determinism contract: victim selection orders incumbents by
    ``(score, last_touch, tenant)`` — ties on score fall to the
    least-recently-touched, then the smallest tenant id — and free slots
    are handed out lowest-index first, so identical request streams
    produce identical placements (unit-tested).
    """

    def __init__(
        self,
        slots: int,
        scorer: Union[str, Callable] = "lru",
        *,
        cost_fn: Optional[Callable[[int], float]] = None,
        min_slots: int = 1,
        max_slots: int = 1 << 20,
        grow_rejects: int = 8,
        shrink_occupancy: float = 0.25,
    ):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if isinstance(scorer, str):
            if scorer not in SCORERS:
                raise ValueError(
                    f"unknown scorer {scorer!r}; pick from {sorted(SCORERS)}"
                )
            self.scorer_name = scorer
            self._scorer = SCORERS[scorer]
        else:
            self.scorer_name = getattr(scorer, "__name__", "custom")
            self._scorer = scorer
        self.slots = slots
        self.cost_fn = cost_fn
        self.min_slots = min_slots
        self.max_slots = max_slots
        self.grow_rejects = grow_rejects
        self.shrink_occupancy = shrink_occupancy
        self.clock = 0
        self.last_touch: dict[int, int] = {}
        self.touches: dict[int, int] = {}
        self._resident: dict[int, int] = {}
        self._free: list[int] = list(range(slots - 1, -1, -1))  # pop() -> 0
        self.rejects_since_resize = 0

    # -- observation --------------------------------------------------------

    def touch(self, tenant: int) -> None:
        """Record one request for ``tenant`` (advances the logical clock)."""
        self.clock += 1
        self.last_touch[tenant] = self.clock
        self.touches[tenant] = self.touches.get(tenant, 0) + 1

    def lookup(self, tenant: int) -> Optional[int]:
        """The slot serving ``tenant``, or None when not resident."""
        return self._resident.get(tenant)

    def route(self, tenant: int) -> Optional[int]:
        """Touch ``tenant`` for one request and return its slot (None when
        not resident): the server's per-request lookup."""
        self.touch(tenant)
        return self._resident.get(tenant)

    # No tenant is barred from the bank: a non-resident one is admitted on
    # its next write, floor permitting.
    evicted: frozenset = frozenset()

    @property
    def resident(self) -> dict[int, int]:
        """Snapshot of the tenant -> slot map."""
        return dict(self._resident)

    @property
    def occupancy(self) -> int:
        return len(self._resident)

    def score(self, tenant: int) -> float:
        """Eviction score (lower = colder = evicted first)."""
        return self._scorer(self, tenant)

    def _key(self, tenant: int):
        return (self.score(tenant), self.last_touch.get(tenant, 0), tenant)

    def victim(self) -> Optional[int]:
        """The incumbent the policy would evict next (None if bank empty)."""
        if not self._resident:
            return None
        return min(self._resident, key=self._key)

    # -- placement ----------------------------------------------------------

    def admit(self, tenant: int, force: bool = False) -> AdmitDecision:
        """Place ``tenant`` in a slot, evicting or rejecting as scored.

        Mutates the resident map according to the returned decision — the
        caller performs the matching bank-state work (park the victim's
        slot, rebuild the admitted tenant from its log). ``force=True``
        bypasses the admission floor (operator-initiated readmit).
        """
        slot = self._resident.get(tenant)
        if slot is not None:
            return AdmitDecision("hit", slot=slot)
        if self._free:
            slot = self._free.pop()
            self._resident[tenant] = slot
            return AdmitDecision("admit", slot=slot)
        victim = self.victim()
        # The incumbent floor: the coldest incumbent must score strictly
        # below the candidate; ties keep the incumbent.
        if not force and self.score(victim) >= self.score(tenant):
            self.rejects_since_resize += 1
            return AdmitDecision("reject")
        slot = self._resident.pop(victim)
        self._resident[tenant] = slot
        return AdmitDecision("evict", slot=slot, victim=victim)

    def release(self, tenant: int) -> Optional[int]:
        """Voluntarily evict ``tenant``; returns the freed slot (or None)."""
        slot = self._resident.pop(tenant, None)
        if slot is not None:
            self._free.append(slot)
            self._free.sort(reverse=True)  # keep lowest-index-first handout
        return slot

    def move(self, tenant: int, new_slot: int) -> None:
        """Re-pin a resident tenant to another slot (compaction move)."""
        if tenant not in self._resident:
            raise KeyError(f"tenant {tenant} is not resident")
        self._resident[tenant] = new_slot

    def reset(self, slots: int) -> None:
        """Forget every tenant (clock, touch history, residency) and manage
        ``slots`` free slots."""
        self.clock = 0
        self.last_touch.clear()
        self.touches.clear()
        self._resident.clear()
        self.set_slots(slots)

    # -- durability ---------------------------------------------------------

    def state_dict(self) -> dict:
        """Plain-dict export of the policy's mutable state (checkpointing).

        Covers everything admission decisions depend on — logical clock,
        touch history, residency, free list, reject pressure — so a
        restored policy makes the same decisions the live one would have.
        ``cost_fn`` is a live callable and is NOT serialized; the facade
        re-wires it at restore.
        """
        return {
            "slots": self.slots,
            "scorer": self.scorer_name,
            "clock": self.clock,
            "last_touch": dict(self.last_touch),
            "touches": dict(self.touches),
            "resident": dict(self._resident),
            "free": list(self._free),
            "rejects_since_resize": self.rejects_since_resize,
        }

    def load_state(self, d: dict) -> None:
        """Restore the mutable state exported by :meth:`state_dict`.

        The receiving policy must already be built with the same scorer
        and structural knobs; slot count is adopted from the snapshot.
        """
        if d["scorer"] != self.scorer_name:
            raise ValueError(
                f"checkpoint scorer {d['scorer']!r} != policy scorer "
                f"{self.scorer_name!r}"
            )
        self.slots = int(d["slots"])
        self.clock = int(d["clock"])
        self.last_touch = {int(k): int(v) for k, v in d["last_touch"].items()}
        self.touches = {int(k): int(v) for k, v in d["touches"].items()}
        self._resident = {int(k): int(v) for k, v in d["resident"].items()}
        self._free = [int(s) for s in d["free"]]
        self.rejects_since_resize = int(d["rejects_since_resize"])

    # -- capacity -----------------------------------------------------------

    def suggest_size(self) -> int:
        """Pow2 slot-count suggestion from occupancy and reject pressure.

        Grow one step when the bank is full and ``grow_rejects`` arrivals
        were rejected since the last resize; shrink one step when
        occupancy is at or below ``shrink_occupancy``. Otherwise the
        current size. The caller decides whether to apply it (and resets
        the reject counter via :meth:`set_slots`).
        """
        if (
            not self._free
            and self.rejects_since_resize >= self.grow_rejects
            and self.slots * 2 <= self.max_slots
        ):
            return self.slots * 2
        if (
            self.slots > self.min_slots
            and self.occupancy <= self.shrink_occupancy * self.slots
        ):
            return max(self.min_slots, self.slots // 2)
        return self.slots

    def set_slots(self, slots: int) -> None:
        """Adopt a new slot count after the caller migrated the bank.

        Every resident slot index must already be < ``slots`` (the facade
        compacts rows first); the free list is rebuilt from the gap.
        """
        used = set(self._resident.values())
        if any(s >= slots for s in used):
            raise ValueError(
                f"resident slots {sorted(used)} do not fit in {slots}"
            )
        self.slots = slots
        self._free = sorted((s for s in range(slots) if s not in used),
                            reverse=True)
        self.rejects_since_resize = 0


class IdentityRouter:
    """The router of a server without a slot policy: tenant ``t`` is slot
    ``t``, and ``slots`` bounds the ids.

    ``release`` (``Server.evict``) puts a tenant *out*: its ``lookup``
    misses and ``admit`` rejects it, so its writes are logged but not
    trained, until a forced admission (``Server.readmit``) gives it its
    own slot back. ``touch`` is a no-op: there is no clock to advance."""

    def __init__(self, slots: int):
        self.slots = slots
        self.out: set[int] = set()

    def lookup(self, tenant: int) -> Optional[int]:
        return None if tenant in self.out else tenant

    route = lookup  # no clock to touch

    def touch(self, tenant: int) -> None:
        pass

    def admit(self, tenant: int, force: bool = False) -> AdmitDecision:
        if tenant not in self.out:
            return AdmitDecision("hit", slot=tenant)
        if not force:
            return AdmitDecision("reject")
        self.out.discard(tenant)
        return AdmitDecision("admit", slot=tenant)

    def release(self, tenant: int) -> Optional[int]:
        if tenant in self.out:
            return None
        self.out.add(tenant)
        return tenant

    @property
    def evicted(self) -> frozenset:
        return frozenset(self.out)

    @property
    def resident(self) -> dict[int, int]:
        return {t: t for t in range(self.slots) if t not in self.out}

    def reset(self, slots: int) -> None:
        self.slots = slots
        self.out.clear()

    def state_dict(self) -> dict:
        return {"evicted": sorted(self.out)}

    def load_state(self, d: dict) -> None:
        self.out = {int(t) for t in d["evicted"]}
