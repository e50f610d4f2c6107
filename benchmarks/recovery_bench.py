"""Recovery-latency harness: what does self-healing cost on the write path?

Two questions, one per record kind:

* ``recovery_repair`` — from the faulted flush's start, how long until the
  probe fold *detects* the corruption (``detect_us``: chunk train + in-jit
  tap + threshold fold), and from detection, how long until the ladder's
  repair is verified and published (``repair_us``)? Repairs replay the
  tenant's log, so the grid sweeps ``log_len`` for the rebuild action and
  covers every ladder rung (resymmetrize / rebuild / reset) across the
  learner families. Each config runs the episode twice where the fault
  allows it: the first pass pays the rebuild jit compile
  (``cold_repair_us``), the recorded ``repair_us`` is the warm second
  episode — the steady-state cost a long-running server sees.
* ``ckpt_roundtrip`` — wall cost of durability: ``save_us`` for an atomic
  generation write (serialize + fsync + rename), ``restore_us`` for
  loading it into a fresh identically-configured server, ``bytes`` on
  disk, and ``state_bitwise`` confirming the round-trip loses nothing.

Run as a script to emit ``BENCH_recovery.json``:

    PYTHONPATH=src python benchmarks/recovery_bench.py --out BENCH_recovery.json
    PYTHONPATH=src python benchmarks/recovery_bench.py --tiny   # CI smoke

Without an explicit ``--out``, a ``--tiny`` run writes to /tmp so tiny
grids can never overwrite the committed full-shape baseline.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

_KW = {
    "klms": dict(mu=0.3),
    "nklms": dict(mu=0.3),
    "krls": dict(lam=0.1, beta=0.99),
    "qklms": dict(sigma=1.0, mu=0.3, quant_eps=0.1, capacity=32),
    "ald": dict(sigma=1.0, nu=5e-4, capacity=32),
}

# (learner, fault kind, target-tenant log length). nan_state lands on the
# rebuild rung, log_corrupt forces the reset fallthrough, asym_pmat on an
# RLS bank exercises the cheap resymmetrize rung.
REPAIR_GRID = (
    ("klms", "nan_state", 32),
    ("klms", "nan_state", 128),
    ("klms", "nan_state", 512),
    ("nklms", "nan_state", 128),
    ("krls", "nan_state", 128),
    ("qklms", "nan_state", 128),
    ("ald", "nan_state", 128),
    ("klms", "log_corrupt", 128),
    ("krls", "asym_pmat", 128),
)
TINY_REPAIR_GRID = (
    ("klms", "nan_state", 32),
    ("klms", "log_corrupt", 128),
    ("krls", "asym_pmat", 128),
)

CKPT_GRID = (("klms", 8), ("klms", 32), ("krls", 8))
TINY_CKPT_GRID = (("klms", 8),)

_D, _DFEAT = 8, 64
_TENANT = 1


def _rff():
    import jax

    from repro.core.rff import sample_rff

    return sample_rff(jax.random.PRNGKey(0), _D, _DFEAT, 1.0)


def _feed(srv, rng, counts):
    """Interleaved per-tenant arrival counts, then drain."""
    order = np.concatenate(
        [np.full(n, t) for t, n in counts.items()]
    )
    rng.shuffle(order)
    for t in order:
        srv.submit(
            int(t),
            rng.standard_normal(_D).astype(np.float32),
            float(rng.standard_normal()),
        )
    srv.drain()


def _healthy(srv) -> bool:
    import jax

    if srv.recovery.quarantined:
        return False
    return all(
        bool(np.isfinite(np.asarray(leaf)).all())
        for leaf in jax.tree.leaves(srv.queue.state)
    )


def bench_repair(learner: str, kind: str, log_len: int) -> dict:
    from repro.obs.faults import Fault, FaultInjector, FaultPlan
    from repro.serve import make_server

    srv = make_server(
        learner, feature_map=_rff(), bank=4, chunk=8, policy="lru",
        log_capacity=max(1024, 2 * log_len), recovery=True,
        **_KW[learner],
    )
    rng = np.random.default_rng(0)
    _feed(
        srv, rng,
        {0: log_len // 4, _TENANT: log_len, 2: log_len // 4},
    )

    fired: list[float] = []
    srv.probe.subscribe(lambda ev: fired.append(time.perf_counter()))

    # log_corrupt clears the target's log (reset repair), so only its
    # first episode is representative; the others run twice — episode 1
    # pays the per-log-length rebuild compile, episode 2 is steady state.
    episodes = 1 if kind == "log_corrupt" else 2
    timings = []
    for _ in range(episodes):
        fired.clear()
        inj = FaultInjector(
            srv, FaultPlan([Fault(kind, tenant=_TENANT, at_flush=0)])
        ).attach()
        # Non-target arrivals drive the faulted flush so the corruption
        # survives to the tap (trained rows get overwritten).
        for t in (0, 2, 0, 2, 0, 2, 0, 2):
            srv.submit(
                t,
                rng.standard_normal(_D).astype(np.float32),
                float(rng.standard_normal()),
            )
        t0 = time.perf_counter()
        srv.flush()
        t1 = time.perf_counter()
        srv.drain()
        inj.detach()
        assert fired, f"{learner}/{kind}: fault was never detected"
        timings.append((fired[0] - t0, t1 - fired[0]))

    detect_us, repair_us = (v * 1e6 for v in timings[-1])
    return {
        "bench": "recovery_repair",
        "learner": learner,
        "fault": kind,
        "action": srv.recovery.history[-1]["action"],
        "log_len": log_len,
        "detect_us": round(detect_us, 1),
        "repair_us": round(repair_us, 1),
        "cold_repair_us": round(timings[0][1] * 1e6, 1),
        "end_healthy": _healthy(srv),
    }


def bench_ckpt(learner: str, slots: int) -> dict:
    import jax

    from repro.serve import make_server
    from repro.serve.recovery import restore_checkpoint

    args = dict(
        feature_map=_rff(), bank=slots, chunk=8, policy="lru",
        log_capacity=256, **_KW[learner],
    )
    srv = make_server(learner, **args)
    rng = np.random.default_rng(1)
    _feed(srv, rng, {t: 16 for t in range(slots)})

    saves, restores, nbytes, bitwise = [], [], 0, True
    for _ in range(3):
        with tempfile.TemporaryDirectory() as tmp:
            t0 = time.perf_counter()
            srv.checkpoint(tmp)
            saves.append(time.perf_counter() - t0)
            nbytes = max(
                os.path.getsize(p)
                for p in glob.glob(os.path.join(tmp, "gen_*.ckpt"))
            )
            fresh = make_server(learner, **args)
            t0 = time.perf_counter()
            restore_checkpoint(fresh, tmp)
            restores.append(time.perf_counter() - t0)
            for a, b in zip(
                jax.tree.leaves(srv.queue.state),
                jax.tree.leaves(fresh.queue.state),
            ):
                bitwise &= bool(
                    np.array_equal(
                        np.asarray(a), np.asarray(b), equal_nan=True
                    )
                )
    return {
        "bench": "ckpt_roundtrip",
        "learner": learner,
        "slots": slots,
        "dfeat": _DFEAT,
        "save_us": round(min(saves) * 1e6, 1),
        "restore_us": round(min(restores) * 1e6, 1),
        "bytes": nbytes,
        "state_bitwise": bitwise,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default=None)
    parser.add_argument("--tiny", action="store_true",
                        help="CI smoke shapes (never the committed baseline)")
    args = parser.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    repair_grid = TINY_REPAIR_GRID if args.tiny else REPAIR_GRID
    ckpt_grid = TINY_CKPT_GRID if args.tiny else CKPT_GRID

    records = []
    for learner, kind, log_len in repair_grid:
        rec = bench_repair(learner, kind, log_len)
        records.append(rec)
        print(
            f"{learner:>5} {kind:<11} log={log_len:<4} "
            f"-> {rec['action']:<12} detect={rec['detect_us']}us "
            f"repair={rec['repair_us']}us (cold {rec['cold_repair_us']}us)",
            flush=True,
        )
    for learner, slots in ckpt_grid:
        rec = bench_ckpt(learner, slots)
        records.append(rec)
        print(
            f"{learner:>5} ckpt slots={slots:<3} save={rec['save_us']}us "
            f"restore={rec['restore_us']}us bytes={rec['bytes']} "
            f"bitwise={rec['state_bitwise']}",
            flush=True,
        )

    payload = {
        "suite": "recovery",
        "tiny": args.tiny,
        "backend": jax.default_backend(),
        "config": {"d": _D, "dfeat": _DFEAT, "chunk": 8},
        "caveats": [
            "repair_us is the warm (second) episode; cold_repair_us keeps"
            " the one-time per-log-length rebuild compile visible",
            "detect_us includes the faulted flush's chunk train — detection"
            " rides the write path, it is not a separate scan",
        ],
        "records": records,
    }
    out = args.out or (
        "/tmp/BENCH_recovery.json" if args.tiny else "BENCH_recovery.json"
    )
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out} ({len(records)} records)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
