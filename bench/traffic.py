"""One general traffic generator; each mix is a data file of parameters.

A mix (``bench/traffic/<name>.json``) gives:

* ``arrivals``: ``"saturate"`` (a closed loop: the next request goes out as
  soon as the call returns) or ``"poisson"`` (an open loop at ``rate``
  requests per second, each request timed from when it was due);
* ``profile`` (poisson, optional): ``{"period_s": p, "pieces": [[share,
  factor], ...]}``, the rate over each period as pieces of it at ``factor``
  times ``rate`` (on/off bursts, ramps, a daily cycle);
* ``read_share``: the share of requests that are reads (one ``x`` each);
* ``keys``: ``{"dist": "round_robin"}`` or ``{"dist": "zipf", "theta":
  a}`` (YCSB's Zipfian: pmf proportional to 1/rank^a over the tenants,
  ranks mapped to tenant ids by a seeded permutation); a zipf may add ``"shift": {"every": n, "by": k}``, which
  moves the hot set by ``k`` ranks every ``n`` requests;
* ``drift`` (optional): ``{"every": n}``, every tenant's hidden function
  drawn anew every ``n`` requests (a concept drift);
* ``warmup``: requests sent, closed loop, before the window (set-up);
* ``block`` (saturate, optional): requests generated at a time;
* ``size_watermark`` / ``age_watermark``: the server's flush triggers.

A closed loop's requests are generated a block at a time, as the run
reaches them: set-up makes the warm-up and the first block only. An open
loop makes the whole window's requests, whose arrival times it needs.
Every seed gets the same numbers of requests, reads and writes in each
block; the seed draws the keys, the order, the arrival times and the data,
and a block's requests do not depend on when it was made.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Optional

import numpy as np

from bench import streams

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
BLOCK = 16384  # closed-loop requests generated at a time


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    with open(directory / f"{name}.json") as f:
        return json.load(f)


def zipf_cdf(tenants: int, theta: float) -> np.ndarray:
    probs = np.arange(1, tenants + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(probs)
    return cdf / cdf[-1]


def zipf_ranks(rng, tenants: int, theta: float, n: int,
               cdf: Optional[np.ndarray] = None) -> np.ndarray:
    """n ranks in [0, tenants) with pmf proportional to 1/(rank+1)^theta."""
    cdf = zipf_cdf(tenants, theta) if cdf is None else cdf
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      tenants - 1)


def intensity(seconds: float, profile: Optional[dict]):
    """Breakpoints ``(t, cumulative rate factor)`` of the window's rate
    profile: the area under ``factor(t)`` from 0 to each ``t``."""
    if not profile:
        return np.array([0.0, seconds]), np.array([0.0, seconds])
    period = profile["period_s"]
    shares = np.array([s for s, _ in profile["pieces"]], np.float64)
    factors = np.array([f for _, f in profile["pieces"]], np.float64)
    if not np.isclose(shares.sum(), 1.0) or np.any(factors < 0):
        raise ValueError("profile pieces: shares sum to 1, factors >= 0")
    ts, cum = [0.0], [0.0]
    start = 0.0
    while start < seconds:
        for share, factor in zip(shares, factors):
            end = min(start + share * period, seconds)
            cum.append(cum[-1] + factor * (end - ts[-1]))
            ts.append(end)
            start = end
            if end >= seconds:
                break
    return np.array(ts), np.array(cum)


def window_requests(mix: dict, seconds: float, rate: Optional[float] = None) -> int:
    """Requests in an open loop's window: the rate's integral over it."""
    area = intensity(seconds, mix.get("profile"))[1][-1]
    return max(1, math.ceil((rate or mix["rate"]) * area))


def arrival_times(rng, n: int, seconds: float,
                  profile: Optional[dict]) -> np.ndarray:
    """A Poisson process conditioned on its count ``n``: its times are
    order statistics of the rate profile's density over the window."""
    ts, cum = intensity(seconds, profile)
    gaps = rng.exponential(size=n + 1)
    u = cum[-1] * np.cumsum(gaps)[:n] / gaps.sum()
    return np.interp(u, cum, ts)


class Schedule:
    """The seeded requests of one run, made a block at a time.

    ``key_l``, ``y_l``, ``read_l``, ``x`` (rows) and, for an open loop,
    ``due_l`` are lists that :meth:`extend` grows in place; the first
    ``warmup`` requests are set-up, and ``due`` is None for a closed loop.
    """

    def __init__(self, mix: dict, cfg: dict, seed: int, seconds: float,
                 rate: Optional[float] = None):
        self.mix, self.cfg, self.seed = mix, cfg, seed
        self.tenants, self.d = cfg["tenants"], cfg["input_dim"]
        self.warmup = mix["warmup"]
        self.open_loop = mix["arrivals"] == "poisson"
        if mix["arrivals"] not in ("poisson", "saturate"):
            raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
        run = self._rng(0)
        keys = mix["keys"]
        if keys["dist"] not in ("round_robin", "zipf"):
            raise ValueError(f"unknown key distribution {keys['dist']!r}")
        self._start = int(run.integers(self.tenants))
        self._perm = run.permutation(self.tenants)
        self._cdf = (zipf_cdf(self.tenants, keys["theta"])
                     if keys["dist"] == "zipf" else None)
        self._params = {0: streams.tenant_params(
            cfg["stream"], self.tenants, self.d, run)}
        self.key_l, self.y_l, self.read_l, self.x = [], [], [], []
        self._blocks: list = []  # (is_read, key, x, y) numpy, in order
        self.due = None
        first = self.warmup
        if self.open_loop:
            k = window_requests(mix, seconds, rate)
            first += k
            self.due = np.concatenate([
                np.zeros(self.warmup),
                arrival_times(self._rng(1, 0), k, seconds, mix.get("profile")),
            ])
            self.due_l = self.due.tolist()
        else:
            first += mix.get("block", BLOCK)
        self._make(first)

    def __len__(self) -> int:
        return len(self.key_l)

    def _rng(self, *key: int):
        return np.random.default_rng(
            np.random.SeedSequence(self.seed, spawn_key=key))

    def extend(self) -> bool:
        """Make the next block; False when the schedule is whole (an open
        loop's window)."""
        if self.open_loop:
            return False
        self._make(self.mix.get("block", BLOCK))
        return True

    def _params_of(self, segment: int) -> dict:
        if segment not in self._params:
            self._params[segment] = streams.tenant_params(
                self.cfg["stream"], self.tenants, self.d, self._rng(3, segment))
        return self._params[segment]

    def _make(self, n: int) -> None:
        mix, lo = self.mix, len(self)
        r_keys, r_mix, r_x, r_noise = (
            self._rng(2, len(self._blocks), part) for part in range(4))
        idx = lo + np.arange(n)
        keys = mix["keys"]
        if keys["dist"] == "round_robin":
            key = (self._start + idx) % self.tenants
        else:
            rank = zipf_ranks(r_keys, self.tenants, keys["theta"], n, self._cdf)
            shift = keys.get("shift")
            if shift:
                rank = (rank + shift["by"] * (idx // shift["every"])) % self.tenants
            key = self._perm[rank]

        is_read = np.zeros(n, bool)
        is_read[: round(mix["read_share"] * n)] = True
        r_mix.shuffle(is_read)

        x = r_x.standard_normal((n, self.d))
        y = np.zeros(n)
        w = ~is_read
        every = mix.get("drift", {}).get("every")
        segment = idx // every if every else np.zeros(n, np.int64)
        noise = r_noise.standard_normal(n)
        for s in np.unique(segment[w]):
            sel = w & (segment == s)
            y[sel] = streams.targets(self.cfg["stream"], self._params_of(int(s)),
                                     key[sel], x[sel], noise[sel])
        block = (is_read, key.astype(np.int64), x.astype(np.float32),
                 y.astype(np.float32))
        self._blocks.append(block)
        self.read_l.extend(block[0].tolist())
        self.key_l.extend(block[1].tolist())
        self.x.extend(block[2])
        self.y_l.extend(block[3].tolist())

    def arrays(self, n: Optional[int] = None):
        """``(is_read, key, x, y)`` of the first ``n`` requests, as arrays."""
        n = len(self) if n is None else n
        return tuple(np.concatenate([b[j] for b in self._blocks])[:n]
                     for j in range(4))


def build(mix: dict, cfg: dict, seed: int, seconds: float,
          rate: Optional[float] = None) -> Schedule:
    """The seeded schedule of one run: warm-up requests, then the window's
    (a closed loop's first block of them)."""
    return Schedule(mix, cfg, seed, seconds, rate)
