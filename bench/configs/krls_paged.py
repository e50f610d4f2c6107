"""Plain reference of ``krls_paged.json``: the float64 RFF-KRLS recursion
of ``krls_fleet.py`` per tenant, over the tenant's whole history.

A paged tenant may be evicted and rebuilt from its replay log any number
of times; the contract is that its state is the one of every write it
ever had, in order. That holds only while the log holds the whole
history, so a checked tenant with more writes than ``log_capacity`` is a
premise this reference does not cover: it raises.
"""
from __future__ import annotations

import numpy as np

from bench.configs.krls_fleet import replay as _replay
from bench.configs.krls_fleet import tenants

__all__ = ["replay", "tenants"]


def replay(run, ids, precision="f64") -> dict:
    counts = np.bincount(run.write_key, minlength=run.cfg["tenants"])[ids]
    cap = run.cfg["hp"]["log_capacity"]
    if counts.max(initial=0) > cap:
        raise ValueError(
            f"a checked tenant has {int(counts.max())} writes, more than the "
            f"log's {cap}: its rebuild would be windowed, not whole-history"
        )
    return _replay(run, ids, precision)
