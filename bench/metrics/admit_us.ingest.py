"""Policy layer, paged ingest: mean policy.admit, the slot policy's admission on a miss (us)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "policy.admit", 1e6)
