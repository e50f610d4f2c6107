"""Bank row write layer, paged ingest: mean serve.evict, releasing the victim's slot on a miss (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "serve.evict", 1e3)
