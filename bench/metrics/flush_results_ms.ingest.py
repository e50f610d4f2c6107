"""Queue layer, saturating ingest: mean queue.results span per flush, counters and per-tenant results to Python (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "queue.results", 1e3)
