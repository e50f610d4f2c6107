"""Queue layer, saturating ingest: mean queue.batch span per flush, the host batch build (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "queue.batch", 1e3)
