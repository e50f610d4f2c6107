"""Queue layer, saturating ingest: mean queue.flush span per flush (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "queue.flush", 1e3)
