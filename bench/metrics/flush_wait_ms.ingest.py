"""Device wait, saturating ingest: mean queue.wait span per flush, the host blocked on the flush program (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "queue.wait", 1e3)
