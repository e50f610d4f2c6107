"""Queue layer, saturating ingest: mean queue.launch span per flush, dispatch and host-to-device copy of the batch (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "queue.launch", 1e3)
