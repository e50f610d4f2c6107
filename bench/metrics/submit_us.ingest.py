"""Facade layer, saturating ingest: mean self time of serve.submit per arrival, less its queue.flush child (us)."""
from bench import readers


def read(obs):
    return readers.submit_self_us(obs)
