"""Replay layer, paged ingest: mean serve.install, rebuilding a readmitted tenant from its log (ms)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "serve.install", 1e3)
