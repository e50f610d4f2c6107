"""Snapshot layer, saturating ingest: mean snapshot.watermark span, the backlog scan and watermark tests each arrival runs (us)."""
from bench import readers


def read(obs):
    return readers.mean_span(obs, "snapshot.watermark", 1e6)
