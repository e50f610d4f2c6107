"""Kernel layer, saturating ingest: the KRLS chunk kernel's share of its roofline, useful work only (%)."""
from bench import readers


def read(obs):
    return readers.krls_chunk_roofline(obs)
