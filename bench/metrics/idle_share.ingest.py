"""Device, saturating ingest: share of the traced window with no program running (%)."""
from bench import readers


def read(obs):
    return readers.idle_share(obs)
