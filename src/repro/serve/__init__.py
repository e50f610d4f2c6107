from repro.serve.serve_loop import generate, prefill_tokens
from repro.serve.api import (
    LEARNER_FAMILIES,
    Server,
    make_chunk_step,
    make_queue,
    make_server,
    make_tick,
    reset_slots,
    run_stream,
)
from repro.serve.metrics import Counter, Histogram, MetricsRegistry
from repro.serve.policy import SCORERS, AdmitDecision, SlotPolicy
from repro.serve.queue import MicroBatchQueue
from repro.serve.recovery import (
    DurableLog,
    RecoveryPolicy,
    restore_checkpoint,
    save_checkpoint,
)
from repro.serve.snapshot import ReplayLog, SnapshotServer, StateSnapshot

# Deprecated pre-facade entry points (DeprecationWarning shims; see
# repro/serve/api.py and the README migration table).
from repro.serve.bank_loop import (
    make_bank_server,
    make_krls_bank_server,
    reset_krls_tenants,
    reset_tenants,
    serve_bank_stream,
    serve_krls_bank_stream,
)
from repro.serve.queue import (
    klms_micro_batch_queue,
    krls_micro_batch_queue,
    make_chunked_bank_server,
    make_chunked_krls_bank_server,
)

__all__ = [
    "generate",
    "prefill_tokens",
    # the facade
    "LEARNER_FAMILIES",
    "Server",
    "make_server",
    "make_tick",
    "make_chunk_step",
    "make_queue",
    "run_stream",
    "reset_slots",
    # policy + metrics tiers
    "SlotPolicy",
    "AdmitDecision",
    "SCORERS",
    "MetricsRegistry",
    "Counter",
    "Histogram",
    # serving building blocks
    "MicroBatchQueue",
    "SnapshotServer",
    "StateSnapshot",
    "ReplayLog",
    # self-healing + durability tier
    "RecoveryPolicy",
    "DurableLog",
    "save_checkpoint",
    "restore_checkpoint",
    # deprecated shims
    "make_bank_server",
    "serve_bank_stream",
    "reset_tenants",
    "make_krls_bank_server",
    "serve_krls_bank_stream",
    "reset_krls_tenants",
    "make_chunked_bank_server",
    "make_chunked_krls_bank_server",
    "klms_micro_batch_queue",
    "krls_micro_batch_queue",
]
