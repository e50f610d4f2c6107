"""Public jit'd wrappers for the Pallas kernels, with backend dispatch.

On TPU the compiled Pallas kernels run natively; on CPU the default is the
pure-XLA reference path, and ``mode="interpret"`` runs the Pallas kernels in
the interpreter for kernel-correctness tests. ``mode="pallas"`` demands the
compiled kernels and raises off a TPU rather than quietly interpreting. The
API is stable across backends so the model code never branches.

Dispatch observability: the serving-path ops are host wrappers around
their jitted cores. Each call reports to ``repro.obs.telemetry`` (live
launch / remainder-launch counters and the bytes-moved gauge from the
benches' closed-form models) and opens one ``kernel.<op>`` span on the
active tracer (``repro.obs.trace``) carrying shape / dtype / mode /
chunk attributes. Calls reached under an enclosing ``jax.jit`` trace
execute at *trace* time, so they are tagged ``traced=True`` and counted
under ``kernel.traces`` instead of live launches (the compiled program's
executions are counted by the tier that invokes it, e.g. the micro-batch
queue's per-flush dispatch record). With no active tracer the span is a
reusable null context — the untraced path costs a few dict operations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import telemetry as _telemetry
from repro.obs import trace as _trace
from repro.kernels import ref
from repro.kernels.chunking import (
    default_chunk_t,
    default_decode_block_t,
    time_blocks,
    unblock_time,
    valid_time_mask,
)
from repro.kernels.rff_features import rff_features_pallas
from repro.kernels.rff_predict import rff_bank_predict_pallas
from repro.kernels.rff_attention import (
    rff_attention_decode_block_pallas,
    rff_attention_pallas,
)
from repro.kernels.rff_klms_step import (
    rff_klms_bank_chunk_pallas,
    rff_klms_bank_step_pallas,
)
from repro.kernels.rff_krls_step import (
    rff_krls_bank_chunk_pallas,
    rff_krls_bank_step_pallas,
)
from repro.kernels.rff_scan import (
    rff_klms_chunk_elements_pallas,
    rff_krls_chunk_elements_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas

__all__ = [
    "default_backend",
    "rff_features",
    "rff_bank_predict",
    "rff_klms_bank_step",
    "rff_klms_bank_chunk",
    "rff_krls_bank_step",
    "rff_krls_bank_chunk",
    "rff_klms_chunk_elements",
    "rff_krls_chunk_elements",
    "rff_attention",
    "rff_attention_decode",
    "rff_attention_decode_block",
    "flash_attention",
]


def default_backend() -> str:
    return jax.default_backend()


def _use_pallas(mode: str) -> tuple[bool, bool]:
    """Resolve mode -> (use_pallas, interpret).

    ``fused`` / ``twopass`` are aliases for ``pallas`` / ``xla``: the fused
    single-program path vs the two-pass reference (feature map and update as
    separate passes with an HBM round-trip between them). ``pallas`` off a
    TPU is an error: a run that asked for the compiled kernels must not
    measure the interpreter instead.
    """
    if mode == "auto":
        on_tpu = default_backend() == "tpu"
        return on_tpu, False
    if mode in ("pallas", "fused"):
        if default_backend() != "tpu":
            raise RuntimeError(
                f"mode={mode!r} needs a TPU, but the JAX backend is "
                f"{default_backend()!r}; use mode='interpret' to run the "
                "Pallas kernels in the interpreter"
            )
        return True, False
    if mode == "interpret":
        return True, True
    if mode in ("xla", "twopass"):
        return False, False
    raise ValueError(f"unknown kernel mode {mode!r}")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _dispatch(
    op: str,
    lead,
    *,
    launches: int = 1,
    remainder: int = 0,
    bytes_moved: float | None = None,
    **attrs,
):
    """Record one dispatch-layer call for ``op`` and open its span.

    ``lead`` is the op's leading array argument: a ``jax.core.Tracer``
    there means this call site was reached under an enclosing jit trace
    (it compiles launches, it doesn't execute them), so it is tagged
    ``traced`` for both the telemetry counters and the span. Returns the
    ``kernel.<op>`` span context (the shared null context when no tracer
    is active).
    """
    traced = isinstance(lead, jax.core.Tracer)
    _telemetry.record_dispatch(
        op,
        launches=launches,
        remainder=remainder,
        bytes_moved=bytes_moved,
        traced=traced,
    )
    return _trace.span(
        f"kernel.{op}", traced=traced, launches=launches, **attrs
    )


@functools.partial(
    jax.jit,
    static_argnames=("mode", "block_m", "block_n", "block_k", "precision"),
)
def rff_features(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    precision: str | None = None,
) -> jax.Array:
    """Affine-trig feature map ``s * cos(x @ w + b)`` over arbitrary leading
    dims. ``s`` optional ``(D,)`` per-feature scales (the canonical form of
    every trig family in repro.features); None = Monte-Carlo ``sqrt(2/D)``.
    ``precision=None/"f32"`` is the bitwise-legacy path; ``"bf16"`` runs the
    GEMM in bf16 with f32 accumulation and emits bf16 features (the
    read-path contract documented in kernels/ref.py).
    """
    use_pallas, interpret = _use_pallas(mode)
    if not use_pallas:
        return ref.rff_features_ref(x, w, b, s, precision)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = rff_features_pallas(
        x2, w, b, s,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret, precision=precision,
    )
    return out.reshape(*lead, w.shape[-1])


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "block_q", "precision")
)
def _rff_bank_predict_jit(
    theta, xq, w, b, s=None, *, mode, block_b, block_q, precision
):
    use_pallas, interpret = _use_pallas(mode)
    if not use_pallas:
        return ref.rff_bank_predict_ref(theta, xq, w, b, s, precision)
    return rff_bank_predict_pallas(
        theta, xq, w, b, s,
        block_b=block_b, block_q=block_q, precision=precision,
        interpret=interpret,
    )


def rff_bank_predict(
    theta: jax.Array,
    xq: jax.Array,
    w: jax.Array,
    b: jax.Array,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    block_b: int = 8,
    block_q: int = 64,
    precision: str | None = None,
) -> jax.Array:
    """Fused predict-only read path: a ``(B, Q, d)`` query block per tenant
    against read-only ``theta (B, D)`` in one launch -> ``(B, Q)``.

    This is `core.bank.bank_predict` (one vmapped featurize+matvec per
    query) batched into one kernel: theta and W are fetched once per launch
    instead of once per query, and ``precision="bf16"`` drops the featurize
    GEMM to bf16 with f32 accumulation (contract in kernels/ref.py; state
    is read-only and stays f32). The serving read path of serve/snapshot.py
    and benchmarks/serve_bench.py.
    """
    bank, q, d = xq.shape
    bm = _telemetry.predict_read_bytes(bank, d, w.shape[-1], q)
    with _dispatch(
        "bank_predict", theta,
        bytes_moved=bm["fused_bytes"],
        shape=[bank, q, d], dfeat=w.shape[-1], dtype=str(theta.dtype),
        mode=mode, precision=precision,
    ):
        return _rff_bank_predict_jit(
            theta, xq, w, b, s,
            mode=mode, block_b=block_b, block_q=block_q, precision=precision,
        )


@functools.partial(jax.jit, static_argnames=("mode", "block_b"))
def _rff_klms_bank_step_jit(theta, x, y, w, b, mu, s=None, *, mode, block_b):
    use_pallas, interpret = _use_pallas(mode)
    if not use_pallas:
        return ref.rff_klms_bank_step_ref(theta, x, y, w, b, mu, s)
    return rff_klms_bank_step_pallas(
        theta, x, y, w, b, jnp.asarray(mu, theta.dtype), s,
        block_b=block_b, interpret=interpret,
    )


def rff_klms_bank_step(
    theta: jax.Array,
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    mu: jax.Array | float,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    block_b: int = 8,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused featurize+predict+update KLMS step for a bank of B filters.

    theta (B, D), x (B, d), y (B,), shared w (d, D) / b (D,), mu scalar or
    (B,), s optional (D,) per-feature scales (None = sqrt(2/D)). Returns
    (theta_new, predictions, prior errors).
    """
    bank, d = x.shape
    bm = _telemetry.klms_chunk_bytes(bank, d, theta.shape[-1], 1)
    with _dispatch(
        "klms_step", theta,
        bytes_moved=bm["bytes_per_tick_model"],
        shape=[bank, d], dfeat=theta.shape[-1], dtype=str(theta.dtype),
        mode=mode,
    ):
        return _rff_klms_bank_step_jit(
            theta, x, y, w, b, mu, s, mode=mode, block_b=block_b
        )


@functools.partial(jax.jit, static_argnames=("mode", "block_b", "chunk"))
def _rff_klms_bank_chunk_jit(
    theta, xs, ys, w, b, mu, mask=None, s=None, *, mode, block_b, chunk
):
    use_pallas, interpret = _use_pallas(mode)
    mu_arr = jnp.asarray(mu, theta.dtype)
    bsz, tlen, _ = xs.shape
    if mask is None:
        mask = jnp.ones((bsz, tlen), theta.dtype)

    def launch(th, xc, yc, mc):
        if not use_pallas:
            return ref.rff_klms_bank_chunk_ref(
                th, xc, yc, w, b, mu_arr, mc, s
            )
        return rff_klms_bank_chunk_pallas(
            th, xc, yc, w, b, mu_arr, mc, s,
            block_b=block_b, interpret=interpret,
        )

    if chunk is None:
        chunk = default_chunk_t(
            bsz, theta.shape[-1], theta.dtype, input_dim=xs.shape[-1]
        )
    if tlen <= chunk:
        return launch(theta, xs, ys, mask)

    xs_c = time_blocks(xs, chunk, axis=1)
    ys_c = time_blocks(ys, chunk, axis=1)
    mask_c = time_blocks(mask.astype(theta.dtype), chunk, axis=1)

    def body(th, xym):
        th, preds, errs = launch(th, *xym)
        return th, (preds, errs)

    theta, (preds, errs) = jax.lax.scan(body, theta, (xs_c, ys_c, mask_c))
    return (
        theta,
        unblock_time(preds, tlen, axis=1),
        unblock_time(errs, tlen, axis=1),
    )


def rff_klms_bank_chunk(
    theta: jax.Array,
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    mu: jax.Array | float,
    mask: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    block_b: int = 8,
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """T-chunked fused KLMS: advance a bank of B filters by T ticks at once.

    theta (B, D), xs (B, T, d), ys (B, T), shared w (d, D) / b (D,), mu
    scalar or (B,), mask optional (B, T) validity gate (1 = apply update),
    s optional (D,) per-feature scales (None = sqrt(2/D)).
    ``chunk`` bounds the ticks per kernel launch: ``chunk=k`` scans
    ceil(T/k) launches with a zero-masked final remainder; ``None`` picks
    the VMEM-budget-aware ``kernels.chunking.default_chunk_t`` for (B, D)
    (>= 512 for serving-sized banks, so short chunks still run in one
    launch). Returns (theta_new, predictions (B, T), errors (B, T)).
    """
    bank, tlen, d = xs.shape
    dfeat = theta.shape[-1]
    if chunk is None:
        chunk = default_chunk_t(bank, dfeat, theta.dtype, input_dim=d)
    launches = _ceil_div(tlen, chunk) if tlen > chunk else 1
    remainder = 1 if tlen > chunk and tlen % chunk else 0
    bm = _telemetry.klms_chunk_bytes(bank, d, dfeat, min(chunk, tlen))
    with _dispatch(
        "klms_chunk", theta,
        launches=launches, remainder=remainder,
        bytes_moved=bm["launch_bytes"] * launches
        + bm["stream_bytes_per_tick"] * tlen,
        shape=[bank, tlen, d], dfeat=dfeat, dtype=str(theta.dtype),
        mode=mode, chunk=chunk,
    ):
        return _rff_klms_bank_chunk_jit(
            theta, xs, ys, w, b, mu, mask, s,
            mode=mode, block_b=block_b, chunk=chunk,
        )


@functools.partial(jax.jit, static_argnames=("mode",))
def _rff_krls_bank_step_jit(theta, pmat, x, y, w, b, beta, s=None, *, mode):
    use_pallas, interpret = _use_pallas(mode)
    if not use_pallas:
        return ref.rff_krls_bank_step_ref(theta, pmat, x, y, w, b, beta, s)
    return rff_krls_bank_step_pallas(
        theta, pmat, x, y, w, b, jnp.asarray(beta, theta.dtype), s,
        interpret=interpret,
    )


def rff_krls_bank_step(
    theta: jax.Array,
    pmat: jax.Array,
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    beta: jax.Array | float,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused featurize+predict+RLS-downdate step for a bank of B tenants.

    theta (B, D), pmat (B, D, D), x (B, d), y (B,), shared w (d, D) /
    b (D,), beta scalar or (B,), s optional (D,) per-feature scales.
    Returns (theta_new, pmat_new, predictions, prior errors).
    """
    bank, d = x.shape
    bm = _telemetry.krls_chunk_bytes(bank, d, theta.shape[-1], 1)
    with _dispatch(
        "krls_step", theta,
        bytes_moved=bm["bytes_per_tick_model"],
        shape=[bank, d], dfeat=theta.shape[-1], dtype=str(theta.dtype),
        mode=mode,
    ):
        return _rff_krls_bank_step_jit(
            theta, pmat, x, y, w, b, beta, s, mode=mode
        )


@functools.partial(jax.jit, static_argnames=("mode", "chunk"))
def _rff_krls_bank_chunk_jit(
    theta, pmat, xs, ys, w, b, beta, mask=None, s=None, *, mode, chunk
):
    use_pallas, interpret = _use_pallas(mode)
    beta_arr = jnp.asarray(beta, theta.dtype)
    bsz, tlen, _ = xs.shape
    if mask is None:
        mask = jnp.ones((bsz, tlen), theta.dtype)

    def launch(th, pm, xc, yc, mc):
        if not use_pallas:
            return ref.rff_krls_bank_chunk_ref(
                th, pm, xc, yc, w, b, beta_arr, mc, s
            )
        return rff_krls_bank_chunk_pallas(
            th, pm, xc, yc, w, b, beta_arr, mc, s, interpret=interpret
        )

    if chunk is None:
        chunk = default_chunk_t(
            bsz, theta.shape[-1], theta.dtype, pmat=True,
            input_dim=xs.shape[-1],
        )
    if tlen <= chunk:
        return launch(theta, pmat, xs, ys, mask)

    xs_c = time_blocks(xs, chunk, axis=1)
    ys_c = time_blocks(ys, chunk, axis=1)
    mask_c = time_blocks(mask.astype(theta.dtype), chunk, axis=1)

    def body(carry, xym):
        th, pm = carry
        th, pm, preds, errs = launch(th, pm, *xym)
        return (th, pm), (preds, errs)

    (theta, pmat), (preds, errs) = jax.lax.scan(
        body, (theta, pmat), (xs_c, ys_c, mask_c)
    )
    return (
        theta,
        pmat,
        unblock_time(preds, tlen, axis=1),
        unblock_time(errs, tlen, axis=1),
    )


def rff_krls_bank_chunk(
    theta: jax.Array,
    pmat: jax.Array,
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    beta: jax.Array | float,
    mask: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """T-chunked fused EW-RLS: advance a bank of B tenants by T ticks at once.

    theta (B, D), pmat (B, D, D), xs (B, T, d), ys (B, T), shared w (d, D) /
    b (D,), beta scalar or (B,), mask optional (B, T) validity gate, s
    optional (D,) per-feature scales (None = sqrt(2/D)).
    ``chunk`` bounds ticks per launch as in :func:`rff_klms_bank_chunk`
    (``None`` = VMEM-budget-aware default, with the ``(D, D)`` P tile
    charged against the budget).
    Returns (theta_new, pmat_new, predictions (B, T), errors (B, T)).
    """
    bank, tlen, d = xs.shape
    dfeat = theta.shape[-1]
    if chunk is None:
        chunk = default_chunk_t(
            bank, dfeat, theta.dtype, pmat=True, input_dim=d
        )
    launches = _ceil_div(tlen, chunk) if tlen > chunk else 1
    remainder = 1 if tlen > chunk and tlen % chunk else 0
    bm = _telemetry.krls_chunk_bytes(bank, d, dfeat, min(chunk, tlen))
    with _dispatch(
        "krls_chunk", theta,
        launches=launches, remainder=remainder,
        bytes_moved=bm["launch_bytes"] * launches
        + bm["stream_bytes_per_tick"] * tlen,
        shape=[bank, tlen, d], dfeat=dfeat, dtype=str(theta.dtype),
        mode=mode, chunk=chunk,
    ):
        return _rff_krls_bank_chunk_jit(
            theta, pmat, xs, ys, w, b, beta, mask, s, mode=mode, chunk=chunk
        )


@functools.partial(
    jax.jit, static_argnames=("mode", "chunk", "normalized", "eps")
)
def _rff_klms_chunk_elements_jit(
    xs, ys, w, b, mu, s=None, *, mode, chunk, normalized, eps
):
    use_pallas, interpret = _use_pallas(mode)
    tlen = xs.shape[0]
    dfeat = w.shape[-1]
    if chunk is None:
        chunk = default_chunk_t(
            1, dfeat, xs.dtype, input_dim=xs.shape[-1], elements=True
        )
    chunk = min(chunk, tlen)
    xs_c = time_blocks(xs, chunk)  # (nc, Tc, d)
    ys_c = time_blocks(ys, chunk)
    mask_c = valid_time_mask(tlen, chunk, jnp.float32)
    if not use_pallas:
        return ref.klms_chunk_elements_ref(
            xs_c, ys_c, w, b, mu, mask_c, s, normalized=normalized, eps=eps
        )
    return rff_klms_chunk_elements_pallas(
        xs_c, ys_c, w, b, mu, mask_c, s,
        normalized=normalized, eps=eps, interpret=interpret,
    )


def rff_klms_chunk_elements(
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    mu: jax.Array | float,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    chunk: int | None = None,
    normalized: bool = False,
    eps: float = 1e-6,
) -> tuple[jax.Array, jax.Array]:
    """Per-chunk composed KLMS affine elements for the replay scan.

    xs (T, d), ys (T,) — ONE replayed stream (a tenant's log), not a bank
    sweep; shared w (d, D) / b (D,), mu scalar, s optional (D,) per-feature
    scales (None = sqrt(2/D)). The stream is time-blocked into
    ceil(T/chunk) chunks (zero-masked remainder composing the identity) and
    each chunk folds into one ``theta -> a theta + v`` element — the
    blocked half of core/scan.py's ``mode="blocked"`` replay. ``chunk=None``
    picks the element-aware VMEM default (``default_chunk_t(...,
    elements=True)``). Returns ``(a (nc, D, D), v (nc, D))`` f32.
    """
    tlen, d = xs.shape
    dfeat = w.shape[-1]
    if chunk is None:
        chunk = default_chunk_t(1, dfeat, xs.dtype, input_dim=d,
                                elements=True)
    chunk = min(chunk, tlen)
    # One grid launch covers every chunk; the tail chunk is zero-masked
    # (composes the identity), not a separate launch.
    with _dispatch(
        "klms_elements", xs,
        shape=[tlen, d], dfeat=dfeat, chunks=_ceil_div(tlen, chunk),
        dtype=str(xs.dtype), mode=mode, chunk=chunk,
    ):
        return _rff_klms_chunk_elements_jit(
            xs, ys, w, b, mu, s,
            mode=mode, chunk=chunk, normalized=normalized, eps=eps,
        )


@functools.partial(jax.jit, static_argnames=("mode", "chunk"))
def _rff_krls_chunk_elements_jit(xs, ys, w, b, beta, s=None, *, mode, chunk):
    use_pallas, interpret = _use_pallas(mode)
    tlen = xs.shape[0]
    dfeat = w.shape[-1]
    if chunk is None:
        chunk = default_chunk_t(
            1, dfeat, xs.dtype, input_dim=xs.shape[-1], elements=True
        )
    chunk = min(chunk, tlen)
    xs_c = time_blocks(xs, chunk)  # (nc, Tc, d)
    ys_c = time_blocks(ys, chunk)
    mask_c = valid_time_mask(tlen, chunk, jnp.float32)
    if not use_pallas:
        return ref.krls_chunk_elements_ref(xs_c, ys_c, w, b, beta, mask_c, s)
    return rff_krls_chunk_elements_pallas(
        xs_c, ys_c, w, b, beta, mask_c, s, interpret=interpret
    )


def rff_krls_chunk_elements(
    xs: jax.Array,
    ys: jax.Array,
    w: jax.Array,
    b: jax.Array,
    beta: jax.Array | float,
    s: jax.Array | None = None,
    *,
    mode: str = "auto",
    chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-chunk composed KRLS decay elements for the replay scan.

    Layout as :func:`rff_klms_chunk_elements`; ``beta`` the scalar
    forgetting factor. Each chunk folds into one information-form element
    ``(g, phi, r)`` with masked remainder ticks composing ``(1, 0, 0)``.
    Returns ``(g (nc,), phi (nc, D, D), r (nc, D))`` f32.
    """
    tlen, d = xs.shape
    dfeat = w.shape[-1]
    if chunk is None:
        chunk = default_chunk_t(1, dfeat, xs.dtype, input_dim=d,
                                elements=True)
    chunk = min(chunk, tlen)
    with _dispatch(
        "krls_elements", xs,
        shape=[tlen, d], dfeat=dfeat, chunks=_ceil_div(tlen, chunk),
        dtype=str(xs.dtype), mode=mode, chunk=chunk,
    ):
        return _rff_krls_chunk_elements_jit(
            xs, ys, w, b, beta, s, mode=mode, chunk=chunk
        )


@functools.partial(
    jax.jit, static_argnames=("mode", "chunk", "normalize", "eps")
)
def rff_attention(
    phi_q: jax.Array,
    phi_k: jax.Array,
    v: jax.Array,
    *,
    mode: str = "auto",
    chunk: int = 256,
    normalize: bool = True,
    eps: float = 1e-6,
) -> jax.Array:
    """Causal linear attention over feature-mapped q/k. Shapes (BH, S, dv)."""
    use_pallas, interpret = _use_pallas(mode)
    if not use_pallas:
        # Chunked scan in pure XLA — same O(S·C·D) math as the kernel (the
        # quadratic ref is O(S^2) and would be unusable at 500k tokens).
        return _chunked_linear_attention_xla(
            phi_q, phi_k, v, chunk=chunk, normalize=normalize, eps=eps
        )
    return rff_attention_pallas(
        phi_q, phi_k, v,
        chunk=chunk, normalize=normalize, eps=eps, interpret=interpret,
    )


def _chunked_linear_attention_xla(phi_q, phi_k, v, *, chunk, normalize, eps):
    bh, s, d = phi_q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    assert s % c == 0
    n = s // c
    qc = phi_q.reshape(bh, n, c, d).astype(jnp.float32)
    kc = phi_k.reshape(bh, n, c, d).astype(jnp.float32)
    vc = v.reshape(bh, n, c, dv).astype(jnp.float32)
    mask = jnp.tril(jnp.ones((c, c), jnp.float32))

    def body(carry, inp):
        s_state, z_state = carry  # (bh, d, dv), (bh, d)
        q, k, vv = inp  # (bh, c, d), (bh, c, d), (bh, c, dv)
        a = jnp.einsum("btd,bsd->bts", q, k) * mask
        out = jnp.einsum("bts,bsv->btv", a, vv) + jnp.einsum(
            "btd,bdv->btv", q, s_state
        )
        if normalize:
            denom = jnp.sum(a, -1) + jnp.einsum("btd,bd->bt", q, z_state)
            out = out / (denom + eps)[..., None]
        s_state = s_state + jnp.einsum("bsd,bsv->bdv", k, vv)
        z_state = z_state + jnp.sum(k, axis=1)
        return (s_state, z_state), out

    init = (
        jnp.zeros((bh, d, dv), jnp.float32),
        jnp.zeros((bh, d), jnp.float32),
    )
    qn = jnp.moveaxis(qc, 1, 0)  # (n, bh, c, d) scan over chunks
    kn = jnp.moveaxis(kc, 1, 0)
    vn = jnp.moveaxis(vc, 1, 0)
    _, outs = jax.lax.scan(body, init, (qn, kn, vn))
    out = jnp.moveaxis(outs, 0, 1).reshape(bh, s, dv)
    return out.astype(phi_q.dtype)


@jax.jit
def rff_attention_decode(
    s_state: jax.Array,
    z_state: jax.Array,
    phi_q: jax.Array,
    phi_k: jax.Array,
    v: jax.Array,
    *,
    eps: float = 1e-6,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step from the fixed-size state (the RFFKLMS-style update).

    Args:
      s_state: ``(BH, D, dv)`` running sum of phi(k) v^T.
      z_state: ``(BH, D)`` running sum of phi(k).
      phi_q, phi_k: ``(BH, D)`` features of the new token.
      v: ``(BH, dv)`` value of the new token.

    Returns:
      (output ``(BH, dv)``, new_s, new_z). O(D·dv) per token, O(1) in context
      length — the KV cache never grows.
    """
    s_new = s_state + jnp.einsum("bd,bv->bdv", phi_k, v)
    z_new = z_state + phi_k
    num = jnp.einsum("bd,bdv->bv", phi_q, s_new)
    den = jnp.einsum("bd,bd->b", phi_q, z_new) + eps
    return num / den[:, None], s_new, z_new


@functools.partial(
    jax.jit,
    static_argnames=(
        "feature_kind", "mode", "block_t", "normalize", "eps", "precision",
    ),
)
def _rff_attention_decode_block_jit(
    s_state, z_state, q, k, v, w, b, s=None, *,
    feature_kind, mode, block_t, normalize, eps, precision,
):
    use_pallas, interpret = _use_pallas(mode)
    bh, tlen, dh = q.shape
    dv = v.shape[-1]
    dfeat = w.shape[-1]
    if s is None:
        s = ref.default_decode_scale(dfeat, feature_kind)
    if block_t is None:
        block_t = default_decode_block_t(dfeat, dv, dh, q.dtype)

    def launch(sm, zv, qc, kc, vc):
        if not use_pallas:
            return ref.rff_attention_decode_block_ref(
                sm, zv, qc, kc, vc, w, b, s,
                feature_kind=feature_kind, normalize=normalize, eps=eps,
                precision=precision,
            )
        return rff_attention_decode_block_pallas(
            sm, zv, qc, kc, vc, w, b, s,
            feature_kind=feature_kind, normalize=normalize, eps=eps,
            precision=precision, interpret=interpret,
        )

    s_state = s_state.astype(jnp.float32)
    z_state = z_state.astype(jnp.float32)
    if tlen <= block_t:
        return launch(s_state, z_state, q, k, v)

    # Full blocks under a scan, then one unpadded remainder launch: padded
    # ticks would corrupt the state (a PRF feature of a zero token is NOT
    # zero), so the remainder gets its own exact launch instead of a mask.
    nfull, rem = tlen // block_t, tlen % block_t
    cut = nfull * block_t

    def body(carry, qkv):
        sm, zv = carry
        out, sm, zv = launch(sm, zv, *qkv)
        return (sm, zv), out

    qf = jnp.moveaxis(q[:, :cut].reshape(bh, nfull, block_t, dh), 1, 0)
    kf = jnp.moveaxis(k[:, :cut].reshape(bh, nfull, block_t, dh), 1, 0)
    vf = jnp.moveaxis(v[:, :cut].reshape(bh, nfull, block_t, dv), 1, 0)
    (s_state, z_state), outs = jax.lax.scan(
        body, (s_state, z_state), (qf, kf, vf)
    )
    out = jnp.moveaxis(outs, 0, 1).reshape(bh, cut, -1)
    if rem:
        tail, s_state, z_state = launch(
            s_state, z_state, q[:, cut:], k[:, cut:], v[:, cut:]
        )
        out = jnp.concatenate([out, tail], axis=1)
    return out, s_state, z_state


def rff_attention_decode_block(
    s_state: jax.Array,
    z_state: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    b: jax.Array,
    s: jax.Array | None = None,
    *,
    feature_kind: str = "prf",
    mode: str = "auto",
    block_t: int | None = None,
    normalize: bool = True,
    eps: float = 1e-6,
    precision: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked decode: advance the fixed-size attention state by T tokens
    in ceil(T / block_t) launches instead of T.

    The fused featurize+tick schedule of
    :func:`repro.kernels.rff_attention.rff_attention_decode_block_pallas`:
    pre-projected q/k ``(BH, T, dh)`` and v ``(BH, T, dv)`` enter, the
    feature map (``feature_kind`` "trig" — the canonical affine-trig form
    of any as_trig family — or "prf") runs in-kernel under the read-path
    precision contract, and the per-head ``(D, dv)``/``(D,)`` state stays
    VMEM-resident across each block's strictly sequential ticks.

    ``block_t`` bounds tokens per launch; ``None`` picks the VMEM-budget
    default ``kernels.chunking.default_decode_block_t`` (which charges the
    resident state + W tiles). Longer decodes scan full blocks and finish
    with one remainder launch — no masked padding, so every launch is
    bitwise the per-token recursion at f32.

    Returns (outputs ``(BH, T, dv)`` f32, new_s, new_z) — the T=1 case is
    exactly :func:`rff_attention_decode` plus the in-kernel feature map.
    """
    bh, tlen, dh = q.shape
    dv = v.shape[-1]
    dfeat = w.shape[-1]
    if block_t is None:
        block_t = default_decode_block_t(dfeat, dv, dh, q.dtype)
    if tlen <= block_t:
        launches, remainder = 1, 0
    else:
        nfull, rem = tlen // block_t, tlen % block_t
        launches = nfull + (1 if rem else 0)
        remainder = 1 if rem else 0
    with _dispatch(
        "decode_block", q,
        launches=launches, remainder=remainder,
        shape=[bh, tlen, dh], dfeat=dfeat, dtype=str(q.dtype),
        mode=mode, block_t=block_t, feature_kind=feature_kind,
        precision=precision,
    ):
        return _rff_attention_decode_block_jit(
            s_state, z_state, q, k, v, w, b, s,
            feature_kind=feature_kind, mode=mode, block_t=block_t,
            normalize=normalize, eps=eps, precision=precision,
        )


@functools.partial(
    jax.jit, static_argnames=("mode", "block_q", "block_k", "causal")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mode: str = "auto",
    block_q: int = 256,
    block_k: int = 256,
    causal: bool = True,
) -> jax.Array:
    """Exact blocked softmax attention, (BH, S, dh) layout."""
    use_pallas, interpret = _use_pallas(mode)
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        interpret=interpret,
    )
