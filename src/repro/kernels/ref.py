"""Pure-jnp oracles for every Pallas kernel (ground truth for allclose tests).

Deliberately naive implementations — clarity over speed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "F32_DOT",
    "canon_precision",
    "mp_project",
    "mp_trig",
    "rff_features_ref",
    "klms_tick_math",
    "krls_tick_math",
    "rff_klms_bank_step_ref",
    "rff_klms_bank_chunk_ref",
    "rff_bank_predict_ref",
    "rff_krls_bank_step_ref",
    "rff_krls_bank_chunk_ref",
    "klms_chunk_elements_ref",
    "krls_chunk_elements_ref",
    "decode_features_ref",
    "default_decode_scale",
    "rff_attention_ref",
    "rff_attention_state_ref",
    "rff_attention_decode_block_ref",
    "flash_attention_ref",
]

# The read-path precision contract (ONE definition, shared by the oracles
# here and the Pallas kernels, so they can never drift):
#
#   precision=None / "f32"  — the GEMM runs in f32 (bitwise-unchanged
#     legacy behavior for f32 inputs).
#   precision="bf16"        — the featurize GEMM inputs are cast to bf16
#     and accumulated in f32 (one MXU pass at half the input bandwidth);
#     the bias-add / cos / scale run in f32 on the f32 accumulator; the
#     feature block is then *stored* in bf16 (halving activation bytes).
#     Every downstream reduction against theta accumulates in f32.
#
# Training state is never touched by this knob: KRLS ``P`` and both
# families' theta stay f32 — only the read path and feature maps drop
# precision (the ISSUE-5 contract; tolerance per family is pinned in
# tests/test_read_path.py).
_BF16 = ("bf16", "bfloat16")
_F32 = (None, "f32", "float32")

# The precision every f32 dot in the Pallas kernels, the featurize GEMM and
# the dense and sharded KRLS recursions (core/krls.py) asks for. A TPU runs an
# f32 matmul as one bf16 pass unless told otherwise, in Mosaic as in XLA:
# on a v5e that moved the KLMS fleet's theta by ~1e-2 against the f32
# reference (chip_smoke.py). CPU dots are f32 either way.
F32_DOT = jax.lax.Precision.HIGHEST


def canon_precision(precision):
    """Validate + canonicalize the knob: ``"bf16"`` or ``None`` (f32).

    Every read-path entry point (ops dispatchers, Pallas wrappers, the
    generic bank fallback) funnels through this, so a typo'd precision
    string raises identically on every backend instead of silently running
    f32 on one of them.
    """
    if precision in _BF16:
        return "bf16"
    if precision in _F32:
        return None
    raise ValueError(f"unknown precision {precision!r}; use None/'f32'/'bf16'")


def mp_project(x, w, precision=None):
    """``x @ w`` under the read-path precision contract (f32 accumulate)."""
    if canon_precision(precision) == "bf16":
        return jnp.dot(
            x.astype(jnp.bfloat16),
            w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    return jnp.dot(x, w, precision=F32_DOT)


def mp_trig(proj, b, s, precision=None):
    """bias-add + cos + per-feature scale; bf16 storage when asked."""
    z = s * jnp.cos(proj + b)
    if precision in _BF16:
        return z.astype(jnp.bfloat16)
    return z


def rff_features_ref(x, w, b, s=None, precision=None):
    """``s * cos(x @ w + b)`` — oracle for kernels/rff_features.py.

    ``s`` is the per-feature scale row of the canonical affine-trig form
    (repro.features); None means the Monte-Carlo ``sqrt(2/D)``.
    ``precision`` follows the module-level read-path contract (bf16 GEMM +
    f32 accumulation + bf16 feature storage); the default is bitwise the
    legacy f32 path.
    """
    if s is None:
        d = w.shape[1]
        s = jnp.sqrt(2.0 / d).astype(x.dtype)
    else:
        s = s.astype(x.dtype)
    if precision in _F32:
        return s * jnp.cos(x @ w + b)
    return mp_trig(mp_project(x, w, precision), b, s, precision)


def klms_tick_math(theta, z, y, mu_b, gate=None):
    """ONE KLMS bank tick given a precomputed feature block ``z (B, D)``.

    The single source of truth for the update math: the fused-kernel
    oracles below AND the generic (featurize-based) bank fallback in
    core/bank.py both delegate here, so the non-trig path can never
    silently diverge from the oracle. ``gate`` optionally masks the state
    update (masked ticks still emit their prior prediction/error); with
    gate==1 the expression multiplies by exactly 1.0, preserving the
    chunk-vs-tick bitwise contract.
    """
    pred = jnp.sum(theta * z, axis=-1)
    err = y - pred
    upd = err if gate is None else gate * err
    return theta + (mu_b * upd)[:, None] * z, pred, err


def krls_tick_math(theta, pmat, z, y, beta_b):
    """ONE EW-RLS bank tick (incl. the symmetrization pass) given ``z``.

    Shared by the fused-kernel oracles and core/bank.py's generic fallback
    — exactly ``core.krls.rls_step`` vmapped over the bank.
    """
    pred = jnp.sum(theta * z, axis=-1)
    err = y - pred
    pz = jnp.einsum("bij,bj->bi", pmat, z)  # (B, D)
    denom = beta_b + jnp.sum(z * pz, axis=-1)
    gain = pz / denom[:, None]
    theta_new = theta + gain * err[:, None]
    pmat_new = (
        pmat - gain[:, :, None] * pz[:, None, :]
    ) / beta_b[:, None, None]
    pmat_new = 0.5 * (pmat_new + jnp.swapaxes(pmat_new, -1, -2))
    return theta_new, pmat_new, pred, err


def rff_klms_bank_step_ref(theta, x, y, w, b, mu, s=None):
    """Two-pass fused-KLMS-step oracle — for kernels/rff_klms_step.py.

    theta (B, D), x (B, d), y (B,), mu scalar or (B,), s optional (D,)
    per-feature scales. Materializes the feature block z (the HBM
    round-trip the fused kernel removes).
    """
    z = rff_features_ref(x, w, b, s)  # (B, D)
    mu_b = jnp.broadcast_to(jnp.asarray(mu, theta.dtype), y.shape)
    return klms_tick_math(theta, z, y, mu_b)


def rff_klms_bank_chunk_ref(theta, xs, ys, w, b, mu, mask=None, s=None):
    """T-chunked KLMS oracle — for ``rff_klms_bank_chunk_pallas``.

    A ``lax.scan`` of the per-tick recursion over the chunk's time axis:
    theta (B, D), xs (B, T, d), ys (B, T), mask (B, T) validity gate
    (1 = apply the update; masked ticks still emit their prior prediction).
    With mask==1 every tick multiplies by exactly 1.0, so an unmasked chunk
    is bitwise identical to T per-tick ``rff_klms_bank_step_ref`` calls.
    """
    import jax

    if mask is None:
        mask = jnp.ones(ys.shape, theta.dtype)
    mu_b = jnp.broadcast_to(jnp.asarray(mu, theta.dtype), ys.shape[:1])

    def tick(th, xym):
        x_t, y_t, m_t = xym
        z = rff_features_ref(x_t, w, b, s)  # (B, D)
        th, pred, err = klms_tick_math(th, z, y_t, mu_b, gate=m_t)
        return th, (pred, err)

    xs_t = jnp.swapaxes(xs, 0, 1)  # (T, B, d) time-major
    ys_t = jnp.swapaxes(ys, 0, 1)
    mask_t = jnp.swapaxes(mask.astype(theta.dtype), 0, 1)
    theta, (preds, errs) = jax.lax.scan(tick, theta, (xs_t, ys_t, mask_t))
    return theta, jnp.swapaxes(preds, 0, 1), jnp.swapaxes(errs, 0, 1)


def rff_bank_predict_ref(theta, xq, w, b, s=None, precision=None):
    """Predict-only bank oracle — for kernels/rff_predict.py.

    The read path of the paper's fixed-cost claim: a query block of Q
    inputs per tenant is one featurize GEMM plus one f32 reduction against
    the tenant's theta — no state is touched. theta (B, D), xq (B, Q, d),
    shared w (d, D) / b (D,), s optional (D,) per-feature scales,
    ``precision`` per the module-level read-path contract. Returns
    predictions (B, Q).

    Numerically this is ``vmap over tenants of vmap over queries of
    ``featurize(x) . theta`` — the `core.bank.bank_predict` adapter — with
    the per-query matvecs batched into one GEMM.
    """
    z = rff_features_ref(xq, w, b, s, precision)  # (B, Q, D)
    pred = jnp.sum(
        theta[:, None, :].astype(jnp.float32) * z.astype(jnp.float32),
        axis=-1,
    )
    return pred.astype(theta.dtype)


def rff_krls_bank_step_ref(theta, pmat, x, y, w, b, beta, s=None):
    """Two-pass fused-KRLS-step oracle — for kernels/rff_krls_step.py.

    Exactly the EW-RLS recursion of ``core.krls.rls_step`` (including the
    symmetrization pass) vmapped over the bank: theta (B, D),
    pmat (B, D, D), x (B, d), y (B,), beta scalar or (B,) per-tenant
    forgetting factors, s optional (D,) per-feature scales. Materializes z
    and pz in HBM (the round-trips the fused kernel removes).
    """
    z = rff_features_ref(x, w, b, s)  # (B, D)
    beta_b = jnp.broadcast_to(jnp.asarray(beta, theta.dtype), y.shape)
    return krls_tick_math(theta, pmat, z, y, beta_b)


def rff_krls_bank_chunk_ref(theta, pmat, xs, ys, w, b, beta, mask=None, s=None):
    """T-chunked EW-RLS oracle — for ``rff_krls_bank_chunk_pallas``.

    ``lax.scan`` of :func:`rff_krls_bank_step_ref` over the chunk's time
    axis with a per-(tenant, tick) validity gate: masked ticks emit their
    prior prediction but select the untouched theta/P (``jnp.where``), so
    an unmasked chunk is bitwise T per-tick steps.
    """
    import jax

    if mask is None:
        mask = jnp.ones(ys.shape, theta.dtype)

    def tick(carry, xym):
        th, pm = carry
        x_t, y_t, m_t = xym
        th2, pm2, pred, err = rff_krls_bank_step_ref(
            th, pm, x_t, y_t, w, b, beta, s
        )
        th = jnp.where(m_t[:, None] > 0, th2, th)
        pm = jnp.where(m_t[:, None, None] > 0, pm2, pm)
        return (th, pm), (pred, err)

    xs_t = jnp.swapaxes(xs, 0, 1)  # (T, B, d) time-major
    ys_t = jnp.swapaxes(ys, 0, 1)
    mask_t = jnp.swapaxes(mask.astype(theta.dtype), 0, 1)
    (theta, pmat), (preds, errs) = jax.lax.scan(
        tick, (theta, pmat), (xs_t, ys_t, mask_t)
    )
    return theta, pmat, jnp.swapaxes(preds, 0, 1), jnp.swapaxes(errs, 0, 1)


def klms_chunk_elements_ref(
    xs, ys, w, b, mu, mask=None, s=None, normalized=False, eps=1e-6
):
    """Per-chunk composed KLMS affine elements — oracle for
    kernels/rff_scan.py's ``rff_klms_chunk_elements_pallas``.

    xs (nc, Tc, d), ys (nc, Tc), mask optional (nc, Tc), mu scalar. Each
    chunk's Tc ticks fold into ONE ``theta -> a theta + v`` map via the
    same rank-1 recursion the kernel runs on its resident tile:

        row = z A;  A <- A - mu_eff z row^T;  v <- v - mu_eff ((z.v) - y) z

    Masked ticks have ``mu_eff = 0`` and compose the identity. Returns
    ``(a (nc, D, D), v (nc, D))`` f32.
    """
    import jax

    if mask is None:
        mask = jnp.ones(ys.shape, jnp.float32)
    dfeat = w.shape[-1]

    def per_chunk(xc, yc, mc):
        zc = rff_features_ref(xc, w, b, s).astype(jnp.float32)  # (Tc, D)

        def tick(carry, zym):
            a, v = carry
            z, y, m = zym
            mu_t = mu / (eps + z @ z) if normalized else mu
            mu_eff = m * mu_t
            row = z @ a  # (D,)
            a = a - mu_eff * jnp.outer(z, row)
            v = v - mu_eff * ((z @ v) - y) * z
            return (a, v), None

        init = (
            jnp.eye(dfeat, dtype=jnp.float32),
            jnp.zeros((dfeat,), jnp.float32),
        )
        (a, v), _ = jax.lax.scan(
            tick, init, (zc, yc.astype(jnp.float32), mc)
        )
        return a, v

    return jax.vmap(per_chunk)(xs, ys, mask.astype(jnp.float32))


def krls_chunk_elements_ref(xs, ys, w, b, beta, mask=None, s=None):
    """Per-chunk composed KRLS decay elements — oracle for
    kernels/rff_scan.py's ``rff_krls_chunk_elements_pallas``.

    xs (nc, Tc, d), ys (nc, Tc), mask optional (nc, Tc), beta scalar. Each
    chunk folds its ticks into the information-form accumulator

        g <- beta g;  Phi <- beta Phi + z z^T;  r <- beta r + y z

    with masked ticks composing the identity ``(1, 0, 0)``. Returns
    ``(g (nc,), phi (nc, D, D), r (nc, D))`` f32.
    """
    import jax

    if mask is None:
        mask = jnp.ones(ys.shape, jnp.float32)
    dfeat = w.shape[-1]

    def per_chunk(xc, yc, mc):
        zc = rff_features_ref(xc, w, b, s).astype(jnp.float32)  # (Tc, D)

        def tick(carry, zym):
            g, phi, r = carry
            z, y, m = zym
            beta_eff = jnp.where(m > 0, jnp.float32(beta), 1.0)
            g = g * beta_eff
            phi = beta_eff * phi + m * jnp.outer(z, z)
            r = beta_eff * r + (m * y) * z
            return (g, phi, r), None

        init = (
            jnp.ones((), jnp.float32),
            jnp.zeros((dfeat, dfeat), jnp.float32),
            jnp.zeros((dfeat,), jnp.float32),
        )
        (g, phi, r), _ = jax.lax.scan(
            tick, init, (zc, yc.astype(jnp.float32), mc)
        )
        return g, phi, r

    return jax.vmap(per_chunk)(xs, ys, mask.astype(jnp.float32))


def decode_features_ref(
    x, w, b, s, feature_kind="trig", precision=None, prf_eps=1e-6
):
    """Attention-path feature map under the read-path precision contract.

    The ONE definition of how the decode kernel featurizes a block of
    pre-projected tokens ``x (..., dh)`` against the shared spectral matrix
    ``w (dh, D)`` — shared by :func:`rff_attention_decode_block_ref` and the
    Pallas decode-block kernel so they can never drift:

    * ``feature_kind="trig"`` — the canonical affine-trig form
      ``s * cos(x @ w + b)`` every ``as_trig``-canonicalizable family
      (rff/orf/qmc/gq) lowers to; runs through :func:`mp_project` /
      :func:`mp_trig`.
    * ``feature_kind="prf"`` — positive random features of the softmax
      kernel, ``s * (exp(x @ w - ||x||^2/2) / sqrt(D) + prf_eps)`` with
      ``b`` unused (PRF has no phase). ``s`` here is a 0/1 column mask
      (1 everywhere unpadded) so zero-padded D columns are exactly 0 —
      exp of a padded column is NOT 0 and would poison the normalizer.

    ``precision`` follows the module-level contract: bf16 GEMM operands,
    f32 accumulation, bf16 feature storage.
    """
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    s32 = s.astype(jnp.float32)
    proj = mp_project(x32, w32, precision)
    if feature_kind == "trig":
        return mp_trig(proj, b.astype(jnp.float32), s32, precision)
    if feature_kind != "prf":
        raise ValueError(f"unknown feature_kind {feature_kind!r}")
    d = w.shape[-1]
    stab = proj - jnp.sum(jnp.square(x32), axis=-1, keepdims=True) / 2.0
    phi = s32 * (jnp.exp(stab) / jnp.sqrt(jnp.float32(d)) + prf_eps)
    if canon_precision(precision) == "bf16":
        return phi.astype(jnp.bfloat16)
    return phi


def default_decode_scale(dfeat, feature_kind="trig"):
    """Default per-feature scale row for the decode path.

    Trig: the Monte-Carlo ``sqrt(2/D)`` (matching ``core.rff.rff_features``);
    PRF: an all-ones column mask (PRF carries its ``1/sqrt(D)`` inside).
    """
    if feature_kind == "prf":
        return jnp.ones((dfeat,), jnp.float32)
    return jnp.broadcast_to(
        jnp.sqrt(2.0 / dfeat).astype(jnp.float32), (dfeat,)
    )


def rff_attention_ref(phi_q, phi_k, v, normalize=True, eps=1e-6):
    """Quadratic-form causal kernel attention — oracle for rff_attention.

    o_t = sum_{s<=t} (phi_q_t . phi_k_s) v_s [/ normalizer]. Shapes as the
    kernel: (BH, S, D), (BH, S, D), (BH, S, dv).
    """
    s = phi_q.shape[1]
    a = jnp.einsum("btd,bsd->bts", phi_q, phi_k)
    mask = jnp.tril(jnp.ones((s, s), a.dtype))
    a = a * mask[None]
    out = jnp.einsum("bts,bsv->btv", a, v)
    if normalize:
        denom = jnp.sum(a, axis=-1, keepdims=True)
        out = out / (denom + eps)
    return out


def rff_attention_state_ref(phi_q, phi_k, v, normalize=True, eps=1e-6):
    """Same computation via the fixed-size running state (recurrent oracle).

    Returns (outputs, final_S (BH, D, dv), final_z (BH, D)) — validates the
    state semantics the decode path relies on.
    """
    import jax

    def per_head(q, k, vv):
        def body(carry, qkv):
            s_state, z_state = carry
            qt, kt, vt = qkv
            s_state = s_state + jnp.outer(kt, vt)
            z_state = z_state + kt
            num = qt @ s_state
            if normalize:
                num = num / (qt @ z_state + eps)
            return (s_state, z_state), num

        init = (
            jnp.zeros((q.shape[-1], vv.shape[-1]), jnp.float32),
            jnp.zeros((q.shape[-1],), jnp.float32),
        )
        (s_f, z_f), outs = jax.lax.scan(
            body,
            init,
            (
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                vv.astype(jnp.float32),
            ),
        )
        return outs.astype(q.dtype), s_f, z_f

    import jax as _jax

    return _jax.vmap(per_head)(phi_q, phi_k, v)


def rff_attention_decode_block_ref(
    s_state,
    z_state,
    q,
    k,
    v,
    w,
    b,
    s=None,
    *,
    feature_kind="prf",
    normalize=True,
    eps=1e-6,
    precision=None,
):
    """Scan-of-tick oracle for the fused decode-block kernel.

    A block of T pre-projected decode tokens advances the fixed-size
    attention state exactly like T ``ops.rff_attention_decode`` calls:
    the whole block featurizes in one GEMM (:func:`decode_features_ref`,
    under the precision contract), then each token applies the
    update-then-emit tick

        S += phi_k v^T;  z += phi_k;  o = phi_q S [/ (phi_q . z + eps)]

    in f32 regardless of feature storage precision (state never drops
    precision).

    Args:
      s_state: ``(BH, D, dv)`` f32 running sum of phi(k) v^T.
      z_state: ``(BH, D)`` f32 running sum of phi(k).
      q, k: ``(BH, T, dh)`` pre-projected (RoPE'd, pre-scaled) tokens.
      v: ``(BH, T, dv)`` values.
      w: ``(dh, D)`` shared spectral matrix; b ``(D,)`` phases (trig only).
      s: ``(D,)`` per-feature scales; None = trig ``sqrt(2/D)`` / prf ones.

    Returns:
      (outputs ``(BH, T, dv)`` f32, new_s, new_z).
    """
    import jax

    if s is None:
        s = default_decode_scale(w.shape[-1], feature_kind)
    phi_q = decode_features_ref(q, w, b, s, feature_kind, precision)
    phi_k = decode_features_ref(k, w, b, s, feature_kind, precision)
    phi_q = phi_q.astype(jnp.float32)
    phi_k = phi_k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)

    def tick(carry, qkv):
        s_st, z_st = carry
        qt, kt, vt = qkv  # (BH, D), (BH, D), (BH, dv)
        s_st = s_st + kt[:, :, None] * vt[:, None, :]
        z_st = z_st + kt
        num = jnp.einsum("bd,bdv->bv", qt, s_st)
        if normalize:
            den = jnp.sum(qt * z_st, axis=-1) + eps
            num = num / den[:, None]
        return (s_st, z_st), num

    qt_ = jnp.swapaxes(phi_q, 0, 1)  # (T, BH, D) time-major
    kt_ = jnp.swapaxes(phi_k, 0, 1)
    vt_ = jnp.swapaxes(v32, 0, 1)
    (s_f, z_f), outs = jax.lax.scan(
        tick,
        (s_state.astype(jnp.float32), z_state.astype(jnp.float32)),
        (qt_, kt_, vt_),
    )
    return jnp.swapaxes(outs, 0, 1), s_f, z_f


def flash_attention_ref(q, k, v, causal=True):
    """Exact softmax attention — oracle for kernels/flash_attention.py.

    q, k: (BH, S, dh); v: (BH, S, dv).
    """
    dh = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * dh**-0.5
    if causal:
        n = q.shape[1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(mask[None], s, -1e30)
    import jax

    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkv->bqv", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
