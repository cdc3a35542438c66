"""Common layers: RMSNorm, RoPE / M-RoPE, SwiGLU MLP, GQA attention.

Attention has two execution paths with identical math:
  - chunked online-softmax attention in pure XLA (lax.scan) — used by the
    dry-run (compiles on any backend, memory-bounded for 32k prefill), and
  - the Pallas flash kernel in ``repro.kernels`` — used when
    ``cfg.use_pallas`` (compiled on a TPU, interpreted on the CPU).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                      # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, D); positions: (3, B, S) — t/h/w position ids. The D/2
    frequency slots are split into `sections` (t, h, w); each section rotates
    by its own position component.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                       # (D/2,)
    # pick the position component per frequency slot
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections),
                        total_repeat_length=d // 2)    # (D/2,)
    pos = positions.astype(jnp.float32)                # (3, B, S)
    pos_per_slot = jnp.take(pos, sec_id, axis=0)       # (D/2, B, S)
    angles = jnp.einsum("fbs,f->bsf", pos_per_slot, freqs)  # (B, S, D/2)
    angles = angles[..., None, :]                      # (B, S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention — chunked online-softmax (XLA) path
# ---------------------------------------------------------------------------


def _chunk_size(seq: int, target: int) -> int:
    """Largest divisor of `seq` that is <= `target`."""
    c = max(1, min(seq, target))
    while seq % c:
        c -= 1
    return c


def full_gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       causal: bool = True,
                       q_offset: int | jax.Array = 0) -> jax.Array:
    """Plain (materialized-scores) attention — scan-free.

    FLOP-equivalent to the chunked path; used by the dry-run cost probes
    (``cfg.exact_costs``) because XLA's cost_analysis counts scan bodies
    once. Never used at runtime for long sequences (O(S*T) memory).
    """
    B, S, H, D = q.shape
    T, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    qr = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, KVH, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qr, k,
                   preferred_element_type=jnp.float32)
    if causal:
        mask = (jnp.arange(S)[:, None] + q_offset) >= jnp.arange(T)[None, :]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, D).astype(q.dtype)


def chunked_gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = True,
                          q_offset: int | jax.Array = 0,
                          q_chunk: int = 512,
                          kv_chunk: int = 1024) -> jax.Array:
    """Memory-bounded attention with online softmax (flash-style, XLA).

    q: (B, S, H, D);  k, v: (B, T, KVH, D);  H = KVH * G.
    Returns (B, S, H, D).  Causal mask uses absolute positions
    (q position = q_offset + index), so it also serves chunked prefill.
    """
    B, S, H, D = q.shape
    T, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qc, kc = _chunk_size(S, q_chunk), _chunk_size(T, kv_chunk)
    nq, nk = S // qc, T // kc
    scale = 1.0 / math.sqrt(D)

    # keep q/k/v in model dtype; accumulate scores/output in f32 via
    # preferred_element_type (upcasting whole k/v doubles HBM traffic and
    # footprint at 32k+ context — §Perf OPT2)
    qr = (q * jnp.asarray(scale, q.dtype)).reshape(B, nq, qc, KVH, G, D)
    kr = k.reshape(B, nk, kc, KVH, D)
    vr = v.reshape(B, nk, kc, KVH, D)

    q_pos = (jnp.arange(S).reshape(nq, qc) + q_offset)       # (nq, qc)
    k_pos = jnp.arange(T).reshape(nk, kc)                    # (nk, kc)

    def q_step(_, qi):
        qb, qp = qi                                          # (B,qc,KVH,G,D)
        m0 = jnp.full((B, KVH, G, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, qc, D), jnp.float32)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb, kp = ki
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb, kb,
                           preferred_element_type=jnp.float32)
            if causal:
                mask = qp[:, None] >= kp[None, :]            # (qc, kc)
                s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new == -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4), k_pos),
            unroll=1)
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,KVH,G,qc,D)
        return None, out.transpose(0, 3, 1, 2, 4)            # (B,qc,KVH,G,D)

    _, outs = lax.scan(q_step, None,
                       (qr.transpose(1, 0, 2, 3, 4, 5), q_pos))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, D)
    return out.astype(q.dtype)


def decode_gqa_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_index: jax.Array) -> jax.Array:
    """Single-token decode attention against a (B, T, KVH, D) cache.

    q: (B, 1, H, D). Positions > cache_index are masked out.
    ``cache_index`` may be a scalar (lockstep decode) or (B,) per-slot
    lengths (continuous batching in the serving engine).
    """
    B, _, H, D = q.shape
    T, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    # no f32 upcast of the cache (2x HBM traffic at 32k+ context); scores
    # accumulate in f32 via preferred_element_type (§Perf OPT2)
    qr = (q * jnp.asarray(scale, q.dtype)).reshape(B, KVH, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qr, k_cache,
                   preferred_element_type=jnp.float32)
    ci = jnp.asarray(cache_index)
    if ci.ndim == 1:
        valid = jnp.arange(T)[None] <= ci[:, None]      # (B, T)
        s = jnp.where(valid[:, None, None], s, -jnp.inf)
    else:
        valid = jnp.arange(T)[None] <= ci               # (1, T)
        s = jnp.where(valid[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projection + rope + attention + out projection)
# ---------------------------------------------------------------------------


def attention_block(params, x, cfg, *, positions=None, cache=None,
                    cache_index=None, causal=True,
                    encoder_kv: Optional[Tuple[jax.Array, jax.Array]] = None):
    """GQA attention block.

    params: {wq, wk, wv, wo [, bq, bk, bv]} — wq: (E, H, D) etc.
    x: (B, S, E). Returns ``(out, extras)`` where extras is
      {"cache": (k_cache, v_cache)}   in decode mode (cache given), or
      {"kv": (k, v)}                  in full-sequence self-attention, or
      {}                              in cross-attention.
    If `encoder_kv` is given, runs cross-attention (no rope, no causal).
    """
    B, S, E = x.shape
    H, D = cfg.num_heads, cfg.head_dim_
    KVH = cfg.num_kv_heads
    dt = x.dtype

    q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
    q = constrain(q, "batch", None, "heads", None)

    cross = encoder_kv is not None
    if cross:
        k, v = encoder_kv
    else:
        k = jnp.einsum("bse,ehd->bshd", x, params["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bshd", x, params["wv"].astype(dt))
        if cfg.qkv_bias:
            k = k + params["bk"].astype(dt)
            v = v + params["bv"].astype(dt)
        k = constrain(k, "batch", None, "kv_heads", None)
        v = constrain(v, "batch", None, "kv_heads", None)

    if not cross:
        if positions is None:
            if cache_index is None:
                base = 0
            else:
                ci = jnp.asarray(cache_index)
                base = ci[:, None] if ci.ndim == 1 else ci   # per-slot ok
            pos = base + jnp.arange(S)[None, :]               # (1|B, S)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
        elif cfg.mrope_sections is not None:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    extras: dict = {}
    if cache is not None and not cross:
        # decode: write this token's k/v at cache_index, attend to cache
        k_cache, v_cache = cache                             # (B, T, KVH, D)
        k_cache = _write_cache(k_cache, k, cache_index)
        v_cache = _write_cache(v_cache, v, cache_index)
        out = decode_gqa_attention(q, k_cache, v_cache, cache_index)
        extras["cache"] = (k_cache, v_cache)
    elif cross:
        out = (full_gqa_attention(q, k, v, causal=False)
               if cfg.exact_costs else
               chunked_gqa_attention(q, k, v, causal=False))
    elif cfg.exact_costs:
        # dry-run cost probe: scan-free, flop-equivalent attention
        out = full_gqa_attention(q, k, v, causal=causal)
        extras["kv"] = (k, v)
    elif cfg.use_pallas:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal)
        extras["kv"] = (k, v)
    else:
        out = chunked_gqa_attention(q, k, v, causal=causal)
        extras["kv"] = (k, v)

    out = constrain(out, "batch", None, "heads", None)
    y = jnp.einsum("bshd,hde->bse", out, params["wo"].astype(dt))
    return y, extras


def _write_cache(cache: jax.Array, kv: jax.Array,
                 index: jax.Array) -> jax.Array:
    """Write (B, 1, KVH, D) kv into (B, T, KVH, D) cache at position index.

    Scalar index: one dynamic_update_slice. (B,) per-slot indices
    (continuous batching): one-hot masked write.
    """
    idx = jnp.asarray(index)
    if idx.ndim == 1:
        T = cache.shape[1]
        onehot = (jnp.arange(T)[None, :] == idx[:, None])    # (B, T)
        m = onehot[:, :, None, None]
        return jnp.where(m, kv.astype(cache.dtype), cache)
    return lax.dynamic_update_slice(
        cache, kv.astype(cache.dtype),
        (0, idx.astype(jnp.int32), 0, 0))


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def swiglu_mlp(params, x, cfg=None):
    """params: {wi (E,F), wg (E,F), wo (F,E)}."""
    dt = x.dtype
    if cfg is not None and cfg.use_pallas:
        from repro.kernels import ops as kops
        h = kops.matmul(x, params["wg"].astype(dt))
        g = kops.matmul(x, params["wi"].astype(dt))
        h = jax.nn.silu(h) * g
        h = constrain(h, "batch", None, "mlp")
        return kops.matmul(h, params["wo"].astype(dt))
    h = jax.nn.silu(x @ params["wg"].astype(dt)) * (x @ params["wi"].astype(dt))
    h = constrain(h, "batch", None, "mlp")
    return h @ params["wo"].astype(dt)
