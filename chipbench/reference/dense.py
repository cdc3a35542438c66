"""Plain Mistral-style decoder forward, one sequence at a time.

Layer: RMSNorm; q, k, v projections (grouped-query: each of the
``num_key_value_heads`` heads serves ``num_attention_heads / kv`` query
heads); rotary embedding on q and k (rotate-half form, theta
``rope_theta``); causal softmax attention with scores scaled by
1/sqrt(head_dim); output projection; residual; RMSNorm; SwiGLU MLP
(down(silu(gate(x)) * up(x))); residual. Final RMSNorm and output head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, HI, mm, rms_norm, silu


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1."""
    S, _, D = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c: dict, p: dict, x, mode: str):
    S, E = x.shape
    H, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    eps = c["rms_norm_eps"]
    a = p["attn"]
    h = rms_norm(x, p["ln1"], eps)
    q = mm(h, a["wq"].reshape(E, H * D), mode).reshape(S, H, D)
    k = mm(h, a["wk"].reshape(E, KV * D), mode).reshape(S, KV, D)
    v = mm(h, a["wv"].reshape(E, KV * D), mode).reshape(S, KV, D)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / jnp.sqrt(F32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))[None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + mm(o.reshape(S, H * D), a["wo"].reshape(H * D, E), mode)
    f = p["ffn"]
    h = rms_norm(x, p["ln2"], eps)
    h = silu(mm(h, f["wg"], mode)) * mm(h, f["wi"], mode)
    return x + mm(h, f["wo"], mode)


def forward(cfg: dict, params, tokens, mode: str = "f32", remat=False):
    """tokens (S,) -> logits (S, V) in float32."""
    c = cfg["config"]
    x = params["embed"][tokens].astype(F32)
    layer = lambda x, p: (_layer(c, p, x, mode), None)       # noqa: E731
    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["layers"]["p0"])
    x = rms_norm(x, params["final_norm"], c["rms_norm_eps"])
    head = (params["embed"].T if c["tie_word_embeddings"]
            else params["lm_head"])
    return mm(x, head, mode)
