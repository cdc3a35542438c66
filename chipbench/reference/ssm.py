"""Plain Mamba-2 forward (arXiv:2405.21060), one sequence at a time.

The SSD layer is computed in its quadratic (attention-like) dual form,
    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t,
not by chunks or a recurrence, so it shares no algorithm with the served
path. Layer: RMSNorm; projections to z, x, B, C, dt (one group); causal
depthwise conv of width d_conv over (x, B, C), then SiLU; dt = softplus(dt
+ dt_bias), A = -exp(A_log); SSD; y * SiLU(z), gated RMSNorm; out
projection; residual. As the configuration runs it: the conv has no bias
(the published layer has one) and the residual stream is float32 here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, HI, mm, rms_norm, silu


def _layer(c: dict, p: dict, x, mode: str):
    s = c["ssm_cfg"]
    eps = c["norm_epsilon"]
    S = x.shape[0]
    d_in = s["expand"] * c["d_model"]
    P, N = s["headdim"], s["d_state"]
    nh = d_in // P
    q = p["ssm"]
    h = rms_norm(x, p["ln1"], eps)
    z = mm(h, q["wz"], mode)
    xbc = jnp.concatenate([mm(h, q["wx"], mode), mm(h, q["wB"], mode),
                           mm(h, q["wC"], mode)], -1)
    dt = mm(h, q["wdt"], mode)
    w = jnp.concatenate([q["conv_x"], q["conv_B"], q["conv_C"]], -1)
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc], 0)
    xbc = silu(sum(xp[i:i + S] * w[i] for i in range(k)))
    xs, B, C = xbc[:, :d_in], xbc[:, d_in:d_in + N], xbc[:, d_in + N:]
    dt = jax.nn.softplus(dt + q["dt_bias"])                    # (S, nh)
    A = -jnp.exp(q["A_log"])
    cum = jnp.cumsum(dt * A, axis=0)                           # (S, nh)
    seg = cum[:, None, :] - cum[None, :, :]                    # (t, s, nh)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = jnp.einsum("tn,sn->ts", C, B, precision=HI)
    m = cb[:, :, None] * decay * dt[None, :, :]                # (t, s, nh)
    xh = xs.reshape(S, nh, P)
    y = jnp.einsum("tsh,shp->thp", m, xh, precision=HI)
    y = y + xh * q["D"][None, :, None]
    y = y.reshape(S, d_in) * silu(z)
    y = rms_norm(y, q["norm"], eps)
    return x + mm(y, q["out_proj"], mode)


def forward(cfg: dict, params, tokens, mode: str = "f32", remat=False):
    """tokens (S,) -> logits (S, V) in float32."""
    c = cfg["config"]
    x = params["embed"][tokens].astype(F32)
    layer = lambda x, p: (_layer(c, p, x, mode), None)       # noqa: E731
    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, params["layers"]["p0"])
    x = rms_norm(x, params["final_norm"], c["norm_epsilon"])
    head = (params["embed"].T if c["tie_embeddings"] else params["lm_head"])
    return mm(x, head, mode)
