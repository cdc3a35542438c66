"""Pieces the plain references share: matmuls, norms, the loss, AdamW.

Everything is float32 at ``Precision.HIGHEST``, written from the published
descriptions, and imports nothing of the program. ``mode="fp8"`` is the
output check's control, the step below the bfloat16 compute the
configurations state: every matmul's operands are rounded to fp8 (e4m3;
weights scaled per output column, activations per row). In training the
rounding is passed through straight (its gradient is taken as 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(x, w, mode: str = "f32"):
    """x (..., K) @ w (K, N) in float32; ``fp8`` rounds both operands to
    e4m3, scaled per row and per column."""
    x, w = x.astype(F32), w.astype(F32)
    if mode == "fp8":
        x, w = _f8(x, -1), _f8(w, 0)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over all positions."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def adamw(params, grads, state, opt: dict):
    """One AdamW step (decoupled weight decay on every leaf) after clipping
    the gradient to a global norm of ``grad_clip``. ``state`` is
    ``(step, m, v)``; returns ``(params, state, clipped_grads)``."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    if opt["grad_clip"] > 0:
        scale = jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
    step, m, v = state
    step = step + 1
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, a, b):
        return p - opt["lr"] * ((a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                                + opt["weight_decay"] * p)
    params = jax.tree.map(upd, params, m, v)
    return params, (step, m, v), grads
