"""Seeded weights in the served model's parameter layout, made on the device.

The benchmark, not the program, makes the weights, so the plain reference
can take the same ones from the same seed without taking anything the
program made. Scales follow common practice for a well-conditioned
network: inputs of each projection at unit variance, output projections
shrunk by ``sqrt(2 * layers)`` (GPT-2), Mamba-2's ``dt`` initialisation,
and an output head whose logits have a standard deviation of about 3, so
that greedy tokens are not near-ties everywhere as with tiny random logits.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_STD = 3.0


def key_from_seed(seed: int, tag: int) -> jax.Array:
    """A threefry key from all bits of ``seed`` (PRNGKey keeps only 32)."""
    words = np.random.SeedSequence([int(seed), int(tag)]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def embedding_rows(cfg: dict) -> int:
    c = cfg["config"]
    v = c["vocab_size"]
    m = c.get("pad_vocab_size_multiple", 1)
    return -(-v // m) * m


# (shape, init, std) per leaf; init in normal | ones | a_log | dt_bias
Spec = Tuple[Tuple[int, ...], str, float]


def _ssm_layer(c: dict, L: int) -> Dict[str, Spec]:
    d, s = c["d_model"], c["ssm_cfg"]
    d_in = s["expand"] * d
    nh = d_in // s["headdim"]
    k, ds = s["d_conv"], s["d_state"]
    out = 1.0 / math.sqrt(d_in) / math.sqrt(2 * L)
    return {
        "wz": ((L, d, d_in), "normal", 1 / math.sqrt(d)),
        "wx": ((L, d, d_in), "normal", 1 / math.sqrt(d)),
        "wB": ((L, d, ds), "normal", 1 / math.sqrt(d)),
        "wC": ((L, d, ds), "normal", 1 / math.sqrt(d)),
        "wdt": ((L, d, nh), "normal", 1 / math.sqrt(d)),
        "conv_x": ((L, k, d_in), "normal", 1 / math.sqrt(k)),
        "conv_B": ((L, k, ds), "normal", 1 / math.sqrt(k)),
        "conv_C": ((L, k, ds), "normal", 1 / math.sqrt(k)),
        "A_log": ((L, nh), "a_log", 0.0),
        "D": ((L, nh), "ones", 0.0),
        "dt_bias": ((L, nh), "dt_bias", 0.0),
        "norm": ((L, d_in), "ones", 0.0),
        "out_proj": ((L, d_in, d), "normal", out),
    }


def param_specs(cfg: dict) -> Dict:
    """The parameter tree of ``cfg`` as specs, in the program's layout."""
    c = cfg["config"]
    V = embedding_rows(cfg)
    if cfg["family"] == "ssm":
        L, E = c["n_layer"], c["d_model"]
        layer = {"ln1": ((L, E), "ones", 0.0), "ssm": _ssm_layer(c, L)}
        tied = c["tie_embeddings"]
    elif cfg["family"] == "dense":
        L, E = c["num_hidden_layers"], c["hidden_size"]
        H, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
        F = c["intermediate_size"]
        shrink = 1.0 / math.sqrt(2 * L)
        layer = {
            "ln1": ((L, E), "ones", 0.0),
            "attn": {"wq": ((L, E, H, D), "normal", 1 / math.sqrt(E)),
                     "wk": ((L, E, KV, D), "normal", 1 / math.sqrt(E)),
                     "wv": ((L, E, KV, D), "normal", 1 / math.sqrt(E)),
                     "wo": ((L, H, D, E), "normal",
                            shrink / math.sqrt(H * D))},
            "ln2": ((L, E), "ones", 0.0),
            "ffn": {"wi": ((L, E, F), "normal", 1 / math.sqrt(E)),
                    "wg": ((L, E, F), "normal", 1 / math.sqrt(E)),
                    "wo": ((L, F, E), "normal", shrink / math.sqrt(F))},
        }
        tied = c["tie_word_embeddings"]
    else:
        raise ValueError(f"unknown family {cfg['family']!r}")
    specs = {"embed": ((V, E), "normal", LOGIT_STD / math.sqrt(E) if tied
                       else 1.0),
             "final_norm": ((E,), "ones", 0.0),
             "layers": {"p0": layer}}
    if not tied:
        specs["lm_head"] = ((E, V), "normal", LOGIT_STD / math.sqrt(E))
    return specs


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def _init(spec: Spec, key, dtype):
    shape, kind, std = spec
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "a_log":                      # A in [-16, -1], as Mamba-2
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if kind == "dt_bias":                    # softplus^-1 of dt in [1e-3, 0.1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(cfg: dict, key, dtype):
    """All weights of ``cfg`` from ``key``. Call it under ``jax.jit`` (see
    ``params_fn``) so the whole tree is made on the device in one call."""
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init(s, k, dtype) for s, k in zip(leaves, keys)])


def params_fn(cfg: dict):
    """A jitted ``key -> params`` for ``cfg``, in its ``param_dtype``."""
    dtype = jnp.dtype(cfg["param_dtype"])
    return jax.jit(lambda key: make_params(cfg, key, dtype))


def shapes(cfg: dict):
    return jax.tree.map(lambda s: s[0], param_specs(cfg), is_leaf=_is_spec)
