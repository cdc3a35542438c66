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

import families

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


def lm_specs(cfg: dict, layer: Dict, E: int, tied: bool) -> Dict:
    """A language model's tree around the specs of its layer stack: the
    embedding, the final norm and, unless tied, the output head."""
    V = embedding_rows(cfg)
    specs = {"embed": ((V, E), "normal", LOGIT_STD / math.sqrt(E) if tied
                       else 1.0),
             "final_norm": ((E,), "ones", 0.0),
             "layers": {"p0": layer}}
    if not tied:
        specs["lm_head"] = ((E, V), "normal", LOGIT_STD / math.sqrt(E))
    return specs


def param_specs(cfg: dict) -> Dict:
    """The parameter tree of ``cfg`` as specs, in the program's layout."""
    return families.of(cfg).param_specs(cfg)


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
