"""Served parameters: ``TransformerLM.serving_params`` casts every leaf the
model reads only through ``.astype(cfg.dtype)`` once, and prefill and
decode then give the same bits as from the float32 tree they came from."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import F32_PARAMS, build_model, pad_cache

# one reduced preset per family the model serves
SERVED = {"dense": "qwen2.5-14b", "moe": "qwen3-moe-30b-a3b",
          "ssm": "mamba2-130m", "hybrid": "jamba-1.5-large-398b",
          "vlm": "qwen2-vl-7b", "audio": "whisper-base"}
B, S = 2, 12


def _bits(tree):
    return [(str(x.dtype), x.shape, np.asarray(x).tobytes())
            for x in jax.tree.leaves(tree)]


def _params(model):
    """Initial parameters moved off their exact ones and zeros, so that a
    float32 leaf cast to bf16 by mistake changes the result."""
    params = model.init(jax.random.PRNGKey(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _prefill_inputs(cfg, rng):
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, S)),
                         jnp.int32)
    kw = {}
    if cfg.encoder_layers:
        kw["encoder_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_audio_frames, cfg.d_model)),
            cfg.dtype)
    if cfg.mrope_sections is not None:
        kw["positions"] = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                           (3, B, S))
    return tokens, kw


@pytest.mark.parametrize("family", sorted(SERVED))
def test_serving_params_give_the_same_bits(family):
    cfg = get_config(SERVED[family]).reduced()
    assert cfg.family == family and cfg.dtype != cfg.param_dtype
    model = build_model(cfg)
    params = _params(model)
    served = model.serving_params(params)

    kept = 0
    for path, x in jax.tree_util.tree_leaves_with_path(served):
        if path[-1].key in F32_PARAMS:
            assert x.dtype == jnp.float32, path
            kept += 1
        else:
            assert x.dtype == cfg.dtype, path
    assert kept and kept < len(jax.tree.leaves(served))

    rng = np.random.default_rng(0)
    tokens, kw = _prefill_inputs(cfg, rng)
    prefill = jax.jit(model.prefill)
    want = prefill(params, tokens, **kw)
    got = prefill(served, tokens, **kw)
    assert _bits(got) == _bits(want)        # logits and cache

    cache = pad_cache(want[1], S + 4)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, 1)),
                      jnp.int32)
    lengths = jnp.asarray([S, S - 5], jnp.int32)    # per-slot lengths
    decode = jax.jit(model.decode_step)
    assert _bits(decode(served, tok, cache, lengths)) == \
        _bits(decode(params, tok, cache, lengths))


def test_f32_compute_serves_its_tree_as_it_is():
    cfg = dataclasses.replace(get_config(SERVED["dense"]).reduced(),
                              dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    served = model.serving_params(params)
    assert jax.tree.structure(served) == jax.tree.structure(params)
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(params)))
