"""Dense decoder-only transformers with grouped-query attention and a
SwiGLU MLP: the file's keys are those of a Llama/Mistral ``config.json``
(``hidden_size``, ``num_hidden_layers``, ``head_dim``, ...)."""
from __future__ import annotations

import math
from typing import Dict

import flops
import weights
from families import common


def param_specs(cfg: dict) -> Dict:
    c = cfg["config"]
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
    return weights.lm_specs(cfg, layer, E, c["tie_word_embeddings"])


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    c = cfg["config"]
    return ModelConfig(
        family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], head_dim=c["head_dim"],
        rope_theta=c["rope_theta"], rms_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], **common(cfg))


def matmul_params(cfg: dict) -> int:
    c = cfg["config"]
    E, F = c["hidden_size"], c["intermediate_size"]
    H, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    layer = 2 * E * H * D + 2 * E * KV * D + 3 * E * F
    return (c["num_hidden_layers"] * layer
            + E * weights.embedding_rows(cfg))


def decode_least(cfg: dict, step) -> tuple[float, float]:
    """Weights once; each slot's cached keys and values read once. A slot
    attends over its ``kv_tokens`` share and the token it decodes, so the
    positions attended are ``kv_tokens + active``."""
    c = cfg["config"]
    H, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    L = c["num_hidden_layers"]
    P = matmul_params(cfg)
    positions = step["kv_tokens"] + step["active"]
    ops = 2.0 * P * step["active"] + 4.0 * H * D * positions * L
    nbytes = flops.COMPUTE_BYTES * (float(P) + 2.0 * KV * D * positions * L)
    return ops, nbytes
