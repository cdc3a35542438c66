"""Mamba-2 (SSD) language models: the file's keys are those of
state-spaces/mamba2-* ``config.json`` (``d_model``, ``n_layer``,
``ssm_cfg``)."""
from __future__ import annotations

import math
from typing import Dict

import flops
import weights
from families import common
from weights import Spec


def _ssm(c: dict):
    s = c["ssm_cfg"]
    d = c["d_model"]
    d_in = s["expand"] * d
    nh = d_in // s["headdim"]
    return d, d_in, nh, s["headdim"], s["d_state"], s["d_conv"]


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
    c = cfg["config"]
    L, E = c["n_layer"], c["d_model"]
    layer = {"ln1": ((L, E), "ones", 0.0), "ssm": _ssm_layer(c, L)}
    return weights.lm_specs(cfg, layer, E, c["tie_embeddings"])


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig, SSMConfig
    c = cfg["config"]
    s = c["ssm_cfg"]
    nh = s["expand"] * c["d_model"] // s["headdim"]
    return ModelConfig(
        family="ssm", num_layers=c["n_layer"], d_model=c["d_model"],
        num_heads=nh, num_kv_heads=nh, d_ff=0,
        tie_embeddings=c["tie_embeddings"], rms_eps=c["norm_epsilon"],
        ssm=SSMConfig(d_state=s["d_state"], expand=s["expand"],
                      head_dim=s["headdim"], conv_kernel=s["d_conv"],
                      chunk_size=s["chunk_size"]), **common(cfg))


def matmul_params(cfg: dict) -> int:
    c = cfg["config"]
    d, d_in, nh, _, N, _ = _ssm(c)
    layer = d * (2 * d_in + 2 * N + nh) + d_in * d
    return c["n_layer"] * layer + d * weights.embedding_rows(cfg)


def decode_least(cfg: dict, step) -> tuple[float, float]:
    """Weights once; each active slot's SSM and conv state read and written
    once, and 4 operations per SSM state number."""
    c = cfg["config"]
    active = step["active"]
    P = matmul_params(cfg)
    d, d_in, nh, hd, N, k = _ssm(c)
    state = nh * hd * N + (k - 1) * (d_in + 2 * N)
    ops = 2.0 * P * active + 4.0 * nh * hd * N * c["n_layer"] * active
    nbytes = flops.COMPUTE_BYTES * (
        float(P) + 2.0 * state * c["n_layer"] * active)
    return ops, nbytes
