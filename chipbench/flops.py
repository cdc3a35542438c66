"""Operations and bytes the algorithm needs, from a configuration's shapes.

Counted from the configuration file, never from the program, and on the
low side, so that a share of a roofline or a peak built on them cannot pass
100% unless the time leaves out work:

- matmul parameters: every projection weight a token passes through, the
  output head included, the embedding lookup not (unless the head is the
  tied embedding, counted once as the head);
- a decode step's least bytes: those weights once at the compute dtype
  (2 bytes each), plus the live state of the active slots at 2 bytes per
  number, read once (SSM states are also written once);
- a decode step's least operations: 2 per matmul parameter per active slot,
  plus the mixer's own work per token (4 per state number for an SSM layer,
  4 * heads * head_dim per cached position for attention).
"""
from __future__ import annotations

import weights

COMPUTE_BYTES = 2


def _ssm(c: dict):
    s = c["ssm_cfg"]
    d = c["d_model"]
    d_in = s["expand"] * d
    nh = d_in // s["headdim"]
    return d, d_in, nh, s["headdim"], s["d_state"], s["d_conv"]


def matmul_params(cfg: dict) -> int:
    c = cfg["config"]
    V = weights.embedding_rows(cfg)
    if cfg["family"] == "ssm":
        d, d_in, nh, _, N, _ = _ssm(c)
        layer = d * (2 * d_in + 2 * N + nh) + d_in * d
        return c["n_layer"] * layer + d * V
    E, F = c["hidden_size"], c["intermediate_size"]
    H, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    layer = 2 * E * H * D + 2 * E * KV * D + 3 * E * F
    return c["num_hidden_layers"] * layer + E * V


def decode_least(cfg: dict, active: int, kv_tokens: int
                 ) -> tuple[float, float]:
    """(operations, bytes) a decode step over ``active`` slots holding
    ``kv_tokens`` cached positions in all needs at the least."""
    c = cfg["config"]
    flops = 2.0 * matmul_params(cfg) * active
    nbytes = float(COMPUTE_BYTES * matmul_params(cfg))
    if cfg["family"] == "ssm":
        d, d_in, nh, P, N, k = _ssm(c)
        state = nh * P * N + (k - 1) * (d_in + 2 * N)
        flops += 4.0 * nh * P * N * c["n_layer"] * active
        nbytes += 2.0 * COMPUTE_BYTES * state * c["n_layer"] * active
    else:
        H, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
        L = c["num_hidden_layers"]
        flops += 4.0 * H * D * kv_tokens * L
        nbytes += 2.0 * COMPUTE_BYTES * KV * D * kv_tokens * L
    return flops, nbytes


def train_flops(cfg: dict, tokens: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter per token
    (recomputation not counted)."""
    return 6.0 * matmul_params(cfg) * tokens
