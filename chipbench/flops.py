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
  4 * heads * head_dim per position attended: a slot's cached tokens and
  the one it decodes).

Each family's file under ``families/`` counts its configurations by these
rules; the functions here dispatch to it.
"""
from __future__ import annotations

import families

COMPUTE_BYTES = 2


def matmul_params(cfg: dict) -> int:
    return families.of(cfg).matmul_params(cfg)


def decode_least(cfg: dict, step) -> tuple[float, float]:
    """(operations, bytes) one decode step needs at the least; ``step`` is
    the stats of its ``tally.serve.decode`` span (``families``)."""
    return families.of(cfg).decode_least(cfg, step)


def train_flops(cfg: dict, tokens: int) -> float:
    """Forward and backward of one step: 6 per matmul parameter per token
    (recomputation not counted)."""
    return 6.0 * matmul_params(cfg) * tokens
