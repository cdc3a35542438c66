"""Tiled matmul Pallas kernel (compiled on TPU, interpreted on CPU).

Grid (nm, nn, nk): (m, n) parallel — the Tally-schedulable blocks — and k
sequential (accumulation into the output tile, MXU-aligned block shapes).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.descriptor import BlockMap, KernelDescriptor, pick_block


def matmul_body(pids, a_ref, b_ref, o_ref):
    k = pids[2]

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)


def matmul_desc(M: int, K: int, N: int, dtype=jnp.float32, *,
                bm: int = 128, bk: int = 512, bn: int = 128,
                interpret: Optional[bool] = None) -> KernelDescriptor:
    # (bm, bk), (bk, bn), (bm, bn) blocks: rows align to 8, lanes to 128
    bm = pick_block(M, bm, 8)
    bk = pick_block(K, bk, 128)
    bn = pick_block(N, bn, 128)
    grid = (M // bm, N // bn, K // bk)
    itemsize = jnp.dtype(dtype).itemsize
    return KernelDescriptor(
        name=f"matmul_{M}x{K}x{N}",
        body=matmul_body,
        grid=grid,
        in_maps=(BlockMap((bm, bk), lambda i, j, k: (i, k)),
                 BlockMap((bk, bn), lambda i, j, k: (k, j))),
        out_maps=(BlockMap((bm, bn), lambda i, j, k: (i, j)),),
        out_shape=(jax.ShapeDtypeStruct((M, N), jnp.float32),),
        parallel_axes=(0, 1),
        flops=2.0 * M * N * K,
        bytes_accessed=float((M * K + K * N) * itemsize + M * N * 4),
        interpret=interpret,
        revisits_output=True,
    )
