"""Flash attention Pallas kernel (online softmax, causal-capable).

Layout: q (BH, S, D); k, v (B*KVH, T, D). Grid (BH, nq) — both axes
parallel (each (head, q-block) tile is independent); the KV sweep is a
``fori_loop`` inside the tile with running (m, l, acc) — the VMEM working
set is one q block + one kv block, flash-style.
GQA: the K/V index maps divide the head index by the group size so grouped
query heads share a KV block without materializing repeats.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.descriptor import BlockMap, KernelDescriptor, pick_block


def make_flash_body(bq: int, bk: int, T: int, D: int, causal: bool,
                    q_offset: int = 0):
    nkb = T // bk
    scale = 1.0 / math.sqrt(D)

    def body(pids, q_ref, k_ref, v_ref, o_ref):
        j = pids[1]
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, D)
        qpos = q_offset + j * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)

        def kv_step(t, carry):
            m, l, acc = carry
            kb = k_ref[0, pl.ds(t * bk, bk), :].astype(jnp.float32)
            vb = v_ref[0, pl.ds(t * bk, bk), :].astype(jnp.float32)
            s = q @ kb.T                                     # (bq, bk)
            if causal:
                kpos = t * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[:, None]), 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[:, None] + p @ vb
            return m_new, l, acc

        m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        a0 = jnp.zeros((bq, D), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, nkb, kv_step, (m0, l0, a0))
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)

    return body


def flash_attention_desc(BH: int, S: int, T: int, D: int, group: int,
                         dtype=jnp.float32, *, causal: bool = True,
                         q_offset: int = 0, bq: int = 256, bk: int = 512,
                         interpret: Optional[bool] = None) -> KernelDescriptor:
    # q/out blocks (1, bq, D) and the in-kernel K/V windows (bk, D) slice
    # the sublane dim, which aligns to 8
    bq = pick_block(S, bq, 8)
    bk = pick_block(T, bk, 8)
    grid = (BH, S // bq)
    itemsize = jnp.dtype(dtype).itemsize
    BKV = BH // group
    return KernelDescriptor(
        name=f"flash_{BH}x{S}x{T}x{D}{'_c' if causal else ''}",
        body=make_flash_body(bq, bk, T, D, causal, q_offset),
        grid=grid,
        in_maps=(BlockMap((1, bq, D), lambda i, j: (i, j, 0)),
                 BlockMap((1, T, D), lambda i, j: (i // group, 0, 0)),
                 BlockMap((1, T, D), lambda i, j: (i // group, 0, 0))),
        out_maps=(BlockMap((1, bq, D), lambda i, j: (i, j, 0)),),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), dtype),),
        parallel_axes=(0, 1),
        flops=4.0 * BH * S * T * D * (0.5 if causal else 1.0),
        bytes_accessed=float((BH * S * D * 2 + 2 * BKV * T * D) * itemsize),
        interpret=interpret,
    )
