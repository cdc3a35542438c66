"""Mamba2 SSD chunk-scan Pallas kernel.

Grid (B, nc): batch parallel, chunk axis sequential (the SSD inter-chunk
recurrence) — Tally slices/preempts only the batch axis (the cluster-level
fallback of paper §6 for kernels with inter-block dependencies).
The running state h (NH, HD, DS) lives in VMEM scratch and persists across
the sequential chunk steps; the final state is also written out for
prefill->decode handoff.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.descriptor import BlockMap, KernelDescriptor


def make_ssd_body(L: int, NH: int, HD: int, DS: int):
    causal = None  # built lazily inside (traced constants are fine)

    def body(pids, x_ref, dt_ref, a_ref, b_ref, c_ref, dD_ref,
             y_ref, hout_ref, h_ref):
        c_idx = pids[1]

        @pl.when(c_idx == 0)
        def _():
            h_ref[...] = jnp.zeros_like(h_ref)

        xk = x_ref[0].astype(jnp.float32)                   # (L, NH, HD)
        dtk = dt_ref[0].astype(jnp.float32)                 # (L, NH)
        A = a_ref[...].astype(jnp.float32)                  # (NH,)
        bk = b_ref[0].astype(jnp.float32)                   # (L, DS)
        ck = c_ref[0].astype(jnp.float32)                   # (L, DS)
        D = dD_ref[...].astype(jnp.float32)                 # (NH,)
        h = h_ref[...]                                      # (NH, HD, DS)

        la = dtk * A[None]                                  # (L, NH)  (<0)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
        # inclusive cumsum as a lower-triangular matmul (Mosaic has no
        # cumsum); the chunk total is a plain sum
        cum = jnp.dot(tri.astype(jnp.float32), la,
                      preferred_element_type=jnp.float32)  # (L, NH)
        tot = jnp.sum(la, axis=0)                           # (NH,)

        cb = ck @ bk.T                                      # (L, L)
        delta = cum[:, None] - cum[None]                    # (t, s, NH)
        delta = jnp.where(tri[..., None], delta, -jnp.inf)
        g = cb[..., None] * jnp.exp(delta) * dtk[None]      # (t, s, NH)
        y = jnp.einsum("tsh,shd->thd", g, xk)               # (L, NH, HD)
        # incoming-state contribution
        y = y + jnp.einsum("th,td,hed->the", jnp.exp(cum), ck, h)
        y = y + xk * D[None, :, None]
        y_ref[0] = y.astype(y_ref.dtype)

        # state update
        w = jnp.exp(tot[None] - cum) * dtk                  # (L, NH)
        hc = jnp.einsum("th,thd,te->hde", w, xk, bk)        # (NH, HD, DS)
        h = jnp.exp(tot)[:, None, None] * h + hc
        h_ref[...] = h
        hout_ref[0] = h.astype(hout_ref.dtype)

    return body


def mamba2_scan_desc(B: int, S: int, NH: int, HD: int, DS: int,
                     chunk: int, dtype=jnp.float32, *,
                     interpret: Optional[bool] = None) -> KernelDescriptor:
    L = min(chunk, S)
    while S % L:
        L -= 1
    nc = S // L
    itemsize = jnp.dtype(dtype).itemsize
    return KernelDescriptor(
        name=f"ssd_{B}x{S}x{NH}x{HD}x{DS}",
        body=make_ssd_body(L, NH, HD, DS),
        grid=(B, nc),
        in_maps=(BlockMap((1, L, NH, HD), lambda b, c: (b, c, 0, 0)),
                 BlockMap((1, L, NH), lambda b, c: (b, c, 0)),
                 BlockMap((NH,), lambda b, c: (0,)),
                 BlockMap((1, L, DS), lambda b, c: (b, c, 0)),
                 BlockMap((1, L, DS), lambda b, c: (b, c, 0)),
                 BlockMap((NH,), lambda b, c: (0,))),
        out_maps=(BlockMap((1, L, NH, HD), lambda b, c: (b, c, 0, 0)),
                  BlockMap((1, NH, HD, DS), lambda b, c: (b, 0, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((B, S, NH, HD), dtype),
                   jax.ShapeDtypeStruct((B, NH, HD, DS), jnp.float32)),
        parallel_axes=(0,),
        scratch_shapes=(pltpu.VMEM((NH, HD, DS), jnp.float32),),
        flops=float(B * nc * (2 * L * L * DS + 2 * L * L * NH * HD
                              + 4 * L * NH * HD * DS)),
        bytes_accessed=float(B * S * (NH * HD * 2 + NH + 2 * DS) * itemsize),
        interpret=interpret,
        revisits_output=True,   # hout written every chunk (last wins)
    )
