"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block in pure JAX.

Two execution paths with identical math:
  - chunked SSD via ``lax.scan`` over chunks (XLA path, used by dry-run), and
  - the Pallas chunk-scan kernel in ``repro.kernels`` when ``cfg.use_pallas``.

Recurrence (per head h, hidden dim d, state dim n):
    h_t = a_t * h_{t-1} + dt_t * x_t (x) B_t          h in R^{hd x ds}
    y_t = h_t @ C_t + D * x_t
with a_t = exp(dt_t * A), A = -exp(A_log) < 0.

The chunked algorithm splits the sequence into chunks of length L:
  intra-chunk  : (C_t . B_s) exp(cum_t - cum_s) dt_s  for s <= t  (L x L matmul)
  chunk state  : sum_s exp(cum_L - cum_s) dt_s x_s (x) B_s
  inter-chunk  : scan over chunk states; y_inter = exp(cum_t) C_t @ H_c
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain
from repro.models.common import P


# Leaves read in float32 (the recurrence's A, D and dt bias, the gated
# norm's scale); every other leaf is read through ``.astype(x.dtype)``.
F32_PARAMS = frozenset({"A_log", "D", "dt_bias", "norm"})


def mamba2_specs(cfg) -> Dict[str, P]:
    d = cfg.d_model
    s = cfg.ssm
    d_in = s.expand * d
    nh = s.num_heads(d)
    k = s.conv_kernel
    return {
        "wz": P((d, d_in), ("embed", "mlp")),
        "wx": P((d, d_in), ("embed", "mlp")),
        "wB": P((d, s.d_state), ("embed", None)),
        "wC": P((d, s.d_state), ("embed", None)),
        "wdt": P((d, nh), ("embed", "ssm_heads")),
        "conv_x": P((k, d_in), (None, "mlp")),
        "conv_B": P((k, s.d_state), (None, None)),
        "conv_C": P((k, s.d_state), (None, None)),
        "A_log": P((nh,), ("ssm_heads",), init="small_log"),
        "D": P((nh,), ("ssm_heads",), init="ones"),
        "dt_bias": P((nh,), ("ssm_heads",), init="zeros"),
        "norm": P((d_in,), ("mlp",), init="ones"),
        "out_proj": P((d_in, d), ("mlp", "embed")),
    }


def _chunk_len(seq: int, target: int) -> int:
    c = max(1, min(seq, target))
    while seq % c:
        c -= 1
    return c


def _causal_conv(x: jax.Array, w: jax.Array,
                 state: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv1d. x: (B, S, C), w: (K, C).

    If `state` (B, K-1, C) is given it is prepended (decode / chunked
    prefill); otherwise zero left-padding.
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                  # (B, S+K-1, C)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None]
              for i in range(k))
    return out


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, D: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None, unroll: bool = False,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x:  (B, S, NH, HD)   dt: (B, S, NH)   A: (NH,) negative
    Bm: (B, S, DS)       Cm: (B, S, DS)   D: (NH,)
    h0: optional incoming state (B, NH, HD, DS)
    Returns (y (B,S,NH,HD), h_final (B,NH,HD,DS)); fp32 internally.
    """
    Bsz, S, NH, HD = x.shape
    DS = Bm.shape[-1]
    L = _chunk_len(S, chunk)
    nc = S // L

    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)

    xc = x.reshape(Bsz, nc, L, NH, HD)
    dtc = dt.reshape(Bsz, nc, L, NH)
    Bc = Bm.reshape(Bsz, nc, L, DS)
    Cc = Cm.reshape(Bsz, nc, L, DS)

    la = dtc * A[None, None, None]                     # log a: (B,nc,L,NH) <0
    cum = jnp.cumsum(la, axis=2)                       # inclusive cumsum
    total = cum[:, :, -1]                              # (B,nc,NH)

    if h0 is None:
        h0 = jnp.zeros((Bsz, NH, HD, DS), jnp.float32)
    else:
        h0 = h0.astype(jnp.float32)

    causal = jnp.tril(jnp.ones((L, L), jnp.float32))   # (t, s) s<=t

    def chunk_step(h, inp):
        xk, dtk, bk, ck, cumk, lak, totk = inp
        # xk (B,L,NH,HD) dtk (B,L,NH) bk/ck (B,L,DS) cumk (B,L,NH) totk (B,NH)
        # intra-chunk: mask the exponent pre-exp (s>t would overflow exp)
        cb = jnp.einsum("btd,bsd->bts", ck, bk)        # (B,L,L)
        delta = cumk[:, :, None] - cumk[:, None]       # (B,t,s,NH)
        delta = jnp.where(causal[None, :, :, None] > 0, delta, -jnp.inf)
        g = cb[..., None] * jnp.exp(delta)
        gx = g * dtk[:, None]                          # weight by dt_s
        y = jnp.einsum("btsh,bshd->bthd", gx, xk)      # (B,L,NH,HD)
        # inter-chunk (incoming state):
        y = y + jnp.einsum("bth,btd,bhed->bthe",
                           jnp.exp(cumk), ck, h)       # note: e indexes HD
        # chunk state update:
        w = jnp.exp(totk[:, None] - cumk) * dtk        # (B,L,NH)
        hc = jnp.einsum("bth,bthd,bte->bhde", w, xk, bk)   # (B,NH,HD,DS)
        h = jnp.exp(totk)[:, :, None, None] * h + hc
        return h, y

    xs = (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
          Bc.transpose(1, 0, 2, 3), Cc.transpose(1, 0, 2, 3),
          cum.transpose(1, 0, 2, 3), la.transpose(1, 0, 2, 3),
          total.transpose(1, 0, 2))
    # unroll=True: scan-free for exact dry-run cost accounting
    h_final, ys = lax.scan(chunk_step, h0, xs, unroll=True if unroll else 1)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, NH, HD)
    y = y + x * D[None, None, :, None]
    return y, h_final


def ssd_decode(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, D: jax.Array, h: jax.Array,
               ) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD step.

    x (B,NH,HD), dt (B,NH), Bm/Cm (B,DS), h (B,NH,HD,DS) -> (y, h')
    """
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    a = jnp.exp(dt * A[None])                              # (B,NH)
    dbx = jnp.einsum("bh,bhd,be->bhde", dt, x, Bm.astype(jnp.float32))
    h = a[..., None, None] * h + dbx
    y = jnp.einsum("bhde,be->bhd", h, Cm.astype(jnp.float32))
    y = y + x * D[None, :, None]
    return y, h


def mamba2_block(params, x: jax.Array, cfg, *,
                 state: Optional[Tuple[jax.Array, jax.Array]] = None,
                 want_state: bool = False):
    """Mamba2 mixer. x: (B, S, E).

    state = (conv_state (B,K-1,CD), ssm_state (B,NH,HD,DS)) for decode (S==1)
    or chunked prefill continuation. Returns (y, new_state | None).
    """
    s = cfg.ssm
    B, S, E = x.shape
    d_in = s.expand * cfg.d_model
    nh = s.num_heads(cfg.d_model)
    hd = s.head_dim
    ds = s.d_state
    k = s.conv_kernel
    dt_ = x.dtype

    z = x @ params["wz"].astype(dt_)                       # (B,S,d_in)
    xin = x @ params["wx"].astype(dt_)
    Bp = x @ params["wB"].astype(dt_)                      # (B,S,DS)
    Cp = x @ params["wC"].astype(dt_)
    dt = x @ params["wdt"].astype(dt_)                     # (B,S,NH)
    z = constrain(z, "batch", None, "mlp")
    xin = constrain(xin, "batch", None, "mlp")

    xBC = jnp.concatenate([xin, Bp, Cp], axis=-1)          # (B,S,CD)
    conv_w = jnp.concatenate(
        [params["conv_x"], params["conv_B"], params["conv_C"]],
        axis=-1).astype(dt_)                               # (K, CD)

    conv_state = state[0] if state is not None else None
    xBC_conv = jax.nn.silu(_causal_conv(xBC, conv_w, conv_state))
    new_conv_state = None
    if want_state or state is not None:
        hist = jnp.concatenate(
            [conv_state if conv_state is not None
             else jnp.zeros((B, k - 1, xBC.shape[-1]), dt_), xBC], axis=1)
        new_conv_state = hist[:, -(k - 1):, :]

    xs = xBC_conv[..., :d_in]
    Bs = xBC_conv[..., d_in:d_in + ds]
    Cs = xBC_conv[..., d_in + ds:]

    A = -jnp.exp(params["A_log"].astype(jnp.float32))      # (NH,)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))

    xh = xs.reshape(B, S, nh, hd)
    ssm_state = state[1] if state is not None else None

    if S == 1 and ssm_state is not None:                   # decode fast path
        y, h = ssd_decode(xh[:, 0], dt[:, 0], A, Bs[:, 0], Cs[:, 0],
                          params["D"].astype(jnp.float32), ssm_state)
        y = y[:, None]                                     # (B,1,NH,HD)
    elif cfg.use_pallas and ssm_state is None:
        from repro.kernels import ops as kops
        y, h = kops.mamba2_scan(xh, dt, A, Bs, Cs,
                                params["D"].astype(jnp.float32),
                                chunk=s.chunk_size)
    else:
        y, h = ssd_chunked(xh, dt, A, Bs, Cs,
                           params["D"].astype(jnp.float32),
                           chunk=s.chunk_size, h0=ssm_state,
                           unroll=cfg.exact_costs)

    y = y.reshape(B, S, d_in).astype(dt_)
    # gated RMSNorm (mamba2: norm(y * silu(z)))
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    y = (yf * lax.rsqrt(var + cfg.rms_eps)
         * params["norm"].astype(jnp.float32)).astype(dt_)
    y = constrain(y, "batch", None, "mlp")
    out = y @ params["out_proj"].astype(dt_)

    new_state = None
    if want_state or state is not None:
        new_state = (new_conv_state, h.astype(jnp.float32))
    return out, new_state
