"""Unified decoder(-encoder) model covering all assigned architecture families.

One implementation parameterized by ``ModelConfig``:
  dense / moe            : homogeneous stack, scan over layers
  ssm (mamba2)           : mixer-only blocks, scan over layers
  hybrid (jamba)         : scan over *periods* of ``attn_every`` layers; each
                           period holds its own per-position param subtrees
  audio (whisper)        : encoder stack (non-causal) + decoder w/ cross-attn
  vlm (qwen2-vl)         : M-RoPE positions threaded through attention

The layer stack is always a ``lax.scan`` over stacked params (compact HLO,
compile time independent of depth); heterogeneous archs scan over periods
with a static Python loop over in-period positions.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import moe as moe_lib
from repro.models import mamba2 as m2
from repro.models.common import (P, axes_from_specs, init_from_specs,
                                 shapes_from_specs, stacked)
from repro.models.layers import attention_block, rms_norm, swiglu_mlp


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def layer_period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.hybrid is not None:
        p = _lcm(p, cfg.hybrid.attn_every)
    if cfg.moe is not None:
        p = _lcm(p, cfg.moe.every)
    return p


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def _attn_specs(cfg: ModelConfig) -> Dict[str, P]:
    E, H, D, KVH = cfg.d_model, cfg.num_heads, cfg.head_dim_, cfg.num_kv_heads
    s = {
        "wq": P((E, H, D), ("embed", "heads", None)),
        "wk": P((E, KVH, D), ("embed", "kv_heads", None)),
        "wv": P((E, KVH, D), ("embed", "kv_heads", None)),
        "wo": P((H, D, E), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = P((H, D), ("heads", None), init="zeros")
        s["bk"] = P((KVH, D), ("kv_heads", None), init="zeros")
        s["bv"] = P((KVH, D), ("kv_heads", None), init="zeros")
    return s


def _mlp_specs(cfg: ModelConfig) -> Dict[str, P]:
    E, F = cfg.d_model, cfg.d_ff
    return {
        "wi": P((E, F), ("embed", "mlp")),
        "wg": P((E, F), ("embed", "mlp")),
        "wo": P((F, E), ("mlp", "embed")),
    }


# Leaves the model reads in float32: the RMS-norm scales (``rms_norm``
# casts its scale to f32) and the SSM's own. Every other leaf is read
# only through ``.astype(cfg.dtype)``, so ``serving_params`` casts it once.
F32_PARAMS = frozenset({"ln1", "ln2", "ln_x", "final_norm", "norm"}
                       ) | m2.F32_PARAMS


class TransformerLM:
    """Model object: specs + pure forward fns (train / prefill / decode)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.period = layer_period(cfg)
        assert cfg.num_layers % self.period == 0, (
            f"{cfg.name}: num_layers={cfg.num_layers} not divisible by "
            f"period={self.period}")
        self.n_periods = cfg.num_layers // self.period
        # static per-position structure
        self.mixer_kind = [
            "attn" if cfg.is_attention_layer(p) else "ssm"
            for p in range(self.period)]
        self.ffn_kind = [
            None if cfg.family == "ssm"
            else ("moe" if cfg.is_moe_layer(p) else "dense")
            for p in range(self.period)]
        self.attn_per_period = sum(k == "attn" for k in self.mixer_kind)
        self.ssm_per_period = sum(k == "ssm" for k in self.mixer_kind)
        self.n_attn = self.attn_per_period * self.n_periods
        self.n_ssm = self.ssm_per_period * self.n_periods

    # -- specs ---------------------------------------------------------------

    def _sublayer_specs(self, p: int) -> Dict[str, Any]:
        cfg = self.cfg
        d: Dict[str, Any] = {"ln1": P((cfg.d_model,), (None,), init="ones")}
        if self.mixer_kind[p] == "attn":
            d["attn"] = _attn_specs(cfg)
            if cfg.encoder_layers:
                d["ln_x"] = P((cfg.d_model,), (None,), init="ones")
                d["xattn"] = _attn_specs(cfg)
        else:
            d["ssm"] = m2.mamba2_specs(cfg)
        if self.ffn_kind[p] is not None:
            d["ln2"] = P((cfg.d_model,), (None,), init="ones")
            d["ffn"] = (moe_lib.moe_specs(cfg) if self.ffn_kind[p] == "moe"
                        else _mlp_specs(cfg))
        return d

    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        E, V = cfg.d_model, cfg.vocab_size
        s: Dict[str, Any] = {
            "embed": P((V, E), ("vocab", "embed"), init="fan_last"),
            "final_norm": P((E,), (None,), init="ones"),
            "layers": {
                f"p{p}": stacked(self.n_periods, self._sublayer_specs(p))
                for p in range(self.period)},
        }
        if not cfg.tie_embeddings:
            s["lm_head"] = P((E, V), ("embed", "vocab"))
        if cfg.encoder_layers:
            enc_layer = {
                "ln1": P((E,), (None,), init="ones"),
                "attn": _attn_specs(cfg),
                "ln2": P((E,), (None,), init="ones"),
                "ffn": _mlp_specs(cfg),
            }
            s["encoder"] = {
                "layers": stacked(cfg.encoder_layers, enc_layer),
                "norm": P((E,), (None,), init="ones"),
            }
        return s

    def init(self, rng) -> Dict[str, Any]:
        return init_from_specs(self.specs(), rng, self.cfg.param_dtype)

    def param_shapes(self):
        return shapes_from_specs(self.specs(), self.cfg.param_dtype)

    def param_axes(self):
        return axes_from_specs(self.specs())

    def serving_params(self, params):
        """The parameter tree as it is served: each leaf outside
        ``F32_PARAMS`` cast to ``cfg.dtype`` once, the rest as they are.
        Prefill and decode then compute with the same bits as from
        ``params``, without writing a cast copy of every weight per call.
        Leaves already in ``cfg.dtype`` (f32 compute) come back as they
        are."""
        dt = jnp.dtype(self.cfg.dtype)

        def serve(path, x):
            if path[-1].key in F32_PARAMS or x.dtype == dt:
                return x
            return x.astype(dt)
        return jax.tree_util.tree_map_with_path(serve, params)

    # -- encoder (audio) ------------------------------------------------------

    def encode(self, params, embeds: jax.Array) -> jax.Array:
        """embeds: (B, F, E) precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg

        def step(x, lp):
            h, _ = attention_block(lp["attn"],
                                   rms_norm(x, lp["ln1"], cfg.rms_eps),
                                   cfg, causal=False)
            x = x + h
            x = x + swiglu_mlp(lp["ffn"],
                               rms_norm(x, lp["ln2"], cfg.rms_eps), cfg)
            return x, None

        if cfg.unroll_stack:
            x = embeds.astype(cfg.dtype)
            lps = params["encoder"]["layers"]
            for i in range(cfg.encoder_layers):
                x, _ = step(x, jax.tree.map(lambda a: a[i], lps))
        else:
            x, _ = lax.scan(step, embeds.astype(cfg.dtype),
                            params["encoder"]["layers"])
        return rms_norm(x, params["encoder"]["norm"], cfg.rms_eps)

    # -- decoder stack ---------------------------------------------------------

    def _stack(self, params, x, *, positions=None, cache=None,
               cache_index=None, enc_out=None, collect_cache=False,
               remat=False):
        """Run the layer stack.

        Returns (x, aux_loss, new_cache_tree|None). `cache` is the pytree
        from ``kv_cache_specs`` (leading dim n_attn / n_ssm / num_layers);
        when given, runs decode (S==1).
        """
        cfg = self.cfg
        decode = cache is not None
        per = self.period
        npd = self.n_periods
        app, spp = self.attn_per_period, self.ssm_per_period

        xs: Dict[str, Any] = {"params": params["layers"]}
        if decode:
            c = dict(cache)
            if "k" in c:
                xs["k"] = c["k"].reshape((npd, app) + c["k"].shape[1:])
                xs["v"] = c["v"].reshape((npd, app) + c["v"].shape[1:])
            if "ssm_state" in c:
                xs["ssm_state"] = c["ssm_state"].reshape(
                    (npd, spp) + c["ssm_state"].shape[1:])
                xs["conv_state"] = c["conv_state"].reshape(
                    (npd, spp) + c["conv_state"].shape[1:])
            if "cross_k" in c:
                xs["cross_k"] = c["cross_k"].reshape(
                    (npd, app) + c["cross_k"].shape[1:])
                xs["cross_v"] = c["cross_v"].reshape(
                    (npd, app) + c["cross_v"].shape[1:])

        def period_step(carry, xs_t):
            x, aux = carry
            ys: Dict[str, Any] = {}
            ai = si = 0
            for p in range(per):
                lp = xs_t["params"][f"p{p}"]
                h = rms_norm(x, lp["ln1"], cfg.rms_eps)
                if self.mixer_kind[p] == "attn":
                    kv_cache = ((xs_t["k"][ai], xs_t["v"][ai])
                                if decode else None)
                    h, ex = attention_block(
                        lp["attn"], h, cfg, positions=positions,
                        cache=kv_cache, cache_index=cache_index)
                    if decode:
                        ys.setdefault("k", []).append(ex["cache"][0])
                        ys.setdefault("v", []).append(ex["cache"][1])
                    elif collect_cache:
                        ys.setdefault("k", []).append(ex["kv"][0])
                        ys.setdefault("v", []).append(ex["kv"][1])
                    x = x + h
                    if cfg.encoder_layers:
                        hx = rms_norm(x, lp["ln_x"], cfg.rms_eps)
                        if decode:
                            ckv = (xs_t["cross_k"][ai], xs_t["cross_v"][ai])
                        else:
                            dt = x.dtype
                            ck = jnp.einsum("bfe,ehd->bfhd", enc_out,
                                            lp["xattn"]["wk"].astype(dt))
                            cv = jnp.einsum("bfe,ehd->bfhd", enc_out,
                                            lp["xattn"]["wv"].astype(dt))
                            ckv = (ck, cv)
                            if collect_cache:
                                ys.setdefault("cross_k", []).append(ck)
                                ys.setdefault("cross_v", []).append(cv)
                        hx, _ = attention_block(lp["xattn"], hx, cfg,
                                                encoder_kv=ckv)
                        x = x + hx
                    ai += 1
                else:  # ssm mixer
                    st = ((xs_t["conv_state"][si], xs_t["ssm_state"][si])
                          if decode else None)
                    h, new_st = m2.mamba2_block(
                        lp["ssm"], h, cfg, state=st,
                        want_state=collect_cache)
                    if new_st is not None and (decode or collect_cache):
                        ys.setdefault("conv_state", []).append(new_st[0])
                        ys.setdefault("ssm_state", []).append(new_st[1])
                    x = x + h
                    si += 1
                if self.ffn_kind[p] is not None:
                    h = rms_norm(x, lp["ln2"], cfg.rms_eps)
                    if self.ffn_kind[p] == "moe":
                        h, al = moe_lib.moe_block(lp["ffn"], h, cfg)
                        aux = aux + al
                    else:
                        h = swiglu_mlp(lp["ffn"], h, cfg)
                    x = x + h
                x = constrain(x, "batch", "seq", "embed")
            ys_st = {k: jnp.stack(v) for k, v in ys.items()}
            return (x, aux), ys_st

        step = jax.checkpoint(period_step) if remat else period_step
        if cfg.unroll_stack:
            # dry-run cost probe: python loop (exact cost_analysis)
            carry = (x, jnp.float32(0.0))
            ys_list = []
            for i in range(npd):
                xs_i = jax.tree.map(lambda a: a[i], xs)
                carry, ys_i = step(carry, xs_i)
                ys_list.append(ys_i)
            (x, aux) = carry
            if ys_list and ys_list[0]:
                ys = jax.tree.map(lambda *ls: jnp.stack(ls), *ys_list)
            else:
                ys = {}
        else:
            (x, aux), ys = lax.scan(step, (x, jnp.float32(0.0)), xs)

        new_cache = None
        if decode or collect_cache:
            new_cache = {}
            for k, v in ys.items():
                # (npd, per_period, ...) -> (n, ...)
                new_cache[k] = v.reshape((-1,) + v.shape[2:])
            if decode:  # static entries (e.g. cross-attn KV) pass through
                for k in cache:
                    new_cache.setdefault(k, cache[k])
        return x, aux, new_cache

    # -- public entry points ---------------------------------------------------

    def embed_tokens(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.cfg.dtype)
        return constrain(x, "batch", "seq", "embed")

    def logits(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        out = jnp.einsum("bse,ev->bsv", x, head.astype(x.dtype))
        return constrain(out, "batch", "seq", "vocab")

    def forward_train(self, params, tokens, *, positions=None,
                      encoder_embeds=None):
        """tokens (B, S) -> (logits (B,S,V), aux_loss)."""
        cfg = self.cfg
        x = self.embed_tokens(params, tokens)
        enc_out = (self.encode(params, encoder_embeds)
                   if cfg.encoder_layers else None)
        x, aux, _ = self._stack(params, x, positions=positions,
                                enc_out=enc_out, remat=cfg.remat)
        return self.logits(params, x), aux

    def prefill(self, params, tokens, *, positions=None,
                encoder_embeds=None):
        """Full-prompt forward; returns (last-token logits, populated cache)."""
        cfg = self.cfg
        x = self.embed_tokens(params, tokens)
        enc_out = (self.encode(params, encoder_embeds)
                   if cfg.encoder_layers else None)
        x, _, cache = self._stack(params, x, positions=positions,
                                  enc_out=enc_out, collect_cache=True)
        logits = self.logits(params, x[:, -1:, :])
        return logits, cache

    def decode_step(self, params, tokens, cache, cache_index, *,
                    positions=None):
        """tokens (B, 1) + cache -> (logits (B,1,V), new cache)."""
        x = self.embed_tokens(params, tokens)
        x, _, new_cache = self._stack(params, x, positions=positions,
                                      cache=cache, cache_index=cache_index)
        return self.logits(params, x), new_cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean token CE, fp32. logits (B,S,V), targets (B,S) int32."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def loss_fn(model: TransformerLM, params, batch: Dict[str, jax.Array]):
    logits, aux = model.forward_train(
        params, batch["tokens"],
        positions=batch.get("positions"),
        encoder_embeds=batch.get("encoder_embeds"))
    ce = cross_entropy(logits, batch["targets"])
    return ce + aux, {"ce": ce, "aux": aux}


def pad_cache(cache: Dict[str, jax.Array], capacity: int) -> Dict[str, Any]:
    """Pad prefill-produced k/v (length S) to decode capacity T >= S."""
    out = dict(cache)
    for key in ("k", "v"):
        if key in out:
            n, b, s, kvh, d = out[key].shape
            if s < capacity:
                pad = jnp.zeros((n, b, capacity - s, kvh, d), out[key].dtype)
                out[key] = jnp.concatenate([out[key], pad], axis=2)
    return out


def build_model(cfg: ModelConfig) -> TransformerLM:
    return TransformerLM(cfg)
