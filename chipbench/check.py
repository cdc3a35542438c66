"""The output check: what the timed path produced against the plain reference.

Served tokens: a sample of the finished requests, drawn from the seed with
the one that served the most tokens always in it. The reference runs once
over each prompt with its served tokens; the number compared is the widest
gap, in logits, by which a served token lies below the reference's best
(``hp_token_gap``). The control reads the same gap for the token that the
reference computed with fp8 operands puts first.

BE training: the set-up's first three steps against three reference steps
from the same weights on the same batches: the relative gap of the first
step's loss (``be_loss_gap``), and by the worst leaf the gap between the
norms of the first clipped gradient (``be_grad_gap``) and of the
parameters' change after three steps (``be_update_gap``), each over the
reference's norm of that leaf or the median leaf's, whichever is larger.
The later steps' losses are not compared: AdamW's first moves are about
+-lr per weight whatever the gradient's size, so rounding that flips a
small gradient sends the two trajectories apart, and their loss gaps swing
from seed to seed (PERF.md gives the readings).
Leaves whose reference gradient is under a thousandth of the median leaf's
move under AdamW by rounding alone and are left out of the change.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import generator
import weights
from reference.common import adamw, cross_entropy

TINY_GRAD = 1e-3
SAMPLE = 16           # finished requests compared in a run


def reference_forward(cfg: dict):
    return importlib.import_module(f"reference.{cfg['reference']}").forward


def sample_requests(records, seed: int, n: int = SAMPLE) -> list:
    """Up to ``n`` finished requests: the one with the most served tokens,
    then a seeded draw of the rest."""
    done = [r for r in records if r.req.done and r.req.tokens]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].req.tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 5]))
    pick = [longest] + list(rng.permutation(rest)[:max(n - 1, 0)])
    return [(done[i].prompt, np.asarray(done[i].req.tokens, np.int32))
            for i in pick]


def _padded(prompt, tokens, length: int):
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    out = np.zeros(length, np.int32)
    out[:len(seq)] = seq
    tgt = np.zeros(length, np.int32)
    mask = np.zeros(length, bool)
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    tgt[pos], mask[pos] = tokens, True
    return out, tgt, mask


def token_gaps(cfg: dict, params, samples, length: int,
               control: Optional[str] = None
               ) -> Tuple[float, Optional[float]]:
    """Widest gap of a served token below the reference's best; with
    ``control`` (a lower precision of ``reference.common.mm``), also the
    widest gap of the token the control puts first."""
    fwd = reference_forward(cfg)

    @jax.jit
    def gaps(p, seq, tgt, mask):
        lg = fwd(cfg, p, seq)
        best = lg.max(-1)
        served = jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0]
        g = jnp.where(mask, best - served, 0.0).max()
        if not control:
            return g, jnp.float32(0)
        pick = fwd(cfg, p, seq, control).argmax(-1)
        q = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return g, jnp.where(mask, best - q, 0.0).max()

    worst, worst_c = 0.0, 0.0
    for prompt, toks in samples:
        g, gc = gaps(params, *map(jnp.asarray, _padded(prompt, toks, length)))
        worst, worst_c = max(worst, float(g)), max(worst_c, float(gc))
    return worst, (worst_c if control else None)


@jax.jit
def _norms(leaves, minus):
    f32 = jnp.float32
    if minus is not None:
        leaves = [x.astype(f32) - y.astype(f32) for x, y in zip(leaves, minus)]
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(f32))))
                      for x in leaves])


def leaf_norms(tree, minus=None) -> Dict[str, float]:
    """Each leaf's L2 norm (of ``tree - minus`` if given), by its path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = _norms([x for _, x in flat],
                   None if minus is None else jax.tree.leaves(minus))
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, np.asarray(norms))}


def reference_training(cfg: dict, job: dict, seed: int, steps: int = 3,
                       mode: str = "f32", rows: Optional[Sequence[int]] = None
                       ) -> dict:
    """``steps`` AdamW steps of the plain reference from the benchmark's
    weights on the BE batches, one row at a time; ``rows`` keeps only some
    rows of each batch (a fault)."""
    fwd = reference_forward(cfg)
    vocab = weights.embedding_rows(cfg)

    def row_loss(p, tok, tgt):
        return cross_entropy(fwd(cfg, p, tok, mode, remat=True), tgt)

    grad_fn = jax.jit(jax.value_and_grad(row_loss))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    opt = job["optimizer"]
    step_fn = jax.jit(lambda p, g, s: adamw(p, g, s, opt))
    make = weights.params_fn(cfg)
    params = make(weights.key_from_seed(seed, 20))
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (jnp.int32(0), zeros, jax.tree.map(jnp.zeros_like, params))
    rows = list(range(job["batch"])) if rows is None else list(rows)
    losses, clipped, raw = [], None, None
    for step in range(steps):
        b = generator.be_batch(seed, step, job["batch"], job["seq_len"], vocab)
        total, gsum = 0.0, None
        for r in rows:
            loss, g = grad_fn(params, jnp.asarray(b["tokens"][r]),
                              jnp.asarray(b["targets"][r]))
            total += float(loss)
            gsum = g if gsum is None else add(gsum, g)
        grads = jax.tree.map(lambda g: g / len(rows), gsum)
        params, state, cg = step_fn(params, grads, state)
        losses.append(total / len(rows))
        if step == 0:
            raw, clipped = leaf_norms(grads), leaf_norms(cg)
        del gsum, grads, cg
    change = leaf_norms(params, minus=make(weights.key_from_seed(seed, 20)))
    return {"losses": losses, "grad_norms": clipped, "raw_grad_norms": raw,
            "change_norms": change}


def leaf_gap(got: Dict[str, float], want: Dict[str, float],
             keys: Optional[List[str]] = None) -> Tuple[float, str]:
    keys = list(want) if keys is None else keys
    med = float(np.median([want[k] for k in want]))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def training_numbers(prog: dict, ref: dict, detail: bool = False
                     ) -> Dict[str, float]:
    """The numbers compared; ``detail`` adds the first step's loss gap and
    what the numbers came from."""
    raw = ref["raw_grad_norms"]
    med = float(np.median(list(raw.values())))
    moved = [k for k, v in raw.items() if v >= TINY_GRAD * med]
    grad, gk = leaf_gap(prog["grad_norms"], ref["grad_norms"])
    upd, uk = leaf_gap(prog["change_norms"], ref["change_norms"], moved)
    out = {"be_grad_gap": grad, "be_update_gap": upd}
    if detail:
        out.update({"be_loss_gap": abs(prog["losses"][0] - ref["losses"][0])
                    / abs(ref["losses"][0]),
                    "losses": prog["losses"], "ref_losses": ref["losses"],
                    "grad_leaf": gk, "update_leaf": uk,
                    "left_out": sorted(set(raw) - set(moved))})
    return out
