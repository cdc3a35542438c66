"""Smoke run of the main path on a TPU, at mamba2-130m's published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded trainer on a 2x2 mesh

One chip runs three phases in one process:

1. co-located serving: ``repro.launch.serve.serve(colocate_train=True)``
   answers 8 requests while a best-effort trainer takes steps in the
   engine's idle gaps;
2. served logits: prefill, then decode through the engine's per-slot cache
   in the config's bf16, against a float32 ``forward_train`` of the same
   weights;
3. Tally kernels: a ``TallyServer`` runs an HP matmul plainly and a BE
   matmul through the slice and the preempt transformations, compiled.

``--chips 4`` runs only the sharded trainer (``train(model_parallel=2)``
on four chips) and the same steps on one chip, and compares the losses.

Each phase prints one line with its results, its compile and run seconds
and the device's peak memory so far. The last line is one JSON object
naming the device. The script exits non-zero, without that line, when JAX
finds no TPU or when any phase fails. It starts no other process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
import jax  # noqa: E402  (after the libtpu setting above)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "mamba2-130m"
SEED = 0
PROMPT_LENS = (7, 13)       # served-logits prompts, one per engine slot
NEW_TOKENS = 3              # decode steps after each prefill
TOKENS = 64                 # rows of the Tally-phase matmuls

# Served bf16 logits against the float32 reference, as errors over the
# reference's RMS at the compared positions. These random-weight SSD stacks
# amplify rounding with depth: on 24-layer cuts of this config 256 and 512
# wide (CPU, vocab 4096, seeds 0-2) the bf16 path measured 0.07-0.52 RMS,
# and rounding only the weights to bf16 moves the f32 logits about as much;
# float16 measured 0.02-0.04 at 16 layers, and float8 gave non-finite
# logits from 4 layers on. Logits unrelated to the reference, at its
# scale, sit near sqrt(2) RMS, and so would a slot whose cache was lost.
REL_RMS_TOL = 0.8
# Rounding spreads the error over every logit: the worst of the ~2e5
# compared lay at 4.2-5.5 times the error RMS in those runs. A fault local
# to one slot, position or vocabulary slice concentrates it instead.
WORST_OVER_RMS_TOL = 8.0
# Tally kernels: bf16 operands multiply exactly into the float32
# accumulator, so the only error is float32 summation order over K <= 1536
KERNEL_RTOL = 1e-4
# Sharded (2x2) against one-chip training, three steps. Steps 0 and 1 see
# the initial weights (the warm-up learning rate is 0 at step 0), so their
# losses differ only by bf16 rounding in another reduction order. On these
# random weights that rounding moves a token's loss by ~0.2-0.3 nats (the
# served-logits phase measures bf16 logits 0.2 RMS off float32); averaged
# over 8 x 128 correlated tokens that is ~1e-2 nats, 1e-3 of the ~11-nat
# loss, and 3e-3 is three times that
SAME_WEIGHTS_RTOL = 3e-3
# Step 2 follows the first update. AdamW's first moves are about +-lr per
# weight whatever the gradient's size, so every weight whose small
# gradient the rounding flips moves the other way; and the backward pass
# amplifies rounding (the two meshes' gradient norms differ by 5% at the
# same weights). The update must lower the loss on both meshes by amounts
# that agree to 20%; a broken sharded update would not lower it at all.
DROP_RTOL = 0.2


class SmokeFailure(RuntimeError):
    """A phase produced a result outside its contract."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums the seconds XLA spends compiling (tracing, which nests, is left
    in the run seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_phase(name: str, fn, clock: CompileClock) -> dict:
    c0, t0 = clock.seconds, time.monotonic()
    out = fn()
    wall = time.monotonic() - t0
    compile_s = clock.seconds - c0
    line = {"phase": name, "ok": True, "compile_s": round(compile_s, 3),
            "run_s": round(wall - compile_s, 3), "peak_bytes": peak_bytes(),
            **out}
    print("PHASE " + json.dumps(line), flush=True)
    return out


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def colocated_serving(reduced: bool = False) -> dict:
    from repro.launch.serve import serve
    n = 8
    out = serve(ARCH, requests=n, capacity=4, max_new_tokens=4,
                colocate_train=True, reduced=reduced)
    require(out["requests"] == n,
            f"{out['requests']} of {n} requests answered")
    require(out["shed"] == 0, f"{out['shed']} requests shed")
    require(out["be_quanta"] >= 1, "best-effort trainer took no step")
    require(out["be_loss"] is not None and math.isfinite(out["be_loss"]),
            f"best-effort loss {out['be_loss']}")
    return out


def served_logits(reduced: bool = False) -> dict:
    """Served bf16 logits (prefill, then decode through the engine's
    per-slot cache) against a float32 ``forward_train`` of the same
    weights, position by position, with greedy tokens fed back."""
    from repro.launch.serve import serving_model
    from repro.models.transformer import build_model
    from repro.serving import ServingConfig, ServingEngine

    model = serving_model(ARCH, reduced)
    cfg = model.cfg
    require(cfg.dtype == jnp.bfloat16, f"served dtype {cfg.dtype}")
    params = model.init(jax.random.PRNGKey(SEED))
    engine = ServingEngine(model, params, ServingConfig(
        capacity=len(PROMPT_LENS), max_len=max(PROMPT_LENS) + NEW_TOKENS))
    rng = np.random.default_rng(SEED)
    seqs = [list(rng.integers(0, cfg.vocab_size, size=n))
            for n in PROMPT_LENS]

    served = [[] for _ in seqs]
    for slot, seq in enumerate(seqs):      # admit: prefill into its slot
        logits, cache = engine._prefill(engine.params,
                                        jnp.asarray([seq], jnp.int32))
        engine._insert_slot(slot, cache)
        served[slot].append(logits[0, -1])
    decode = jax.jit(model.decode_step)
    lengths = np.array(PROMPT_LENS, np.int32)
    for _ in range(NEW_TOKENS):
        for slot, seq in enumerate(seqs):
            seq.append(int(jnp.argmax(served[slot][-1])))
        tokens = jnp.asarray([[seq[-1]] for seq in seqs], jnp.int32)
        logits, engine.cache = decode(engine.params, tokens, engine.cache,
                                      jnp.asarray(lengths))
        lengths += 1
        for slot in range(len(seqs)):
            served[slot].append(logits[slot, -1])

    ref_model = build_model(dataclasses.replace(cfg, dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref_fwd = jax.jit(ref_model.forward_train)
        errs = {}
        for slot, (seq, n) in enumerate(zip(seqs, PROMPT_LENS)):
            want, _ = ref_fwd(params, jnp.asarray([seq], jnp.int32))
            want = np.asarray(want[0, n - 1:], np.float64)
            got = np.asarray(jnp.stack(served[slot]), np.float64)
            require(got.shape == want.shape and np.isfinite(got).all(),
                    f"served logits {got.shape}, finite "
                    f"{np.isfinite(got).all()}")
            diff = got - want
            err_rms = float(np.sqrt(np.mean(diff ** 2)))
            errs[n] = (err_rms / float(np.sqrt(np.mean(want ** 2))),
                       float(np.abs(diff).max()) / err_rms)
    for n, (rms, worst) in errs.items():
        require(rms <= REL_RMS_TOL,
                f"prompt {n}: logits rel RMS error {rms:.3e} > {REL_RMS_TOL}")
        require(worst <= WORST_OVER_RMS_TOL,
                f"prompt {n}: worst logit error {worst:.2f} x the error "
                f"RMS > {WORST_OVER_RMS_TOL}")
    return {"positions": len(PROMPT_LENS) * (NEW_TOKENS + 1),
            "rel_rms_err": {str(n): e[0] for n, e in errs.items()},
            "worst_over_rms": {str(n): e[1] for n, e in errs.items()},
            "rel_rms_tol": REL_RMS_TOL,
            "worst_over_rms_tol": WORST_OVER_RMS_TOL}


def tally_kernels(reduced: bool = False) -> dict:
    """HP ``out_proj`` launched plainly; BE ``in_proj`` (submitted first)
    forced through the slice and then the preempt config."""
    from repro.configs.base import get_config
    from repro.core import transforms as T
    from repro.core.descriptor import build_plain, resolve_interpret
    from repro.core.profiler import LaunchConfig
    from repro.core.virtualization import TallyServer
    from repro.kernels import ref
    from repro.kernels.matmul import matmul_desc

    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    d, s = cfg.d_model, cfg.ssm
    d_in = s.expand * d
    n_in_proj = 2 * d_in + 2 * s.d_state + s.num_heads(d)   # z, x, B, C, dt
    bf16 = jnp.bfloat16
    d_hp = matmul_desc(TOKENS, d_in, d, bf16)               # out_proj
    d_be = matmul_desc(TOKENS, d, n_in_proj, bf16, bm=8)    # in_proj
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    a_hp = jax.random.normal(keys[0], (TOKENS, d_in), bf16)
    b_hp = jax.random.normal(keys[1], (d_in, d), bf16)
    a_be = jax.random.normal(keys[2], (TOKENS, d), bf16)
    b_be = jax.random.normal(keys[3], (d, n_in_proj), bf16)
    with jax.default_matmul_precision("highest"):
        want_hp = np.asarray(ref.matmul_ref(a_hp, b_hp))
        want_be = np.asarray(ref.matmul_ref(a_be, b_be))

    def rel_err(got, want) -> float:
        return float(np.abs(np.asarray(got) - want).max()
                     / np.abs(want).max())

    server = TallyServer()
    hp = server.register("hp", priority=0)
    be = server.register("be", priority=1)
    out: dict = {"device_name": hp.device_info("name"),
                 "hp_grid": list(d_hp.grid), "be_grid": list(d_be.grid)}
    workers = slices = min(4, d_be.num_blocks)
    for cfg_be in (LaunchConfig("slice", slices),
                   LaunchConfig("preempt", workers)):
        job_be = be.launch(d_be, a_be, b_be)
        server.profiler.profile(job_be, cfg_be)      # measure this one only
        server.profiler.set_launch_config(job_be, [cfg_be], bound=math.inf)
        require(server.profiler.lookup_launch_config(job_be) == cfg_be,
                f"BE config not forced to {cfg_be}")
        job_hp = hp.launch(d_hp, a_hp, b_hp)
        server.serve_until_idle(max_seconds=600)
        require(job_be.done.is_set() and job_hp.done.is_set(),
                f"{cfg_be}: launches not completed")
        e_hp = rel_err(job_hp.result(0)[0], want_hp)
        e_be = rel_err(job_be.result(0)[0], want_be)
        require(e_hp <= KERNEL_RTOL, f"HP matmul rel error {e_hp:.3e}")
        require(e_be <= KERNEL_RTOL, f"{cfg_be} BE rel error {e_be:.3e}")
        require(job_hp.complete_t <= job_be.complete_t,
                f"{cfg_be}: HP finished after BE")
        out[str(cfg_be)] = {"hp_rel_err": e_hp, "be_rel_err": e_be,
                            "hp_latency_s": job_hp.latency,
                            "be_latency_s": job_be.latency}

    sliced = T.build_sliced(d_be, *T.slice_plan(d_be, slices)[0])
    pre = T.make_preemptible(d_be, workers)
    o_be = jax.ShapeDtypeStruct((TOKENS, n_in_proj), jnp.float32)
    forms = {"hp_plain": (build_plain(d_hp), (a_hp, b_hp)),
             "be_slice": (lambda p, x, y: sliced([p], x, y),
                          (o_be, a_be, b_be)),
             "be_preempt": (lambda p, x, y: pre([p], 0, 1, x, y),
                            (o_be, a_be, b_be))}
    counts = {name: jax.jit(fn).lower(*args).compile().as_text()
              .count("tpu_custom_call") for name, (fn, args) in forms.items()}
    if not resolve_interpret(None):       # compiled: each form is a kernel
        require(all(counts.values()), f"tpu_custom_call counts {counts}")
    out["tpu_custom_call"] = counts
    return out


def sharded_train(reduced: bool = False) -> dict:
    """``train()`` on a (data 2, model 2) mesh of four chips, then the
    same seed and batches on one chip."""
    from repro.launch.train import train
    devices = jax.devices()
    require(len(devices) == 4, f"{len(devices)} devices, need 4")
    kw = dict(steps=3, batch=8, seq=128, reduced=reduced, seed=SEED,
              log_every=1)
    four = train(ARCH, model_parallel=2, **kw)["losses"]
    one = train(ARCH, devices=devices[:1], **kw)["losses"]
    require(all(math.isfinite(x) for x in four + one),
            f"non-finite losses {four} {one}")
    rel = max(abs(a - b) / abs(b) for a, b in zip(four[:2], one[:2]))
    require(rel <= SAME_WEIGHTS_RTOL,
            f"initial-weight losses differ by {rel:.3e} relative > "
            f"{SAME_WEIGHTS_RTOL}")
    drop4, drop1 = four[1] - four[2], one[1] - one[2]
    require(drop1 > 0, f"one-chip update raised the loss by {-drop1}")
    drop_rel = abs(drop4 - drop1) / drop1
    require(drop_rel <= DROP_RTOL,
            f"loss drops {drop4:.4f} (4 chips) and {drop1:.4f} (1 chip) "
            f"differ by {drop_rel:.3e} relative > {DROP_RTOL}")
    return {"mesh": {"data": 2, "model": 2}, "losses_4chip": four,
            "losses_1chip": one, "same_weights_rel_diff": rel,
            "same_weights_rtol": SAME_WEIGHTS_RTOL,
            "drop_rel_diff": drop_rel, "drop_rtol": DROP_RTOL}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded trainer and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    clock = CompileClock()
    if args.chips == 4:
        phases = [("sharded_train", sharded_train)]
    else:
        phases = [("colocated_serving", colocated_serving),
                  ("served_logits", served_logits),
                  ("tally_kernels", tally_kernels)]
    for name, fn in phases:
        run_phase(name, fn, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
