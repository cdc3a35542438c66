"""The two tenants, built from the program under test.

HP: ``repro.serving.ServingEngine`` over the configuration's model, its
weights made by ``weights.py`` from the seed. BE: the program's train step
(``repro.launch.steps.make_train_step``, jitted with the bundle's own
``donate_argnums``) and its optimizer, fed batches made from the seed in
set-up and kept on the device. The BE step is the engine's
``best_effort_hook``: it runs when the engine has nothing to serve, and
returns when the step has finished on the device, so one quantum at most is
in flight when a request arrives.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax

import check
import families
import generator
import weights

BATCHES = 64      # BE batches made in set-up; later steps cycle through them


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return families.of(cfg).model_config(cfg)


def _same_layout(cfg: dict, model) -> None:
    got = jax.tree.map(lambda s: tuple(s.shape), model.param_shapes())
    want = weights.shapes(cfg)
    if got != want:
        raise RuntimeError(f"{cfg['name']}: the program's parameter layout "
                           f"{got} is not the benchmark's {want}")


class HP:
    """The serving engine over the configuration's model and weights."""

    def __init__(self, cfg: dict, workload: dict, seed: int, hook):
        from repro.models.transformer import build_model
        from repro.serving import ServingConfig, ServingEngine
        model = build_model(model_config(cfg))
        _same_layout(cfg, model)
        params = weights.params_fn(cfg)(weights.key_from_seed(seed, 10))
        self.engine = ServingEngine(
            model, params,
            ServingConfig(capacity=workload["capacity"],
                          max_len=workload["max_len"]),
            best_effort_hook=hook)

    def warm_up(self, buckets: List[int], seed: int) -> None:
        """One request per prompt bucket through the engine: each bucket's
        prefill and slot insertion, and the decode step."""
        vocab = self.engine.cfg.vocab_size
        for i, n in enumerate(buckets):
            self.engine.submit(generator.prompt_tokens(seed, i, n, vocab, tag=6),
                               max_new_tokens=2)
        self.engine.run_until_idle()
        self.engine.done.clear()


class BE:
    """The best-effort trainer and the spans of its steps."""

    def __init__(self, be_cfg: dict, job: dict, seed: int):
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_optimizer, make_train_step
        from repro.models.transformer import build_model
        from repro.optim.adamw import AdamWConfig
        self.cfg, self.job, self.seed = be_cfg, job, seed
        mcfg = model_config(be_cfg)
        opt = job["optimizer"]
        run_opt = AdamWConfig(lr=opt["lr"])
        want = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                           eps=opt["eps"], weight_decay=opt["weight_decay"],
                           grad_clip=opt["grad_clip"])
        if run_opt != want or opt["schedule"] != "constant":
            raise RuntimeError(f"the program's optimizer {run_opt} is not "
                               f"the configuration's {opt}")
        self.model = build_model(mcfg)
        _same_layout(be_cfg, self.model)
        bundle = make_train_step(
            self.model, make_host_mesh(devices=jax.devices()[:1]),
            ShapeConfig("be", job["seq_len"], job["batch"], "train"),
            lr=opt["lr"])
        self.fn = jax.jit(bundle.fn, donate_argnums=bundle.donate_argnums)
        self.params = weights.params_fn(be_cfg)(
            weights.key_from_seed(seed, 20))
        self.opt = jax.jit(make_optimizer(mcfg, opt["lr"]).init)(self.params)
        self.batches = jax.device_put([
            generator.be_batch(seed, i, job["batch"], job["seq_len"],
                               mcfg.vocab_size) for i in range(BATCHES)])
        self.steps = 0
        self.spans: List[Tuple[float, float]] = []   # (start, end) host clock
        self.tokens_per_step = job["batch"] * job["seq_len"]

    def step(self):
        """One BE step (the engine's hook); returns its metrics."""
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("chipbench.be_step"):
            self.params, self.opt, m = self.fn(
                self.params, self.opt, self.batches[self.steps % BATCHES])
            jax.block_until_ready(m["loss"])
        self.spans.append((t0, time.monotonic()))
        self.steps += 1
        return m

    def first_steps(self, n: int = 3) -> dict:
        """Set-up: ``n`` steps through the window's own call and feed, with
        what the output check compares: each step's loss, each leaf's norm
        of the first (clipped) gradient as the optimizer holds it after one
        step, and each leaf's norm of the parameters' change after ``n``."""
        losses, grads = [], None
        for i in range(n):
            m = self.step()
            losses.append(float(m["loss"]))
            if i == 0:
                b1 = self.job["optimizer"]["b1"]
                grads = {k: v / (1.0 - b1)
                         for k, v in check.leaf_norms(self.opt.mu).items()}
        change = check.leaf_norms(self.params, minus=weights.params_fn(
            self.cfg)(weights.key_from_seed(self.seed, 20)))
        return {"losses": losses, "grad_norms": grads, "change_norms": change}
