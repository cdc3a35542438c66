"""The output check on the CPU at a tiny size: a sound run is correct; the
fp8 control, and each fault planted in the timed path, is not."""
from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

import tiny
import check
import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _result(root, capsys, cell="tiny-ssm.tiny", seed=11):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                   "--trace", "0", "--root", str(root)], require_tpu=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "compared"
    return out


@pytest.mark.parametrize("cell", ["tiny-ssm.tiny", "tiny-dense.tiny"])
def test_a_sound_run_is_correct(root, capsys, cell):
    out = _result(root, capsys, cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"hp_ttft_p95_ms", "hp_itl_p95_ms",
                                   "be_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("config,layers,cells", [
    ("mamba2-130m", None, ("mamba2-130m.decode_steady",)),
    ("mistral-nemo-12b.pp10", 1, ("mistral-nemo-12b.pp10.chat_burst",))],
    ids=["mamba2-130m", "mistral-nemo-12b.pp10"])
def test_the_fp8_control_is_not_correct(seed, config, layers, cells):
    """The reference with fp8 operands in the program's place (8
    sequences of 256 positions, not a run's sample; mamba2-130m whole, the
    dense share with one of its four layers, so a test run holds it): the
    gap of the token it puts first, under the float32 reference, fails the
    cells' served-token limit."""
    from spec import Bench
    import generator
    import weights
    bench = Bench()
    cfg = bench.config(config)
    if layers:
        cfg["config"]["num_hidden_layers"] = layers
    V = weights.embedding_rows(cfg)
    params = weights.params_fn(cfg)(weights.key_from_seed(seed, 10))
    seqs = [(generator.prompt_tokens(seed, i, 128, V),
             generator.prompt_tokens(seed, 100 + i, 128, V))
            for i in range(8)]
    _, control = check.token_gaps(cfg, params, seqs, 256,
                                  control=run.CONTROL)
    for cell in cells:
        assert control > bench.workload(cell)["limits"]["hp_token_gap"]


def _token_altered(monkeypatch):
    from repro.serving.engine import ServingEngine
    real = ServingEngine._decode_impl

    def altered(self, *a):
        tok, cache = real(self, *a)
        return (tok + 1) % self.cfg.vocab_size, cache
    monkeypatch.setattr(ServingEngine, "_decode_impl", altered)


def _wrap_step(monkeypatch, wrap):
    import repro.launch.steps as steps
    real = steps.make_train_step

    def make(*a, **kw):
        b = real(*a, **kw)
        return type(b)(fn=wrap(b.fn), abstract_inputs=b.abstract_inputs,
                       in_shardings=b.in_shardings,
                       out_shardings=b.out_shardings,
                       donate_argnums=b.donate_argnums)
    monkeypatch.setattr(steps, "make_train_step", make)


def _state_unchanged(monkeypatch):
    def wrap(fn):
        return lambda p, o, b: (jax_copy(p), jax_copy(o), fn(p, o, b)[2])
    _wrap_step(monkeypatch, wrap)


def _half_batch(monkeypatch):
    def wrap(fn):
        return lambda p, o, b: fn(p, o, {k: v[:v.shape[0] // 2]
                                         for k, v in b.items()})
    _wrap_step(monkeypatch, wrap)


def jax_copy(tree):
    import jax
    return jax.tree.map(lambda x: x + jnp.zeros_like(x), tree)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_a_broken_timed_path_is_not_correct(root, capsys, monkeypatch,
                                            fault):
    fault(monkeypatch)
    out = _result(root, capsys)
    assert out["correct"] is False, out["compared"]
