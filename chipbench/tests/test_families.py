"""The per-family files (``families/``): their interface, the lookup by
name, a family added as new files only, and the decode counts that the
engine's ``tally.serve.decode`` spans give on the CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

import tiny
import families
import flops
import phases
import spec
import tenants
import trace_reduce
import weights

sys.path.insert(0, str(tiny.REPO / "src"))     # the program's ModelConfig
INTERFACE = ("param_specs", "model_config", "matmul_params", "decode_least")
MODULES = sorted(p.stem for p in families.HERE.glob("*.py")
                 if p.stem != "__init__")


def _configs():
    bench = spec.Bench()
    names = [c["name"] for c in bench.benchmark()["configs"]]
    return [bench.config(n) for n in names] + [tiny.SSM, tiny.DENSE]


@pytest.mark.parametrize("name", MODULES)
def test_each_family_file_has_the_interface(name):
    mod = families.of({"name": "probe", "family": name})
    for fn in INTERFACE:
        assert callable(getattr(mod, fn)), f"families/{name}.py lacks {fn}"


@pytest.mark.parametrize("cfg", _configs(), ids=lambda c: c["name"])
def test_every_configuration_resolves_to_its_family(cfg):
    mod = families.of(cfg)
    assert mod.__name__ == f"families.{cfg['family']}"
    assert weights.shapes(cfg)["embed"] == (weights.embedding_rows(cfg),
                                            tenants.model_config(cfg).d_model)
    assert flops.matmul_params(cfg) > 0
    f, b = flops.decode_least(cfg, {"active": 3, "kv_tokens": 300})
    assert f > 2 * flops.matmul_params(cfg) * 3 and b > 0


def test_an_unknown_family_names_its_missing_file():
    with pytest.raises(ModuleNotFoundError) as e:
        families.of({"name": "x", "family": "no_such_family"})
    assert str(families.HERE / "no_such_family.py") in str(e.value)


# a family that re-exports another, found only by its file's name
ECHO = 'from families.dense import (decode_least, matmul_params,  # noqa\n' \
       '                            model_config, param_specs)\n'


@pytest.fixture
def echo_root(tmp_path, monkeypatch):
    """The tiny benchmark, plus a configuration of family ``echo`` whose
    file lies in a directory of its own on the families package's path."""
    fam = tmp_path / "fam"
    fam.mkdir()
    (fam / "echo.py").write_text(ECHO)
    monkeypatch.setattr(families, "__path__", [*families.__path__, str(fam)])
    root = tiny.make_root(tmp_path / "bench")
    d = root / "chipbench"
    echo = dict(tiny.DENSE, name="tiny-echo", family="echo",
                be=dict(tiny.DENSE["be"], config="tiny-echo"))
    (d / "configs" / "tiny-echo.json").write_text(json.dumps(echo))
    (d / "workloads" / "tiny-echo.tiny.json").write_text(
        json.dumps(tiny.cell("tiny-echo")))
    return root


class OldCount:
    """The engine, with each decode step counted from the host's side as the
    benchmark counted it before it read the engine's span: the slots that
    gained a decoded token, and the positions they attended, which is a
    slot's prompt and tokens so far less the one just decoded."""

    def __init__(self, engine):
        self.engine, self.reqs, self.steps = engine, [], []

    def submit(self, prompt, **kw):
        req = self.engine.submit(prompt, **kw)
        self.reqs.append(req)
        return req

    def step(self) -> bool:
        before = [len(r.tokens) for r in self.reqs]
        worked = self.engine.step()
        active = positions = 0
        for r, b in zip(self.reqs, before):
            if len(r.tokens) - b - (b == 0) > 0:
                active += 1
                positions += len(r.prompt) + len(r.tokens) - 1
        if active:
            self.steps.append((active, positions))
        return worked


def _traced_window(root: Path, cell_name: str, seed: int):
    """Both tenants of a tiny cell from ``seed``, a traced window of 1 s,
    and what the trace and the host-side count read."""
    import jax
    import driver
    import generator
    import run
    bench = spec.Bench(root)
    cell = run.cell_files(bench, cell_name)
    devices, compiles = run.start_jax(bench, cell_name, require_tpu=False)
    arrivals = generator.generate(cell["mix"], cell["wl"]["rate_rps"], 1.0)
    prompts = run.prompts_for(cell, arrivals, seed)
    be, hp, _ = run.build(cell, seed)
    eng = OldCount(hp.engine)
    trace_dir = root / ".chipbench_trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        driver.run_window(eng, arrivals, prompts, 1.0, 30.0, compiles)
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.load_xplane(
        str(sorted(trace_dir.glob("**/*.xplane.pb"))[-1]))
    shutil.rmtree(trace_dir)
    lo, hi = trace_reduce.window_of(events)
    spans = [s for s in events["program_spans"] if lo <= s[1] < hi]
    return cell, eng.steps, spans, be.steps


@pytest.mark.parametrize("cell_name", ["tiny-ssm.tiny", "tiny-dense.tiny"])
def test_decode_span_counts_match_the_host_side_count(tmp_path, cell_name):
    """Per decode step, the span's ``active`` is the slots that gained a
    token, its ``kv_tokens`` is one fewer per slot than the positions they
    attended, and ``decode_least`` reads the same from either."""
    root = tiny.make_root(tmp_path)
    cell, host, spans, _ = _traced_window(root, cell_name, 2**40 + 3)
    stats = phases.decode_stats(spans)
    assert len(stats) == len(host) > 5
    for st, (active, positions) in zip(stats, host):
        assert (st["active"], st["kv_tokens"] + st["active"]) == (
            active, positions)
        assert flops.decode_least(cell["cfg"], st) == flops.decode_least(
            cell["cfg"], {"active": active, "kv_tokens": positions - active})


def test_a_family_added_as_new_files_builds_and_counts(echo_root):
    """Family ``echo`` is one new file and a configuration naming it: both
    tenants build from it, and the decode readers count its steps as the
    dense family's, from the engine's spans."""
    cell, host, spans, be_steps = _traced_window(echo_root, "tiny-echo.tiny",
                                                 2**35 + 1)
    cfg = cell["cfg"]
    assert cfg["family"] == "echo" and cell["be_cfg"]["family"] == "echo"
    assert families.of(cfg).__name__ == "families.echo"
    assert be_steps >= 3 and len(phases.decode_stats(spans)) == len(host) > 5
    bench = spec.Bench()
    pk = bench.peaks("TPU v5 lite")
    n = len(host)
    ctx = {"trace": {"programs": {"jit__decode_impl": {
        "count": n, "seconds": n * 1e-3}}},
        "program_spans": spans, "cfg": cfg, "peaks": pk}
    dense = dict(cfg, family="dense")
    least = [max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
             for f, b in (flops.decode_least(dense, {"active": a,
                                                     "kv_tokens": p - a})
                          for a, p in host)]
    assert bench.metric_reader("hp_decode_roofline")(ctx) == pytest.approx(
        100.0 * sum(least) / n / 1e-3)
    assert bench.metric_reader("hp_decode_mfu")(ctx) == pytest.approx(
        100.0 * 2 * flops.matmul_params(dense) * sum(a for a, _ in host) / n
        / (1e-3 * pk["flops_bf16"]))
