"""The harness's parts that need no chip: generation, file discovery,
peaks, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the benchmark directory on sys.path)
import generator
import spec

CHAT = json.loads((tiny.BENCH / "traffic" / "chat_burst.json").read_text())
STEADY = json.loads((tiny.BENCH / "traffic" / "decode_steady.json")
                    .read_text())


@pytest.mark.parametrize("mix", [CHAT, STEADY], ids=["maf2_like", "poisson"])
def test_the_schedule_is_fixed_and_within_the_mix(mix):
    a = generator.generate(mix, 12.0, 20.0)
    assert a == generator.generate(mix, 12.0, 20.0) and len(a) > 150
    assert a != generator.generate(mix, 13.0, 20.0)
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    assert all(0 <= x.due_s < 20.0 for x in a)
    assert {x.prompt_len for x in a} <= set(mix["prompt"]["buckets"])
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    assert all(lo <= x.max_new_tokens <= hi for x in a)


def test_prompt_tokens_are_drawn_from_the_seed():
    p1 = generator.prompt_tokens(2**33 + 7, 3, 64, 1000)
    assert np.array_equal(p1, generator.prompt_tokens(2**33 + 7, 3, 64, 1000))
    assert not np.array_equal(p1, generator.prompt_tokens(7, 3, 64, 1000))
    assert not np.array_equal(p1, generator.prompt_tokens(2**33 + 7, 4, 64,
                                                          1000))
    assert p1.min() >= 0 and p1.max() < 1000


def test_bursts_are_overdispersed_and_poisson_is_not():
    """Arrivals per second: variance over mean is about 1 for Poisson and
    well above it when lognormal rate levels modulate the rate."""
    def dispersion(mix):
        a = generator.generate(mix, 12.0, 60.0)
        per_s = np.bincount([int(x.due_s) for x in a], minlength=60)
        return per_s.var() / per_s.mean()
    assert dispersion(STEADY) < 1.5 < 2.0 < dispersion(CHAT)


def test_be_batches_are_deterministic_and_rows_differ():
    a = generator.be_batch(3, 0, 4, 256, 50288)
    assert np.array_equal(a["tokens"], generator.be_batch(3, 0, 4, 256,
                                                          50288)["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    rows = {r.tobytes() for r in a["tokens"]}
    rows |= {r.tobytes() for r in generator.be_batch(3, 1, 4, 256,
                                                     50288)["tokens"]}
    assert len(rows) == 8
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 50288


def test_a_cell_dropped_into_a_directory_is_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = spec.Bench(root)
    extra = dict(tiny.cell("tiny-ssm"), rate_rps=3.0)
    (root / "chipbench" / "workloads" / "tiny-ssm.new.json").write_text(
        json.dumps(extra))
    wl = bench.workload("tiny-ssm.new")
    assert wl["rate_rps"] == 3.0 and wl["name"] == "tiny-ssm.new"
    assert bench.config(wl["config"])["family"] == "ssm"
    assert bench.traffic(wl["traffic"])["arrivals"] == "poisson"
    with pytest.raises(FileNotFoundError):
        bench.workload("no-such-cell")


def test_every_cell_of_the_benchmark_resolves():
    bench = spec.Bench()
    b = bench.benchmark()
    names = {m["name"] for m in b["end_to_end"] + b["per_layer"]}
    for c in b["workloads"]:
        wl = bench.workload(c["name"])
        assert (wl["config"], wl["traffic"]) == (c["config"], c["traffic"])
        cfg = bench.config(c["config"])
        bench.config(cfg["be"]["config"])
        assert bench.end_to_end(c["name"]) and bench.per_layer(c["name"])
    for m in b["per_layer"]:
        assert callable(bench.metric_reader(m["name"]))
    assert "setup_s" in names
    for c in b["configs"]:
        assert sorted(bench.config(c["name"])["reduced"]) == sorted(
            c["reduced"])


def test_peaks_are_keyed_by_device_kind():
    bench = spec.Bench()
    pk = bench.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        bench.peaks("TPU v9 imaginary")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    p = _run(["--workload", "mamba2-130m.decode_steady", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_in_a_bare_checkout_exits_non_zero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "mamba2-130m.decode_steady", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
