"""A benchmark root at a size the CPU runs in seconds, for the tests.

It holds tiny configurations of both families, one tiny traffic mix, one
cell per configuration and a ``BENCHMARK.json`` naming them, beside a link
to the repository's ``src``. The harness finds all of it by name, as it
finds the real cells.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

OPT = {"lr": 0.0003, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
       "weight_decay": 0.1, "grad_clip": 1.0, "schedule": "constant"}

SSM = {
    "name": "tiny-ssm", "source": "test", "family": "ssm", "reference": "ssm",
    "config": {"d_model": 64, "n_layer": 2, "vocab_size": 250,
               "pad_vocab_size_multiple": 16,
               "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                           "expand": 2, "headdim": 16, "ngroups": 1,
                           "chunk_size": 16, "conv_bias": False},
               "rms_norm": True, "norm_epsilon": 1e-5,
               "residual_in_fp32": False, "tie_embeddings": True},
    "dtype": "bfloat16", "param_dtype": "float32",
    "be": {"config": "tiny-ssm", "batch": 2, "seq_len": 64,
           "optimizer": OPT},
}
DENSE = {
    "name": "tiny-dense", "source": "test", "family": "dense",
    "reference": "dense",
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
               "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
               "tie_word_embeddings": False},
    "dtype": "bfloat16", "param_dtype": "float32",
    "be": {"config": "tiny-ssm", "batch": 2, "seq_len": 64,
           "optimizer": OPT},
}
MIX = {"arrivals": "poisson",
       "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                  "buckets": [8, 16]},
       "output": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                  "min": 2, "max": 8}}
LIMITS = {"hp_token_gap": 0.05, "be_grad_gap": 0.04, "be_update_gap": 0.02}


def cell(config: str) -> dict:
    return {"config": config, "traffic": "tiny", "rate_rps": 20.0,
            "capacity": 4, "max_len": 32,
            "limits": dict(LIMITS)}


def make_root(tmp: Path) -> Path:
    """Write the tiny benchmark under ``tmp`` and return it."""
    d = tmp / BENCH.name
    for sub in ("configs", "traffic", "workloads"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    for cfg in (SSM, DENSE):
        (d / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        (d / "workloads" / f"{cfg['name']}.tiny.json").write_text(
            json.dumps(cell(cfg["name"])))
    (d / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    (d / "peaks.json").write_text((BENCH / "peaks.json").read_text())
    (tmp / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": f"{c['name']}.tiny", "config": c["name"], "traffic": "tiny",
         "chips": 1, "why": "test"} for c in (SSM, DENSE)]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
