"""What the harness knows of one model family, one file per family.

A configuration file names its family (``"family": "<name>"``), and
``of(cfg)`` imports ``families/<name>.py``, as ``check.reference_forward``
imports ``reference/<name>.py``. So a configuration of a new family is new
files only: ``configs/``, ``families/``, ``reference/`` and ``workloads/``.

Each family module exports four functions of a configuration file ``cfg``:

- ``param_specs(cfg)``: the parameter tree as ``weights.Spec`` leaves
  (shape, init, std), in the program's layout (``weights.lm_specs`` adds the
  embedding, final norm and output head around the layers);
- ``model_config(cfg)``: the program's ``repro.configs.base.ModelConfig``
  (``common(cfg)`` gives the fields every family shares);
- ``matmul_params(cfg)``: the projection weights a token passes through, as
  ``flops.py``'s docstring counts them;
- ``decode_least(cfg, step) -> (operations, bytes)``: what one decode step
  needs at the least. ``step`` is the mapping of the stats that the
  program's ``tally.serve.decode`` span carries for that step, untouched:
  today ``active`` (slots decoded) and ``kv_tokens`` (tokens held in their
  caches before the step; each slot also attends to the token it decodes).
  A family that needs more, such as the experts a step routed to, reads the
  stat that the program puts on that span.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent


def of(cfg: dict) -> ModuleType:
    """The family module of the configuration ``cfg``."""
    name = cfg["family"]
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise ModuleNotFoundError(
            f"{cfg['name']}: family {name!r} has no file "
            f"{HERE / (name + '.py')}", name=e.name) from None


def common(cfg: dict) -> dict:
    """The ``ModelConfig`` fields every family takes from its file."""
    import jax.numpy as jnp
    import weights
    return dict(name=cfg["name"], vocab_size=weights.embedding_rows(cfg),
                dtype=jnp.dtype(cfg["dtype"]).type,
                param_dtype=jnp.dtype(cfg["param_dtype"]).type,
                source=cfg["source"])
