"""Compile rehearsal: the main path's Pallas kernels, compiled for one
described TPU v5e chip with ``interpret=False`` at mamba2-130m's shapes,
and the serving engine's decode at mamba2-130m's published widths.

Nothing runs: the TPU compiler is installed, and it compiles for a chip
that is described and not attached, raising what the chip's compiler
would raise (unaligned blocks, VMEM overflows, unsupported primitives).
The topology is described inside a fixture, never at import, because
only one process at a time may load the TPU library.
"""
import dataclasses
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from jaxlib.mlir.ir import MLIRError

from repro.configs import get_config, kv_cache_specs
from repro.core import transforms as T
from repro.core.descriptor import build_plain
from repro.kernels.flash_attention import flash_attention_desc
from repro.kernels.mamba2_scan import mamba2_scan_desc
from repro.kernels.matmul import matmul_desc
from repro.models.transformer import build_model
from repro.serving import ServingEngine

# mamba2-130m, 64 tokens: out_proj (d_inner 1536 -> d_model 768) and
# in_proj (768 -> z, x, B, C, dt = 2*1536 + 2*128 + 24 = 3352); bm=8 gives
# in_proj eight row blocks to slice and preempt
MATMULS = {"out_proj": (64, 1536, 768, 128), "in_proj": (64, 768, 3352, 8)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _matmul(name, one_chip):
    M, K, N, bm = MATMULS[name]
    desc = matmul_desc(M, K, N, jnp.bfloat16, bm=bm, interpret=False)
    a = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((K, N), jnp.bfloat16, sharding=one_chip)
    o = jax.ShapeDtypeStruct((M, N), jnp.float32, sharding=one_chip)
    return desc, a, b, o


@pytest.mark.parametrize("form", ["plain", "slice", "preempt"])
@pytest.mark.parametrize("name", sorted(MATMULS))
def test_matmul_compiles(name, form, one_chip):
    desc, a, b, o = _matmul(name, one_chip)
    if form == "plain":
        text = _compile_text(build_plain(desc), a, b)
    elif form == "slice":
        off, ln = T.slice_plan(desc, 4)[-1]     # a slice at an offset
        sliced = T.build_sliced(desc, off, ln)
        text = _compile_text(lambda p, x, y: sliced([p], x, y), o, a, b)
    else:
        pre = T.make_preemptible(desc, 4)
        text = _compile_text(lambda p, s, x, y: pre([p], s, 1, x, y), o,
                             jax.ShapeDtypeStruct((), jnp.int32,
                                                  sharding=one_chip), a, b)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    """(40 heads, 2048 queries, 2048 keys, head 128), GQA group 5."""
    BH, S, T_, D, G = 40, 2048, 2048, 128, 5
    desc = flash_attention_desc(BH, S, T_, D, G, jnp.bfloat16,
                                interpret=False)
    q = jax.ShapeDtypeStruct((BH, S, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((BH // G, T_, D), jnp.bfloat16,
                              sharding=one_chip)
    assert "tpu_custom_call" in _compile_text(build_plain(desc), q, kv, kv)


@pytest.mark.xfail(strict=True, raises=MLIRError, reason=(
    "Mosaic refuses the SSD kernel's three-operand einsums "
    "('th,td,hed->the' and 'th,thd,te->hde'): 'Unable to parse attribute: "
    "#tpu.dot_dimension_numbers<...>: failed to parse "
    "TPU_DotDimensionNumbersAttr parameter lhs_contracting_dims'"))
def test_ssd_scan_compiles(one_chip):
    B, S, NH, HD, DS = 1, 512, 24, 64, 128
    desc = mamba2_scan_desc(B, S, NH, HD, DS, 256, interpret=False)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = _compile_text(build_plain(desc), sds(B, S, NH, HD), sds(B, S, NH),
                         sds(NH), sds(B, S, DS), sds(B, S, DS), sds(NH))
    assert "tpu_custom_call" in text


def test_served_decode_reads_weights_once(one_chip):
    """The engine's decode over 16 slots of 2048 at mamba2-130m's published
    widths (tied embedding, vocabulary padded to 50288): fed the served
    tree it moves under half the bytes it moves fed the float32 tree, and
    no convert writes a bf16 copy of a layer-stacked weight per call."""
    cfg = dataclasses.replace(get_config("mamba2-130m"), tie_embeddings=True,
                              vocab_size=50288)
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    shapes = model.param_shapes()
    cache = on_chip(kv_cache_specs(cfg, 16, 2048))
    tokens = jax.ShapeDtypeStruct((16, 1), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    decode = jax.jit(lambda *a: ServingEngine._decode_impl(
        SimpleNamespace(model=model), *a))
    stacked_cast = re.compile(
        rf"= bf16\[{cfg.num_layers},[0-9,]+\]\S* convert\(")
    moved, casts = {}, {}
    for form, params in (
            ("f32", shapes),
            ("served", jax.eval_shape(model.serving_params, shapes))):
        compiled = decode.lower(on_chip(params), tokens, cache,
                                lengths).compile()
        cost = compiled.cost_analysis()
        moved[form] = (cost[0] if isinstance(cost, list) else cost
                       )["bytes accessed"]
        entry = compiled.as_text().split("\nENTRY", 1)[1].split("\n}", 1)[0]
        casts[form] = len(stacked_cast.findall(entry))
    assert moved["served"] < 0.5 * moved["f32"], moved
    assert casts["f32"] > 0 and casts["served"] == 0, casts
