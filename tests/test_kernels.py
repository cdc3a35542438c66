"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(Pallas interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transforms as T
from repro.core.descriptor import build_plain, pick_block, resolve_interpret
from repro.kernels import ops, ref
from repro.kernels.matmul import matmul_desc

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("M,K,N", [(32, 32, 32), (96, 160, 64),
                                   (128, 64, 48), (17 * 8, 24, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(M, K, N, dtype):
    a = jnp.asarray(RNG.normal(size=(M, K)), dtype)
    b = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    out = ops.matmul(a, b, bm=32, bk=32, bn=16)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=2e-1 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_multiblock(dtype):
    """(8, 128)-aligned blocks over every grid axis: k accumulates over
    three blocks, and m and n split."""
    a = jnp.asarray(RNG.normal(size=(64, 384)), dtype)
    b = jnp.asarray(RNG.normal(size=(384, 256)), dtype)
    out = ops.matmul(a, b, bm=32, bk=128, bn=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.matmul_ref(a, b), np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-1 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("dim,target,align,want", [
    (3352, 128, 128, 3352),    # no 128-multiple divides it: whole dim
    (768, 512, 128, 384),
    (300, 256, 8, 300),        # the old picker chose 150
    (136, 32, 8, 8),
    (48, 128, 128, 48),        # fits the target: whole dim
])
def test_pick_block_never_unaligned(dim, target, align, want):
    b = pick_block(dim, target, align)
    assert b == want
    assert dim % b == 0 and (b % align == 0 or b == dim)


def test_interpret_none_resolves_from_backend():
    """With no flag the backend decides: under JAX_PLATFORMS=cpu the
    kernel builds and runs interpreted, and matches the oracle."""
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    desc = matmul_desc(64, 256, 256, bk=128, bn=128)
    assert desc.interpret is None
    a = jnp.asarray(RNG.normal(size=(64, 256)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(256, 256)), jnp.float32)
    out = build_plain(desc)(a, b)[0]
    np.testing.assert_allclose(out, ref.matmul_ref(a, b), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("form", ["plain", "slice", "preempt"])
@pytest.mark.parametrize("flag", [False, True])
def test_explicit_interpret_reaches_pallas_call(form, flag):
    """An explicit flag wins over the backend, in every launch form; read
    off the traced ``pallas_call``, without running it."""
    desc = matmul_desc(64, 256, 256, bk=128, bn=128, interpret=flag)
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    o = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    if form == "plain":
        fn, args = build_plain(desc), (a, b)
    elif form == "slice":
        sliced = T.build_sliced(desc, 0, 1)
        fn, args = (lambda p, x, y: sliced([p], x, y)), (o, a, b)
    else:
        pre = T.make_preemptible(desc, 2)
        fn, args = (lambda p, x, y: pre([p], 0, 1, x, y)), (o, a, b)
    eqns = [e for e in jax.make_jaxpr(fn)(*args).eqns
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    assert eqns[0].params["interpret"] is flag


def test_preemptible_refuses_operands_beyond_vmem():
    """The persistent-worker form maps whole operands into VMEM; a launch
    that cannot fit raises, naming the kernel and its bytes."""
    M, K, N = 4096, 4096, 13440
    desc = matmul_desc(M, K, N)
    pre = T.make_preemptible(desc, 8)
    a = jax.ShapeDtypeStruct((M, K), jnp.float32)
    b = jax.ShapeDtypeStruct((K, N), jnp.float32)
    o = jax.ShapeDtypeStruct((M, N), jnp.float32)
    with pytest.raises(ValueError, match=rf"{desc.name}.*\d+ bytes"):
        jax.eval_shape(lambda p, x, y: pre([p], 0, 1, x, y), o, a, b)


def test_matmul_batched_lead():
    a = jnp.asarray(RNG.normal(size=(2, 8, 48)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(48, 32)), jnp.float32)
    out = ops.matmul(a, b, bm=16, bk=16, bn=16)
    want = jnp.einsum("bmk,kn->bmn", a, b)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,T,H,KVH,D", [(64, 64, 4, 4, 16),
                                         (64, 64, 8, 2, 32),
                                         (48, 48, 6, 3, 16)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, T, H, KVH, D, causal, dtype):
    B = 2
    q = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, KVH, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, KVH, D)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=16, bk=16)
    G = H // KVH
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, T, D)
    want = ref.attention_ref(qf, kf, vf, causal=causal, group=G
                             ).reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,NH,HD,DS,chunk", [(2, 48, 3, 8, 5, 16),
                                                (1, 64, 2, 16, 8, 32),
                                                (3, 30, 4, 4, 4, 10)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba2_scan_sweep(B, S, NH, HD, DS, chunk, dtype):
    x = jnp.asarray(RNG.normal(size=(B, S, NH, HD)), dtype)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(B, S, NH)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(NH,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, DS)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, DS)), dtype)
    D = jnp.asarray(RNG.normal(size=(NH,)), jnp.float32)
    y, h = ops.mamba2_scan(x, dt, A, Bm, Cm, D, chunk=chunk)
    yr, hr = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-1 if dtype == jnp.bfloat16 else 1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-1 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.slow
def test_model_pallas_path_matches_xla():
    """cfg.use_pallas routes attention+mlp+ssd through kernels; logits must
    match the XLA path (the cuBLAS->CUTLASS swap must be semantically
    invisible)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.transformer import build_model

    for arch in ["qwen2.5-14b", "mamba2-130m"]:
        cfg = get_config(arch).reduced()
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, size=(2, 16)),
                             jnp.int32)
        lg_xla, _ = model.forward_train(params, tokens)
        cfg_p = dataclasses.replace(cfg, use_pallas=True)
        model_p = build_model(cfg_p)
        lg_pal, _ = model_p.forward_train(params, tokens)
        np.testing.assert_allclose(np.asarray(lg_xla), np.asarray(lg_pal),
                                   rtol=2e-4, atol=2e-4)
