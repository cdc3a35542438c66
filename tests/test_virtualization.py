"""Real-mode Tally server: end-to-end functional correctness with actual
Pallas kernels — priority enforcement, transformed BE execution with exact
numerics, client-side state caching."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.virtualization import TallyServer
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_desc
from repro.kernels.matmul import matmul_desc

RNG = np.random.default_rng(11)


@pytest.fixture()
def server():
    return TallyServer()


def _mm_case(m=96, k=256, n=384):
    # blocks (16, 128) x (128, 128): lane dims aligned to 128 as on a TPU
    a = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    return matmul_desc(m, k, n, bm=16, bk=128, bn=128), (a, b), \
        ref.matmul_ref(a, b)


def test_priority_and_numerics(server):
    hp = server.register("hp", priority=0)
    be = server.register("be", priority=1)
    d_be, args_be, want_be = _mm_case(96, 256, 384)
    d_hp, args_hp, want_hp = _mm_case(32, 256, 384)
    job_be = be.launch(d_be, *args_be)
    job_hp = hp.launch(d_hp, *args_hp)
    server.serve_until_idle(max_seconds=180)
    np.testing.assert_allclose(job_hp.result(0)[0], want_hp,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(job_be.result(0)[0], want_be,
                               rtol=5e-4, atol=1e-5)
    assert job_hp.complete_t <= job_be.complete_t


def test_be_kernel_is_transformed(server):
    be = server.register("be", priority=1)
    desc, args, want = _mm_case(96, 256, 384)
    job = be.launch(desc, *args)
    server.serve_until_idle(max_seconds=180)
    np.testing.assert_allclose(job.result(0)[0], want, rtol=5e-4,
                               atol=1e-5)
    cfg = server.profiler.lookup_launch_config(job)
    assert cfg is not None and cfg.mode in ("slice", "preempt")


def test_flash_attention_through_server(server):
    be = server.register("be", priority=1)
    BH, S, D, G = 4, 32, 8, 2
    q = jnp.asarray(RNG.normal(size=(BH, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(BH // G, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(BH // G, S, D)), jnp.float32)
    desc = flash_attention_desc(BH, S, S, D, G, causal=True, bq=8, bk=8)
    job = be.launch(desc, q, k, v)
    server.serve_until_idle(max_seconds=180)
    want = ref.attention_ref(q, k, v, causal=True, group=G)
    np.testing.assert_allclose(job.result(0)[0], want, rtol=1e-3,
                               atol=1e-4)


def test_client_side_state_caching(server):
    c = server.register("c", priority=0)
    assert c.device_info("sm_count") == 8
    before = c.forwarded_calls
    for _ in range(5):
        c.device_info("sm_count")
    assert c.forwarded_calls == before        # served from local cache
    assert c.cached_calls >= 5


def test_hp_runs_untransformed(server):
    hp = server.register("hp", priority=0)
    desc, args, want = _mm_case(48, 256, 256)
    job = hp.launch(desc, *args)
    server.serve_until_idle(max_seconds=180)
    np.testing.assert_allclose(job.result(0)[0], want, rtol=5e-4,
                               atol=1e-5)
    # HP kernels bypass the profiler entirely (launched immediately)
    assert server.profiler.lookup_launch_config(job) is None
