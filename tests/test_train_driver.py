"""Training driver end-to-end: loss decreases, checkpoint/restart exact.

The checkpoint/microbatching/adafactor end-to-end runs compile large
reduced models and dominate suite wall time; they carry the ``slow``
marker (run with ``pytest -m slow``).
"""
import jax
import numpy as np
import pytest

from repro.launch.train import train


@pytest.mark.slow
def test_training_reduces_loss():
    out = train("mamba2-130m", steps=12, batch=4, seq=32, reduced=True,
                log_every=100)
    assert np.isfinite(out["last_loss"])
    assert out["loss_drop"] > 0.1


@pytest.mark.slow
def test_checkpoint_restart_is_exact(tmp_path):
    """Run 8 steps straight vs 4 + restart + 4: identical final params."""
    kw = dict(steps=8, batch=2, seq=32, reduced=True, log_every=100,
              lr=1e-2)
    straight = train("qwen2.5-14b", **kw)

    d = str(tmp_path / "ck")
    train("qwen2.5-14b", ckpt_dir=d, ckpt_every=4, total_steps=8,
          **{**kw, "steps": 4})
    resumed = train("qwen2.5-14b", ckpt_dir=d, ckpt_every=100,
                    resume=True, **kw)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-4, atol=2e-5),
        straight["params"], resumed["params"])


@pytest.mark.slow
def test_microbatched_grad_accumulation_matches():
    """num_microbatches=2 must equal one big batch (same data, fp32)."""
    a = train("qwen2.5-14b", steps=3, batch=4, seq=32, reduced=True,
              num_microbatches=1, log_every=100, lr=1e-3)
    b = train("qwen2.5-14b", steps=3, batch=4, seq=32, reduced=True,
              num_microbatches=2, log_every=100, lr=1e-3)
    # CE mean over microbatches == CE over batch (same token count)
    np.testing.assert_allclose(a["losses"], b["losses"], rtol=5e-3)


@pytest.mark.slow
def test_adafactor_arch_trains():
    out = train("arctic-480b", steps=6, batch=2, seq=32, reduced=True,
                log_every=100)
    assert np.isfinite(out["last_loss"])


def test_train_step_compiles_once(monkeypatch):
    """The initial state is placed as the step returns it, so later steps
    reuse the first step's executable."""
    steps, real_jit = [], jax.jit

    def jit(fn, **kw):
        out = real_jit(fn, **kw)
        if "donate_argnums" in kw:              # the driver's train step
            steps.append(out)
        return out

    monkeypatch.setattr(jax, "jit", jit)
    train("mamba2-130m", steps=3, batch=2, seq=32, reduced=True,
          log_every=100)
    assert [f._cache_size() for f in steps] == [1]


def test_compile_cache_dir(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` is left to JAX; otherwise the cache
    sits at the repo's fixed ``.jax_cache/``."""
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(compile_cache.REPO_ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert (compile_cache.REPO_ROOT / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_host_mesh_on_given_devices():
    """A mesh over a subset of the devices, with Auto axes so the steps'
    sharding constraints apply."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(2, devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert mesh.devices.flat[0] == jax.devices()[0]
    assert set(mesh.axis_types) == {AxisType.Auto}
