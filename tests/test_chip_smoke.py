"""``chip_smoke.py`` on the CPU: each one-chip phase at the reduced preset
(the chip runs them at published widths), and no result without a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() == "cpu"
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_colocated_serving(smoke):
    out = smoke.colocated_serving(reduced=True)
    assert (out["requests"], out["shed"]) == (8, 0)
    assert out["be_quanta"] >= 1


def test_served_logits_match_reference(smoke):
    out = smoke.served_logits(reduced=True)
    assert out["positions"] == 2 * (3 + 1)
    assert set(out["rel_rms_err"]) == {"7", "13"}
    assert max(out["rel_rms_err"].values()) <= smoke.REL_RMS_TOL


def test_served_logits_catch_a_wrong_cache(smoke, monkeypatch):
    """Decoding from a zeroed SSM state must fail the logits check."""
    from repro.serving import ServingEngine
    real = ServingEngine._insert_slot

    def forget(self, slot, cache):
        real(self, slot, {k: v * 0 for k, v in cache.items()})

    monkeypatch.setattr(ServingEngine, "_insert_slot", forget)
    with pytest.raises(smoke.SmokeFailure, match="logit"):
        smoke.served_logits(reduced=True)


def test_tally_kernels(smoke):
    out = smoke.tally_kernels(reduced=True)
    assert out["be_grid"][0] == 8
    for cfg in ("slice:4", "preempt:4"):
        assert out[cfg]["hp_rel_err"] <= smoke.KERNEL_RTOL
        assert out[cfg]["be_rel_err"] <= smoke.KERNEL_RTOL
    # interpreted on the CPU: no Mosaic kernel in the compiled HLO
    assert set(out["tpu_custom_call"].values()) == {0}
