"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases, run here at small sizes (the four-chip phase on four virtual
devices), pass."""
import importlib.util
import pathlib

import jax
import pytest
from conftest import run_multidevice

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_tpu(chip_smoke, capsys):
    platform = jax.devices()[0].platform
    assert platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    assert repr(platform) in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_fabric_phase(chip_smoke):
    tel = chip_smoke.fabric_phase()
    assert tel["fused_waves"] >= 1
    assert tel["scheduled"] == tel["requests"] == chip_smoke.FABRIC_REQUESTS
    assert tel["init_requests"] == (chip_smoke.FABRIC_REQUESTS
                                    // chip_smoke.FABRIC_INIT_SHARE)


def test_serve_phase_smoke_config(chip_smoke, mesh1):
    tel = chip_smoke.serve_phase(jax.devices()[0], n_layers=None,
                                 smoke=True, n_requests=2, prompt_len=8,
                                 new_tokens=4)
    assert tel["peak_tenants"] == 1
    assert tel["init_requests"] >= 2


@pytest.mark.slow
def test_four_chip_phase_on_four_virtual_devices():
    out = run_multidevice(f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.models.moe import MoEConfig
cs.four_chip_phase(jax.devices()[:4], moe_cfg=MoEConfig(256, 384, 16, 2),
                   a2a_rows=8, width=128, moe_tokens=(2, 64))
print("FOUR_OK")
""", n_devices=4)
    assert "MoE nom vs xla max |diff|=0.0" in out
    assert "FOUR_OK" in out
