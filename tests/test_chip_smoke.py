"""``chip_smoke.py``'s phases on the CPU at the smoke size.

The script itself refuses to run without a TPU; these tests drive its
phases — HTTP serving, the bf16/int8 logit checks with their fp8 control,
and the one-stage-per-device comparison — on the smoke config with the
Pallas kernel interpreted, so a change that breaks the chip check fails
here first.
"""
import importlib.util
from pathlib import Path

import pytest

import jax

from repro.configs import get_smoke_config
from repro.models import init
from repro.serving import EngineConfig

EC = EngineConfig(max_batch=4, max_len=96, prompt_len=16)
LENS = (5, 16, 40, 64)


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("smollm_360m")
    return cfg, init(cfg, jax.random.key(0))


@pytest.fixture
def interpreted(chip_smoke, monkeypatch):
    """The CPU runs the kernel interpreted, which the chip check refuses."""
    monkeypatch.setattr(chip_smoke, "assert_compiled_kernels",
                        lambda rt: None)
    return chip_smoke


def test_refuses_cpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""


def test_one_chip_phase(interpreted, model, capsys):
    cfg, params = model
    interpreted.one_chip(cfg, params, interpreted.CompileClock(), ec=EC,
                         prompt_lens=LENS, check_len=40)
    out = capsys.readouterr().out
    for tag in ("[serve-http]", "[logits bf16-kv]", "[logits control]",
                "[logits int8-kv]"):
        assert tag in out


def test_four_chips_phase(interpreted, model, capsys):
    if jax.device_count() < 4:
        pytest.skip(f"needs 4 host devices, have {jax.device_count()}")
    cfg, params = model
    interpreted.four_chips(cfg, params, interpreted.CompileClock(), ec=EC,
                           prompt_lens=LENS, check_len=40)
    assert "identical=4/4" in capsys.readouterr().out
