"""CPU rehearsal of ``chip_smoke.py``: each one-chip phase at smoke size
(the four-chip phase runs in ``test_multidevice.py``'s forced-device
subprocess), and the guard that refuses to run anywhere but a TPU."""

import dataclasses
import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bf16_smoke_config():
    """qwen2-0.5b's smoke config in the published bfloat16 numerics."""
    from repro.configs.base import get_config

    return dataclasses.replace(
        get_config("qwen2-0.5b", smoke=True),
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )


def test_price_phase_matches_host_engine(smoke):
    out = smoke.price(num_agents=12, rollouts=8, checked=8)
    assert out["worst_rel_err"] <= smoke.PRICE_RTOL
    assert 0 <= out["worst_rollout"] < 8


def test_design_phase_matches_host_engine(smoke):
    out = smoke.design_phase(num_agents=8, rollouts=16)
    assert out["worst_rel_err"] <= smoke.PRICE_RTOL


def test_train_phase_loss_falls_and_matches_fp32(smoke):
    out = smoke.train(cfg=bf16_smoke_config(), seq=32, batch=4, steps=5)
    losses = out["losses"]
    assert len(losses) == 5 and losses[-1] < losses[0]
    assert out["loss_rel_err"] <= smoke.LOSS_RTOL


def test_failed_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke._check(False, "boom")


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "not a TPU" in captured.err
