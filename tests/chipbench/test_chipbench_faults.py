"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a run (set-up, window, check against the
plain reference) on the CPU at a small size, skipping only the entry
point's look for a chip, with one fault planted where the program
produces its result: a step that returns its state unchanged, half of
each batch left out (the mean taken over the rest), a priced answer
altered, half of a launch's answers missing. A sound run of the same
size must come out correct.
"""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run as R  # noqa: E402
from chipbench.bench import Cell, Spec  # noqa: E402

SEED = 2**31 + 777
TINY_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "vocab_size": 512}


PRICE = "price.roofnet10.r256"


def spec() -> Spec:
    """``BENCHMARK.json`` with the cells prepared under
    ``chipbench/prepared/`` but not yet in it."""
    s = Spec(ROOT)
    names = {w["name"] for w in s.data["workloads"]}
    for p in sorted((ROOT / "chipbench/prepared").glob("*.json")):
        extra = json.loads(p.read_text())
        if all(w["name"] not in names for w in extra["workloads"]):
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                s.data[key] = s.data[key] + extra[key]
    return s


def small_cell(workload: str) -> Cell:
    """The cell with its sizes cut to what a test can hold; its limits
    are the cell's own."""
    c = spec().cell(workload)
    if c.driver == "price":
        cfg = dict(c.config, num_agents=5)
        tr = dict(c.traffic, rollouts=8, pool=2, check_rollouts=16)
    else:
        cfg = {k: v for k, v in c.config.items() if k != "registry"}
        cfg.update(TINY_MODEL)
        tr = dict(c.traffic, seq_len=32, pool=4)
    return Cell(c.name, c.chips, c.config_name, cfg, c.traffic_name, tr,
                c.limits, c.end_to_end, c.per_layer)


def execute(cell: Cell) -> dict:
    s = spec()
    run = R.Run(cell=cell, seed=SEED, seconds=0.5, trace=False,
                devices=jax.devices()[:cell.chips],
                peaks=s.peaks("TPU v5 lite"), t0=time.perf_counter())
    return R.execute(s, run)


def driver(cell: Cell):
    return spec().driver(cell)


def broken_step(monkeypatch, cell, wrap):
    """Plant ``wrap(step)`` in place of the compiled train step."""
    drv = driver(cell)
    build = drv.build

    def planted(c):
        st = build(c)
        st.step = wrap(st.step)
        return st

    monkeypatch.setattr(drv, "build", planted)


# -- pricing ----------------------------------------------------------------


def test_price_sound_run_is_correct():
    out = execute(small_cell(PRICE))
    assert out["correct"], out["checks"]
    assert out["metrics"]["price_rollouts_per_s"]["value"] > 0


def _patch_launch(monkeypatch, alter):
    from repro.net import jax_engine

    real = jax_engine.simulate_rollout_batch
    monkeypatch.setattr(jax_engine, "simulate_rollout_batch",
                        lambda *a, **k: alter(real(*a, **k)))


def test_price_altered_answer_is_caught(monkeypatch):
    import dataclasses

    def alter(results):
        out = []
        for r in results:
            fc = list(r.flow_completion)
            fc[3] *= 1 + 1e-6
            out.append(dataclasses.replace(r, flow_completion=tuple(fc)))
        return tuple(out)

    _patch_launch(monkeypatch, alter)
    out = execute(small_cell(PRICE))
    assert not out["correct"]
    assert out["checks"]["flow_completion_rel_err"]["value"] > 1e-7


def test_price_half_the_answers_missing_is_caught(monkeypatch):
    _patch_launch(monkeypatch, lambda res: res[: len(res) // 2])
    out = execute(small_cell(PRICE))
    assert not out["correct"]
    assert out["failed"] > 0


# -- training on one chip ---------------------------------------------------


@pytest.mark.parametrize("workload", ["train.qwen2-0.5b.solo",
                                      "train.qwen1.5-0.5b.solo"])
def test_train_sound_run_is_correct(workload):
    out = execute(small_cell(workload))
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_train_step_that_returns_its_state_is_caught(monkeypatch):
    cell = small_cell("train.qwen2-0.5b.solo")

    def wrap(step):
        def frozen(state, batch):
            _, met = step(jax.tree.map(jnp.copy, state), batch)
            return state, met
        return frozen

    broken_step(monkeypatch, cell, wrap)
    out = execute(cell)
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_the_batch_left_out_is_caught(monkeypatch):
    cell = small_cell("train.qwen2-0.5b.solo")

    def wrap(step):
        def half(state, batch):
            t = batch["tokens"]
            n = t.shape[2] // 2  # rows: the rest repeats the first half
            return step(state, {"tokens": jnp.concatenate(
                [t[:, :, :n], t[:, :, :n]], axis=2)})
        return half

    broken_step(monkeypatch, cell, wrap)
    out = execute(cell)
    assert not out["correct"]
