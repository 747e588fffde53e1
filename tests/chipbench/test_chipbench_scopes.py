"""The train step's device time named by program scope.

``repro.launch.train.op_scopes`` on the program's real step, compiled on
the CPU at a tiny qwen-shaped size (2 layers, width 64, vocab 512, a 1x1
mesh): every instruction gets one (phase, part), and each phase and part
of the step is there. The readers (``chipbench/scopes.py`` and the
``train.*_ms_per_step`` / ``train.unscoped_share`` metrics) on a
hand-built trace with known op times; the accepted readers and the
breakdown read the same on it; an executable without program scopes
gives no reading.
"""

import dataclasses
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import run as R  # noqa: E402
from chipbench import scopes  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from repro.launch import train as T  # noqa: E402
from test_chipbench_faults import small_cell, spec  # noqa: E402

CELL = "train.qwen2-0.5b.solo"
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.MULTILINE)
NEW = ["train.forward_ms_per_step", "train.recompute_ms_per_step",
       "train.backward_ms_per_step", "train.optimizer_ms_per_step",
       "train.attention_ms_per_step", "train.mlp_ms_per_step",
       "train.head_loss_ms_per_step", "train.unscoped_share"]


def tiny_cell(remat: str, microbatch: int = 1):
    c = small_cell(CELL)
    return dataclasses.replace(
        c, config=dict(c.config, remat=remat),
        traffic=dict(c.traffic, microbatch=microbatch))


@pytest.fixture(scope="module", params=[("full", 1), ("none", 2)],
                ids=["remat_full", "remat_none_2_microbatches"])
def compiled(request):
    """(remat, microbatches, the tiny cell's compiled step) as the train
    driver builds it."""
    cell = tiny_cell(*request.param)
    return (*request.param, spec().driver(cell).build(cell).step)


def test_every_instruction_gets_one_scope(compiled):
    *_, step = compiled
    scopes_ = T.op_scopes(step)
    names = INSTR.findall(step.as_text())
    assert names and set(names) == set(scopes_)
    for phase, part in scopes_.values():
        assert phase in T.PHASES
        assert isinstance(part, str) and part


def test_phases_and_parts_of_the_step_are_all_there(compiled):
    remat, microbatches, step = compiled
    found = set(T.op_scopes(step).values())
    phases = {p for p, _ in found}
    parts = {q for _, q in found}
    assert {"forward", "backward", "optimizer"} <= phases
    assert {"attention", "mlp", "head_loss", "embed"} <= parts
    # One microbatch: XLA folds the accumulation into the gradient.
    assert (("optimizer", "grad_accumulate") in found) == (microbatches > 1)
    assert ("recompute" in phases) == (remat == "full")
    assert "gossip" not in phases  # one agent: no mixing


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/grads/vmap(transpose(jvp(blocks)))/while/body/closed_call"
     "/checkpoint/rematted_computation/attention/dot_general",
     ("recompute", "attention")),
    ("jit(step)/grads/vmap(transpose(jvp(blocks)))/while/body/closed_call"
     "/checkpoint/mlp/dot_general", ("backward", "mlp")),
    ("jit(step_fn)/grads/vmap()/while/body/closed_call/jvp(blocks)/while"
     "/body/closed_call/rsqrt", ("forward", "block_other")),
    ("jit(step_fn)/grads/vmap()/while/body/closed_call/transpose("
     "jvp(head_loss))/mul", ("backward", "head_loss")),
    ("jit(step_fn)/grads/vmap()/while/body/closed_call/jvp(embed)/gather",
     ("forward", "embed")),
    ("jit(step_fn)/grads/vmap()/while/body/grad_accumulate/add",
     ("optimizer", "grad_accumulate")),
    ("jit(step_fn)/optimizer/mul", ("optimizer", "optimizer")),
    ("jit(step_fn)/gossip/shard_map/ppermute", ("gossip", "gossip")),
    ("jit(step_fn)/grads/vmap()/while/body/dynamic_slice",
     ("forward", "grads")),
    # A fused op joining several names takes the first.
    ("jit(step_fn)/optimizer/mul;jit(step_fn)/grads/vmap()/jvp(blocks)/"
     "attention/add", ("optimizer", "optimizer")),
    # Whole components only: an op or a scope merely containing a name.
    ("jit(step_fn)/mlp_like/attention_mask_fn/transpose", T.UNSCOPED),
    ("jit(step_fn)/add", T.UNSCOPED),
    ("", T.UNSCOPED),
    (None, T.UNSCOPED),
])
def test_scope_of(op_name, want):
    assert T.scope_of(op_name) == want


@pytest.mark.parametrize("kind, scope", [
    ("attn", "attention"), ("attn_moe", "attention"), ("swa", "attention"),
    ("local", "attention"), ("global", "attention"), ("mamba", "mamba"),
    ("mamba_moe", "mamba"), ("mlstm", "mlstm"), ("slstm", "slstm")])
def test_each_mixer_kind_opens_a_part_scope(kind, scope):
    from repro.models import blocks

    assert blocks._mixer_scope(kind) == scope
    assert scope in T.MODEL_PARTS


class FakeCompiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_a_fusion_without_a_name_takes_its_root_name():
    text = """HloModule m

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/optimizer/add"}
  ROOT %b = f32[4]{0} bitcast(%a)
}

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %c = f32[4]{0} copy(%x)
  ROOT %fusion.3 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused_computation.1
}
"""
    got = T.op_scopes(FakeCompiled(text))
    assert got["fusion.3"] == ("optimizer", "optimizer")
    assert got["c"] == got["x"] == T.UNSCOPED
    assert set(got) == {"p", "a", "b", "x", "c", "fusion.3"}


DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
# Op name -> its scope, and on each device the op's intervals in a window
# of [0, 1000) ns holding 2 steps.
MAP = {"f.attn": ("forward", "attention"), "r.attn": ("recompute",
       "attention"), "b.mlp": ("backward", "mlp"),
       "b.head": ("backward", "head_loss"), "opt": ("optimizer",
       "optimizer"), "acc": ("optimizer", "grad_accumulate"),
       "cp": T.UNSCOPED}
OPS = {
    DEV0: [("f.attn", 0, 100), ("r.attn", 100, 160), ("b.mlp", 160, 400),
           ("b.head", 400, 500), ("opt", 500, 540), ("acc", 540, 560),
           ("cp", 560, 580), ("mystery.7", 580, 600), ("while.1", 0, 600)],
    DEV1: [("f.attn", 0, 140), ("r.attn", 140, 200), ("b.mlp", 200, 400),
           ("b.head", 400, 540), ("opt", 540, 560), ("acc", 560, 600),
           ("cp", 600, 620), ("mystery.7", 620, 620)],
}
STEPS = 2
# Per device per step, averaged over the two devices, in ms.
WANT_MS = {
    "train.forward_ms_per_step": (100 + 140) / 2 / STEPS / 1e6,
    "train.recompute_ms_per_step": (60 + 60) / 2 / STEPS / 1e6,
    "train.backward_ms_per_step": (340 + 340) / 2 / STEPS / 1e6,
    "train.optimizer_ms_per_step": (60 + 60) / 2 / STEPS / 1e6,
    "train.attention_ms_per_step": (160 + 200) / 2 / STEPS / 1e6,
    "train.mlp_ms_per_step": (240 + 200) / 2 / STEPS / 1e6,
    "train.head_loss_ms_per_step": (100 + 140) / 2 / STEPS / 1e6,
}
# "cp" and the unmapped "mystery.7" are unscoped; "while.1" is a
# container, in no op total.
WANT_UNSCOPED = 100.0 * (40 + 20) / (600 + 620)


def reading():
    s = spec()
    cell = s.cell(CELL)
    run = R.Run(cell=cell, seed=1, seconds=1.0, trace=True,
                devices=[None, None], peaks={"bf16_flops_per_s": 1e12})
    run.window_s = 1e-6
    run.counts = {"steps": STEPS, "tokens": 4096, "flops_per_token": 10.0}
    trace = tr.Trace(ops={k: list(v) for k, v in OPS.items()},
                     spans=[("chipbench.window", 0.0, 1000.0),
                            ("chipbench.step", 0.0, 300.0),
                            ("chipbench.collect", 600.0, 1000.0)])
    return s, R.Reading(cell, run, tr.reduce(trace))


@pytest.fixture
def mapped(monkeypatch):
    """The readers take ``MAP`` as the step's map."""
    monkeypatch.setattr(scopes, "scope_map", lambda run: MAP)


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_known_time_per_step(metric, mapped):
    s, rd = reading()
    got = s.reader(metric).read(rd)
    want = WANT_UNSCOPED if metric == "train.unscoped_share" else \
        WANT_MS[metric]
    assert got == pytest.approx(want, rel=1e-12)


def test_phases_and_unscoped_add_up_to_the_op_time(mapped):
    s, rd = reading()
    total = sum(s.reader(f"train.{p}_ms_per_step").read(rd)
                for p in ("forward", "recompute", "backward", "optimizer"))
    unscoped = s.reader("train.unscoped_share").read(rd) / 100.0
    op_ms = sum(sum(d.op_ns.values()) for d in rd.summary.devices.values()
                ) / 2 / STEPS / 1e6
    assert total == pytest.approx(op_ms * (1 - unscoped), rel=1e-12)


def test_accepted_readers_and_breakdown_read_the_same(capsys, mapped):
    """On one fixture trace: ``train.mfu``, ``train.device_idle_share``
    and the breakdown read their known values, before and after the new
    readers ran, which also print one ``scopes`` line."""
    s, rd = reading()

    def accepted():
        return (s.reader("train.mfu").read(rd),
                s.reader("train.device_idle_share").read(rd),
                rd.summary.top_ops(), rd.summary.top_gaps())

    mfu, idle, ops, gaps = accepted()
    assert mfu == pytest.approx(100.0 * 4096 * 10.0 / 1e-6 / 2e12)
    assert idle == pytest.approx(100.0 * (1 - (600 + 620) / 2 / 1000))
    assert ops[0] == ["b.mlp", pytest.approx(220e-9)]
    assert gaps == [["chipbench.collect", pytest.approx(400e-9)],
                    ["chipbench.collect", pytest.approx(380e-9)]]
    for m in NEW:
        s.reader(m).read(rd)
    assert accepted() == (mfu, idle, ops, gaps)
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("scopes ")]
    assert len(lines) == 1
    assert '"b.mlp", 2.2e-07, "backward", "mlp"' in lines[0]


def test_readers_find_nothing_without_the_program_map(monkeypatch):
    monkeypatch.delattr(T, "op_scopes")
    s, rd = reading()
    for m in NEW:
        assert s.reader(m).read(rd) is None


def test_readers_find_nothing_in_an_executable_without_scopes(monkeypatch):
    """A step compiled before the program opened its scopes (a stale
    compile cache hands it back) maps every op to unscoped: the readers
    give None, not 0 ms and 100% unscoped."""
    text = """HloModule m

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %f.attn = f32[4]{0} add(%x, %x), metadata={op_name="jit(f)/add"}
}
"""
    stale = T.op_scopes(FakeCompiled(text))
    assert set(stale.values()) == {T.UNSCOPED}
    monkeypatch.setattr(scopes, "scope_map", lambda run: stale)
    s, rd = reading()
    for m in NEW:
        assert s.reader(m).read(rd) is None


def test_the_map_is_rebuilt_from_the_cell_step():
    """The reader builds the cell's step again (here the tiny cell, on
    the CPU) and maps its instructions."""
    cell = tiny_cell("full")
    run = R.Run(cell=cell, seed=1, seconds=1.0, trace=True, devices=[],
                peaks={})
    got = scopes.scope_map(run)
    assert ("recompute", "attention") in set(got.values())
