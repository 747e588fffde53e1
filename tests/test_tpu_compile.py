"""Ahead-of-time compiles of the main path for a TPU v5e that is
described, not attached: what the chip's compiler would refuse fails
here, at no chip time. Nothing runs, so nothing here is a result or a
time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and every test worker imports every test file. The fixture
skips only where libtpu is not installed; any other failure to describe
the topology fails the tests. The float64 guard (c) also runs on a
step lowered for the CPU, so it runs wherever the suite does.
"""

import dataclasses
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

SEQ, BATCH = 2048, 4
# A float64 value in StableHLO (``tensor<4xf64>``) or in HLO (``f64[4]``);
# metadata such as source names never matches.
F64_MLIR = re.compile(r"[<x]f64>")
F64_HLO = re.compile(r"\bf64\[")
# The name of an instruction that calls a Pallas kernel.
KERNEL_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*custom_call_target="tpu_custom_call"',
    re.MULTILINE,
)


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described-device compile can be written to the persistent cache
    # but never read back without a chip: keep the cache out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _mesh(topo, shape):
    devices = np.array(topo.devices[: int(np.prod(shape))]).reshape(shape)
    return Mesh(
        devices, ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def _qwen2_two_layers():
    from repro.configs.base import get_config

    return dataclasses.replace(get_config("qwen2-0.5b"), num_layers=2)


def _compile_train_step(mesh, gossip=None, mixing_matrix=None, cfg=None,
                        seq=SEQ):
    """Lowered and compiled qwen2-0.5b step (default: full widths, two
    layers) at ``seq`` x 4 sequences per agent. The artifacts are built
    outside ``set_mesh``: their eager init-key draw cannot run on
    described devices."""
    from repro.configs.base import ShapeConfig, get_train_config
    from repro.launch.mesh import num_agents
    from repro.launch.train import build_train_artifacts

    tcfg = get_train_config("qwen2-0.5b")
    if gossip is not None:
        tcfg = dataclasses.replace(tcfg, gossip=gossip)
    m = num_agents(mesh, tcfg.agent_layout)
    shape = ShapeConfig("compile", seq, BATCH * m, "train")
    art = build_train_artifacts(
        cfg or _qwen2_two_layers(), tcfg, shape, mesh, mixing_matrix
    )
    with jax.set_mesh(mesh):
        lowered = art.lower()
        return lowered, lowered.compile()


def test_rollout_batch_compiles_for_one_chip(topo):
    """(a) ``_run_batch`` at ``benchmarks/rollout_scale.py``'s 220-agent
    star x 256 rollouts, float64 as the pricing launch traces it."""
    from benchmarks.rollout_scale import make_batch, make_instance
    from repro.net import jax_engine
    from repro.net.simulator import compile_incidence

    sol, ov = make_instance(220)
    inc = compile_incidence(sol, ov)
    _tau, batch = make_batch(sol, ov, inc, 256)
    dev = jax_engine.device_incidence(
        inc, np.array([d.size for d in sol.demands], dtype=np.float64)
    )
    flow_source = np.array([d.source for d in sol.demands], dtype=np.int64)
    args = jax_engine.device_args(
        dev, batch.starts, batch.capacity,
        jax_engine.batch_cancel_times(inc, flow_source, batch),
    )
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = [
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                             sharding=one_chip)
        for a in args
    ]
    assert shapes[5].shape[-1] == 256  # caps [P, E_pad, R]
    with jax.enable_x64(True):
        lowered = jax_engine._run_batch.lower(*shapes)
        compiled = lowered.compile()
    assert F64_MLIR.search(lowered.as_text())
    assert compiled.memory_analysis() is not None


@pytest.fixture(scope="module")
def qwen2_step(topo):
    """The compiled qwen2-0.5b step of (b) and (e)."""
    return _compile_train_step(_mesh(topo, (1, 1)))[1]


def test_qwen2_train_step_compiles_for_one_chip(qwen2_step):
    """(b) The ``launch/train.py`` step, qwen2-0.5b at its published
    widths (two layers), on a 1x1 mesh of one described chip."""
    mem = qwen2_step.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_qwen2_step_trains_attention_through_the_kernel(qwen2_step):
    """(e) On the chip the step's attention is the blocked Pallas kernel,
    forward, recompute and backward (dq, dk/dv), and ``op_scopes`` puts
    every kernel under part ``attention``."""
    from repro.launch.train import op_scopes

    kernels = KERNEL_INSTR.findall(qwen2_step.as_text())
    scopes = op_scopes(qwen2_step)
    assert {scopes[k] for k in kernels} == {
        ("forward", "attention"), ("recompute", "attention"),
        ("backward", "attention"),
    }


# One attention layer's forward and backward under remat at qwen2-0.5b
# and qwen1.5-0.5b widths, 4 x 2048 tokens, read at the parent commit
# (the jnp path, whose float32 scores are 0.94 and 1.07 GB a layer).
JNP_LAYER_TEMP = {"qwen2-0.5b": 1_463_835_648, "qwen1.5-0.5b": 1_666_170_368}
SCORES_BYTES = {"qwen2-0.5b": 4 * 14 * SEQ * SEQ * 4,
                "qwen1.5-0.5b": 4 * 16 * SEQ * SEQ * 4}


def _attention_layer_grad(name, replicated, batch):
    """One attention layer's forward and gradient under remat, at the
    published widths of ``name``, 4 x 2048 tokens, under scope
    ``attention`` as ``blocks.apply_train`` opens it: the jitted function
    and its argument shapes (weights on ``replicated``, the activations
    on ``batch``)."""
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import attention, blocks

    cfg = get_config(name)
    spec = blocks._attn_spec(cfg, "attn")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=replicated),
        jax.eval_shape(
            lambda k: attention.init(k, spec, jnp.bfloat16), jax.random.key(0)
        ),
    )
    x = jax.ShapeDtypeStruct((BATCH, SEQ, cfg.d_model), jnp.bfloat16,
                             sharding=batch)
    def attend(p, x):
        with jax.named_scope("attention"):
            return attention.apply_train(p, x, spec, jnp.bfloat16)

    layer = jax.checkpoint(attend)
    grad = jax.grad(lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)),
                    argnums=(0, 1))
    return jax.jit(grad), (params, x)


@pytest.fixture(scope="module", params=sorted(JNP_LAYER_TEMP))
def attention_layer(topo, request):
    """``(name, compiled)``: one attention layer's gradient at the widths
    of ``name``, compiled for one described chip under a 1x1 mesh."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    grad, args = _attention_layer_grad(request.param, one_chip, one_chip)
    with jax.set_mesh(_mesh(topo, (1, 1))):
        return request.param, grad.lower(*args).compile()


def test_attention_layer_holds_no_score_tensor(attention_layer):
    """(f) The kernel never builds the scores: one attention layer's
    temp falls below the jnp path's by more than a layer's float32
    scores. (The whole two-layer step's temp, 2.56 GB either way, is
    set by the head's logits, not by attention.)"""
    name, compiled = attention_layer
    assert KERNEL_INSTR.search(compiled.as_text())
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < JNP_LAYER_TEMP[name] - SCORES_BYTES[name]


# An array in an instruction's output type, with its layout's
# minor-to-major order: ``bf16[1,4,2048,16,32]{4,2,3,1,0:T(8,128)(2,1)}``.
HLO_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\](?:\{([\d,]*))?")


def _output_type(rest):
    """The output type at the head of an instruction's right-hand side:
    one array, or a parenthesised tuple of them."""
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[: i + 1]
    return rest


def _per_head_halves(compiled, scopes, heads, half):
    """Instructions under part ``attention`` (by ``scopes``, the map of
    ``op_scopes``) that output an array with a dimension of one of
    ``heads`` and ``half`` as its minor dimension."""
    from repro.launch.train import _INSTR

    found = []
    for line in compiled.as_text().splitlines():
        if (m := _INSTR.match(line)) is None:
            continue
        name, rest = m.groups()
        if scopes.get(name, ("", ""))[1] != "attention":
            continue
        for dims, layout in HLO_ARRAY.findall(_output_type(rest)):
            dims = [int(d) for d in dims.split(",") if d]
            if not dims:
                continue
            minor = dims[int(layout.split(",")[0])] if layout else dims[-1]
            if minor == half and heads & set(dims):
                found.append(name)
                break
    return found


def test_attention_layer_writes_no_per_head_half(attention_layer):
    """RoPE rotates the whole head: no instruction of the attention
    layer's gradient (its recompute and backward) outputs a per-head
    half, an array with a query or KV head dimension whose minor
    dimension is ``head_dim/2`` (the split form wrote such halves, with
    the half as the lane dimension, and glued them back). The cos/sin
    tables have no head dimension."""
    from repro.configs.base import get_config
    from repro.launch.train import op_scopes

    name, compiled = attention_layer
    cfg = get_config(name)
    scopes = op_scopes(compiled)
    assert {("recompute", "attention"),
            ("backward", "attention")} <= set(scopes.values())
    heads = {cfg.num_heads, cfg.num_kv_heads}
    half = cfg.resolved_head_dim // 2
    assert _per_head_halves(compiled, scopes, heads, half) == []


def test_attention_over_two_chips_without_a_mesh_keeps_the_jnp_core(topo):
    """Jitted over two described chips with no mesh set, the layer holds
    no kernel: GSPMD would have to partition it, which it cannot."""
    mesh = _mesh(topo, (2, 1))
    grad, args = _attention_layer_grad(
        "qwen2-0.5b",
        NamedSharding(mesh, jax.sharding.PartitionSpec()),
        NamedSharding(mesh, jax.sharding.PartitionSpec("data")),
    )
    lowered = grad.lower(*args)
    assert "tpu_custom_call" not in lowered.as_text()
    assert not KERNEL_INSTR.search(lowered.compile().as_text())


def test_cpu_step_keeps_the_jnp_attention():
    """Lowered for the CPU, a step whose shapes the kernel fits (S 128,
    head_dim 64) holds no kernel: the default path is the jnp one."""
    from repro.configs.base import get_config
    from repro.launch.mesh import make_test_mesh

    cfg = dataclasses.replace(get_config("qwen2-0.5b", smoke=True),
                              head_dim=64)
    lowered, compiled = _compile_train_step(
        make_test_mesh((1, 1), ("data", "model")), cfg=cfg, seq=128
    )
    assert "tpu_custom_call" not in lowered.as_text()
    assert "tpu_custom_call" not in compiled.as_text()


class _HloText:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


# A dq kernel and a recomputed forward kernel of the step compiled for the
# described v5e, cut short: each instruction runs over three lines, its
# ``op_name`` on the last.
_KERNEL_HLO = """HloModule m

%body.1 (p: bf16[4,2,7,2048,64]) -> bf16[4,2,7,2048,64] {
  %p = bf16[4,2,7,2048,64]{4,3,2,1,0} parameter(0)
  %splash_mqa_fwd_residuals.22 = (bf16[4,2,7,2048,64]{4,3,2,1,0}) custom-call(%p), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512}"
}}, metadata={op_name="jit(step_fn)/grads/vmap()/while/body/closed_call/transpose(jvp(blocks))/while/body/closed_call/checkpoint/rematted_computation/attention/cond/branch_0_fun/jit(flash_attention)/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/splash_mqa_fwd_residuals/pallas_call" stack_frame_id=103}, backend_config={"flag_configs":[]}
  ROOT %splash_mqa_dq_no_residuals.12 = (bf16[4,2,7,2048,64]{4,3,2,1,0}) custom-call(%p), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q_dq\\": 512, \\"block_kv_dq\\": 512}"
}}, metadata={op_name="jit(step_fn)/grads/vmap()/while/body/closed_call/transpose(jvp(blocks))/while/body/closed_call/checkpoint/attention/cond/branch_0_fun/jit(flash_attention)/vmap(vmap(jit(_splash_attention)))/splash_mqa_dq_no_residuals/splash_mqa_dq_no_residuals/pallas_call" stack_frame_id=103}, backend_config={"flag_configs":[]}
}

FileNames
1 "train.py"
"""


def test_op_scopes_reads_a_kernel_instruction_over_its_lines():
    from repro.launch.train import op_scopes

    assert op_scopes(_HloText(_KERNEL_HLO)) == {
        "p": ("unscoped", "unscoped"),
        "splash_mqa_fwd_residuals.22": ("recompute", "attention"),
        "splash_mqa_dq_no_residuals.12": ("backward", "attention"),
    }


@pytest.mark.parametrize("target", ["cpu", "v5e"])
def test_train_step_holds_no_f64_after_pricing(target, request):
    """(c) Pricing in the same process (import + a float64 launch) must
    leave the train step's program free of float64: the step at full
    widths for the described chip, and at smoke size for the CPU."""
    from repro.configs.base import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.net import (
        build_overlay,
        compute_categories,
        demands_from_links,
        line_underlay,
        route_direct,
    )
    from repro.net.jax_engine import simulate_jax

    u = line_underlay(2, capacity=125_000.0)
    ov = build_overlay(u, [0, 1])
    sol = route_direct(
        demands_from_links([(0, 1)], 1e6, 2), compute_categories(ov), 1e6
    )
    assert simulate_jax(sol, ov).makespan == pytest.approx(8.0)
    if target == "cpu":
        lowered, compiled = _compile_train_step(
            make_test_mesh((1, 1), ("data", "model")),
            cfg=get_config("qwen2-0.5b", smoke=True), seq=32,
        )
    else:
        mesh = _mesh(request.getfixturevalue("topo"), (1, 1))
        lowered, compiled = _compile_train_step(mesh)
    assert not F64_MLIR.search(lowered.as_text())
    assert not F64_HLO.search(compiled.as_text())


def test_sparse_gossip_step_compiles_for_four_chips(topo):
    """(d) Four qwen2-0.5b agents on the described 2x2, one per chip,
    gossiping over a sparse 4-ring: the program holds the ppermute
    schedule as ``collective-permute``."""
    from repro.core.weight_opt import optimize_weights

    w = optimize_weights(
        4, [(0, 1), (1, 2), (2, 3), (0, 3)], steps=150
    ).matrix
    mesh = _mesh(topo, (4, 1))
    _lowered, compiled = _compile_train_step(
        mesh, gossip="sparse", mixing_matrix=w
    )
    assert "collective-permute" in compiled.as_text()
    params = compiled.input_shardings[0][0]["params"]
    leaf = jax.tree.leaves(params)[0]
    assert isinstance(leaf, NamedSharding)
    assert leaf.spec[0] == "data"  # agent axis: one agent per chip
