"""Distributed D-PSGD training step (pjit + shard_map gossip).

One step per agent (paper eq. (2), compute ∥ exchange form):

  1. per-agent gradients over the stacked agent axis (vmap), with
     gradient accumulation over ``microbatch`` chunks,
  2. local SGD-momentum update,
  3. gossip mixing of the parameters — sparse ppermute schedule,
     dense einsum, or all-reduce (W = J), per the designed mixing matrix.

State pytree: {"params": [A, ...], "opt": {"momentum": [A, ...]},
"step": i32[]} — stacked leading agent axis A on every leaf.

``build_train_artifacts`` returns everything the dry-run and the real
launcher need: the step function, NamedShardings for state and batch, and
abstract input shapes. ``op_scopes`` maps each op of the compiled step to
the named scopes (phase, model part) the step and the model open.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import gossip as gossip_lib
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_rules
from repro.models import model as M
from repro.optim import sgd


@dataclasses.dataclass
class TrainArtifacts:
    step_fn: Callable                    # (state, batch) -> (state, metrics)
    state_shapes: Any                    # ShapeDtypeStructs (stacked agents)
    batch_shapes: Any
    state_shardings: Any                 # NamedShardings
    batch_shardings: Any
    num_agents: int
    mixing_matrix: np.ndarray | None
    init_state: Callable[[jax.Array], Any]  # key -> concrete state

    def jit(self, donate: bool = True):
        """Steady-state jit: outputs land on the input shardings so the
        train loop round-trips without resharding; state is donated."""
        return jax.jit(
            self.step_fn,
            in_shardings=(self.state_shardings, self.batch_shardings),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )

    def lower(self):
        return self.jit(donate=False).lower(
            self.state_shapes, self.batch_shapes
        )


# Named scopes the model and the step open (``jax.named_scope``), read
# back from the compiled step's HLO metadata by ``op_scopes``.
MODEL_PARTS = ("embed", "attention", "mamba", "mlstm", "slstm", "mlp", "moe",
               "head_loss")
PROGRAM_SCOPES = MODEL_PARTS + ("blocks", "grads", "grad_accumulate",
                                "optimizer", "gossip")
PHASES = ("forward", "recompute", "backward", "optimizer", "gossip",
          "unscoped")
UNSCOPED = ("unscoped", "unscoped")

_WRAPPED = re.compile(r"^([\w.]+)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def _components(op_name: str) -> list[tuple[tuple[str, ...], str]]:
    """``(wrappers, name)`` of each ``/``-separated component of a name
    stack: ``transpose(jvp(blocks))`` is ``(("transpose", "jvp"),
    "blocks")``."""
    parts, depth, cur = [], 0, ""
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    parts.append(cur)
    out = []
    for comp in parts:
        wrappers = []
        while (m := _WRAPPED.match(comp)) is not None:
            wrappers.append(m.group(1))
            comp = m.group(2)
        out.append((tuple(wrappers), comp))
    return out


def scope_of(op_name: str | None) -> tuple[str, str]:
    """``(phase, part)`` of one op from its ``op_name`` metadata.

    A fused op whose name joins several with ``;`` takes the first.
    Transform wrappers (``vmap(…)``, ``jvp(…)``, ``transpose(…)``) are
    stripped and only whole components match. Phase: ``optimizer`` under
    ``optimizer`` or ``grad_accumulate``; ``gossip`` under ``gossip``;
    else ``recompute`` under ``rematted_computation``, ``backward`` under
    a ``transpose(`` wrapper, ``forward`` otherwise. Part: the innermost
    of ``MODEL_PARTS``; under ``blocks`` alone ``block_other``; else the
    innermost step scope (``grads``, ``optimizer``, …). An op under no
    program scope, or with no name, is ``("unscoped", "unscoped")``.
    """
    if not op_name:
        return UNSCOPED
    comps = _components(op_name.split(";", 1)[0])
    names = [n for _, n in comps]
    scoped = [n for n in names if n in PROGRAM_SCOPES]
    if not scoped:
        return UNSCOPED
    if "optimizer" in names or "grad_accumulate" in names:
        phase = "optimizer"
    elif "gossip" in names:
        phase = "gossip"
    elif "rematted_computation" in names:
        phase = "recompute"
    elif any("transpose" in w for w, _ in comps):
        phase = "backward"
    else:
        phase = "forward"
    parts = [n for n in scoped if n in MODEL_PARTS]
    if parts:
        part = parts[-1]
    elif scoped[-1] == "blocks":
        part = "block_other"
    else:
        part = scoped[-1]
    return phase, part


def op_scopes(compiled) -> dict[str, tuple[str, str]]:
    """``{hlo_instruction_name: (phase, part)}`` for every instruction of
    a compiled step (``compiled.as_text()``), by ``scope_of`` of its
    ``metadata={op_name="…"}``. A fusion with no name of its own takes
    the name of the last instruction of its fused computation that has
    one (the root, where it has one). A device trace names its ops by
    these instructions, so the map joins a trace of the step. An
    instruction printed over several lines (a Pallas kernel's
    ``kernel_metadata``, with ``op_name`` after it) is read whole."""
    comps: dict[str, list[tuple[str, str]]] = {}
    body: list[tuple[str, str]] = []
    for line in compiled.as_text().splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split("(", 1)[0].split()
            body = comps.setdefault(head[-1].lstrip("%"), [])
        elif (m := _INSTR.match(line)) is not None:
            body.append((m.group(1), m.group(2)))
        elif line.rstrip() == "}":
            body = []
        elif body and line.strip():
            body[-1] = (body[-1][0], body[-1][1] + line)

    named: dict[str, str | None] = {}

    def comp_name(comp: str) -> str | None:
        if comp not in named:
            found = [n for _, rest in comps.get(comp, ()) if (n := own(rest))]
            named[comp] = found[-1] if found else None
        return named[comp]

    def own(rest: str) -> str | None:
        if (m := _OP_NAME.search(rest)) is not None:
            return m.group(1)
        if (m := _CALLS.search(rest)) is not None:
            return comp_name(m.group(1))
        return None

    return {name: scope_of(own(rest))
            for instrs in comps.values() for name, rest in instrs}


def _batch_shapes(
    cfg: ModelConfig, shape: ShapeConfig, num_agents: int, microbatch: int
) -> dict:
    per_agent = shape.global_batch // max(num_agents, 1)
    k = max(microbatch, 1)
    if per_agent % k != 0:
        k = 1
    mb = per_agent // k
    s = shape.seq_len
    shapes = {}
    if cfg.frontend == "vision_patches":
        text = s - cfg.num_patches
        shapes["tokens"] = jax.ShapeDtypeStruct(
            (num_agents, k, mb, text + 1), jnp.int32
        )
        shapes["patch_embeds"] = jax.ShapeDtypeStruct(
            (num_agents, k, mb, cfg.num_patches, cfg.d_model), jnp.bfloat16
        )
    else:
        shapes["tokens"] = jax.ShapeDtypeStruct(
            (num_agents, k, mb, s + 1), jnp.int32
        )
    return shapes


def _stacked_state_shapes(cfg: ModelConfig, num_agents: int) -> Any:
    params = jax.eval_shape(lambda k: M.init(cfg, k), jax.random.key(0))
    opt = jax.eval_shape(lambda p: sgd.init(p), params)

    def stack(x):
        return jax.ShapeDtypeStruct((num_agents,) + x.shape, x.dtype)

    return {
        "params": jax.tree.map(stack, params),
        "opt": jax.tree.map(stack, opt),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def build_train_artifacts(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    shape: ShapeConfig,
    mesh: jax.sharding.Mesh,
    mixing_matrix: np.ndarray | None = None,
    learning_rate: Callable | None = None,
) -> TrainArtifacts:
    """Assemble the distributed train step for one (arch × shape) cell.

    ``mixing_matrix`` must be m×m for m = number of agents implied by the
    layout and mesh; None ⇒ identity (no gossip; m=1 cells).
    """
    agent_axes = mesh_lib.agent_axes(mesh, tcfg.agent_layout)
    m = mesh_lib.num_agents(mesh, tcfg.agent_layout)
    if mixing_matrix is not None and mixing_matrix.shape[0] != m:
        raise ValueError(
            f"mixing matrix is {mixing_matrix.shape[0]}x…, layout implies m={m}"
        )

    state_shapes = _stacked_state_shapes(cfg, m)
    batch_shapes = _batch_shapes(cfg, shape, m, tcfg.microbatch)

    param_specs = shard_rules.param_specs_train(
        state_shapes["params"], mesh, tcfg.agent_layout
    )
    state_specs = {
        "params": param_specs,
        "opt": {"momentum": param_specs},
        "step": P(),
    }
    batch_specs = jax.tree.map(
        lambda spec: P(spec[0], None, *spec[1:]),  # insert microbatch dim
        shard_rules.batch_specs_train(
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    (x.shape[0],) + x.shape[2:], x.dtype
                ),
                batch_shapes,
            ),
            mesh,
            tcfg.agent_layout,
        ),
        is_leaf=lambda s: isinstance(s, P),
    )

    # float32: the dtype sgd.update computes in for every param dtype,
    # and never float64, whatever the process's x64 flag.
    lr_fn = learning_rate or (
        lambda step: jnp.asarray(tcfg.learning_rate, jnp.float32)
    )

    # Gossip mode resolution.
    mode = tcfg.gossip
    schedule = None
    w_arr = None
    if mixing_matrix is None or m <= 1:
        mode = "none"
    else:
        w_arr = np.asarray(mixing_matrix, np.float64)
        is_j = np.allclose(w_arr, np.full((m, m), 1.0 / m), atol=1e-9)
        if mode == "auto":
            nnz = np.count_nonzero(
                np.abs(w_arr - np.diag(np.diag(w_arr))) > 1e-12
            )  # directed activated edges
            # ppermute schedule ships nnz·κ bytes total vs the clique
            # all-gather's m(m−1)·κ — sparse wins for any non-clique.
            mode = (
                "allreduce" if is_j else
                ("sparse" if nnz < m * (m - 1) else "dense")
            )
        if mode == "sparse":
            schedule = gossip_lib.build_schedule(w_arr)

    def loss_for_agent(params, batch_mb):
        total, metrics = M.loss(
            cfg,
            params,
            batch_mb,
            moe_aux_weight=tcfg.moe_aux_weight,
            router_z_weight=tcfg.router_z_weight,
            remat=(tcfg.remat != "none"),
        )
        return total, metrics

    def grads_for_agent(params, batch_agent):
        """Gradient accumulation over the leading microbatch dim."""
        k = jax.tree.leaves(batch_agent)[0].shape[0]

        def one(mb):
            (l, metr), g = jax.value_and_grad(loss_for_agent, has_aux=True)(
                params, mb
            )
            return l, metr, g

        def acc_step(carry, mb):
            l0, g0 = carry
            l, metr, g = one(mb)
            with jax.named_scope("grad_accumulate"):
                return (
                    l0 + l / k,
                    jax.tree.map(
                        lambda a, b: a + b.astype(a.dtype) / k, g0, g
                    ),
                ), metr

        with jax.named_scope("grad_accumulate"):
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
        (loss, grads), _ = jax.lax.scan(
            acc_step, (jnp.zeros((), jnp.float32), zeros), batch_agent
        )
        if tcfg.agent_layout == "data_dp":
            # Accumulate fp32 locally, reduce in bf16: halves the
            # cross-"model" gradient all-reduce (§Perf iteration 2).
            grads = jax.tree.map(
                lambda g: g.astype(jnp.bfloat16), grads
            )
        return loss, grads

    from repro.models.sharding_hints import hints

    # Per-agent activations: the batch role maps to the intra-agent FSDP
    # axis ("pod" layout), the repurposed "model" axis ("data_dp"
    # layout), or nothing ("data" — each agent's microbatch lives wholly
    # on its own data rank).
    role_axes = {
        "batch": {
            "pod": ("data",),
            "data_dp": ("model",),
            "data": (),
        }[tcfg.agent_layout],
        "tp": ("model",) if tcfg.agent_layout != "data_dp" else (),
        # sequence-parallel boundaries (no-op for data_dp: "model" is DP)
        "seq": ("model",) if tcfg.agent_layout != "data_dp" else (),
    }

    def step_fn(state, batch):
        params, opt, step = state["params"], state["opt"], state["step"]
        with hints(role_axes), jax.named_scope("grads"):
            loss, grads = jax.vmap(grads_for_agent)(params, batch)
        lr = lr_fn(step)
        with jax.named_scope("optimizer"):
            new_params, new_opt = sgd.update(
                grads, opt, params, lr, momentum=tcfg.momentum
            )
        # Gossip mixing (paper eq. (2)): mix the post-update parameters.
        with jax.named_scope("gossip"):
            if mode == "allreduce":
                new_params = gossip_lib.mix_allreduce(new_params)
            elif mode == "dense":
                new_params = gossip_lib.mix_dense(
                    new_params, jnp.asarray(w_arr)
                )
            elif mode == "sparse":
                if tcfg.agent_layout == "data_dp":
                    # Params are replicated over "model": gossip the raveled
                    # tree sliced over that axis (no redundant traffic).
                    new_params = gossip_lib.mix_sparse_flat(
                        new_params, schedule, mesh, agent_axes, ("model",)
                    )
                else:
                    new_params = gossip_lib.mix_sparse_shardmap(
                        new_params, schedule, mesh, agent_axes, param_specs
                    )
        new_state = {"params": new_params, "opt": new_opt, "step": step + 1}
        metrics = {"loss": jnp.mean(loss), "lr": lr}
        return new_state, metrics

    to_sharding = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )

    state_shardings = to_sharding(state_specs)

    def init_state(key) -> Any:
        def init_one(k):
            p = M.init(cfg, k)
            return {"params": p, "opt": sgd.init(p)}

        keys = jax.random.split(key, m)
        # Identical init across agents (standard D-PSGD start): fold key 0.
        stacked = jax.vmap(init_one)(jnp.broadcast_to(keys[0], keys.shape))
        state = {
            "params": stacked["params"],
            "opt": stacked["opt"],
            "step": jnp.zeros((), jnp.int32),
        }
        return jax.device_put(state, state_shardings)

    return TrainArtifacts(
        step_fn=step_fn,
        state_shapes=state_shapes,
        batch_shapes=batch_shapes,
        state_shardings=state_shardings,
        batch_shardings=to_sharding(batch_specs),
        num_agents=m,
        mixing_matrix=w_arr,
        init_state=init_state,
    )
