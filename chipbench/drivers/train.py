"""Training driver: D-PSGD steps of the program's ``launch/train.py``.

Set-up builds the program's train step for the cell (agents on a
``(agents, 1)`` mesh, one per chip, gossip as the mix says), compiles it
with its state donated, makes the weights and a pool of token batches on
the device from ``--seed``, and drives that one compiled step through
its first three steps on the first three batches. Those three are the
warm-up and what the check reads: each step's loss, every leaf's norm
of the first gradient as the optimizer holds it (the momentum after one
step, which starts at zero), and every leaf's norm of the parameters'
change after three steps. The window then goes on stepping the same
state, cycling through the pool, one step in flight behind the one
being waited for.

Check: the plain reference (``reference/qwen.py``) follows the same
three steps from the same weights and batches in float32; the driver
compares the losses and the two sets of norms.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check as chk
from chipbench import flops, inputs
from chipbench.reference import qwen
from chipbench.run import Check, span

CHECKED_STEPS = 3

# BENCHMARK config keys (the published config.json's) -> the program's.
PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
REFERENCE_KEYS = tuple(PROGRAM_KEYS) + ("head_dim",)


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file; where the
    file names a registry entry, the two must agree."""
    from repro.configs.base import ModelConfig, get_config

    fields = {PROGRAM_KEYS[k]: cfg[k] for k in PROGRAM_KEYS}
    mc = ModelConfig(
        name=cfg.get("registry", "chipbench"), family="dense",
        block_pattern=("attn",), qkv_bias=bool(cfg["attention_bias"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        head_dim=cfg.get("head_dim"), **fields,
    )
    if "registry" in cfg and get_config(cfg["registry"]) != mc:
        raise ValueError(f"{cfg['registry']}: the program's registry entry "
                         f"differs from the configuration file")
    return mc


def reference_dims(cfg: dict) -> dict:
    return {k: cfg[k] for k in REFERENCE_KEYS if cfg.get(k) is not None}


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    mesh: object
    step: object  # the compiled, state-donating train step
    make_params: object
    make_tokens: object
    zeros: object
    step_sharding: object
    seed: int = 0
    state: object = None
    batches: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    grad_norms: list = dataclasses.field(default_factory=list)
    update_norms: list = dataclasses.field(default_factory=list)
    first_grads: list = dataclasses.field(default_factory=list)  # host
    window_losses: list = dataclasses.field(default_factory=list)
    # Seconds of set-up spent reading what the check compares, not warming.
    check_s: float = 0.0

    @property
    def agents(self) -> int:
        return int(self.traffic["agents"])

    def keys(self):
        return inputs.seed_key(self.seed, 0), inputs.seed_key(self.seed, 1)


def build(cell) -> State:
    """Compile the cell's train step and its input makers."""
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build_train_artifacts

    cfg, tr = cell.config, cell.traffic
    agents = int(tr["agents"])
    mc = model_config(cfg)
    tcfg = TrainConfig(
        agent_layout=cfg["agent_layout"], remat=cfg["remat"],
        learning_rate=float(cfg["learning_rate"]),
        momentum=float(cfg["momentum"]), gossip=tr["gossip"],
        microbatch=int(tr["microbatch"]),
    )
    if agents != 1:
        raise ValueError("the reference steps one agent: a cell of several "
                         "needs the reference's gossip first")
    mesh = make_test_mesh((agents, 1), ("data", "model"))
    shape = ShapeConfig(cell.name, int(tr["seq_len"]),
                        agents * int(tr["per_agent_batch"]), "train")
    art = build_train_artifacts(mc, tcfg, shape, mesh)
    with jax.set_mesh(mesh):
        step = art.jit().lower(art.state_shapes, art.batch_shapes).compile()
    mom = art.state_shapes["opt"]["momentum"]
    zeros = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), mom),
        out_shardings=art.state_shardings["opt"]["momentum"])
    return State(
        cfg=cfg, traffic=tr, mesh=mesh, step=step,
        make_params=inputs.param_maker(art.state_shapes["params"],
                                       art.state_shardings["params"]),
        make_tokens=inputs.token_maker(art.batch_shapes["tokens"].shape,
                                       mc.vocab_size,
                                       art.batch_shardings["tokens"]),
        zeros=zeros, step_sharding=art.state_shardings["step"],
    )


def start(st: State, seed: int) -> None:
    """Weights and batches from ``seed``; the first three steps, read."""
    st.seed = seed
    pkey, tkey = st.keys()
    with jax.set_mesh(st.mesh):
        st.state = {
            "params": st.make_params(pkey),
            "opt": {"momentum": st.zeros()},
            "step": jax.device_put(jnp.zeros((), jnp.int32),
                                   st.step_sharding),
        }
        st.batches = [{"tokens": st.make_tokens(tkey, i)}
                      for i in range(int(st.traffic["pool"]))]
        losses = []
        for i in range(CHECKED_STEPS):
            st.state, met = st.step(st.state, st.batches[i])
            losses.append(met["loss"])
            if i == 0:
                jax.block_until_ready(st.state)
                t = time.perf_counter()
                g1 = chk.stacked_norms(st.state["opt"]["momentum"])
                # On the host, so it holds no chip memory in the window.
                first = jax.device_get(st.state["opt"]["momentum"])
                st.check_s += time.perf_counter() - t
        jax.block_until_ready(st.state)
        t = time.perf_counter()
        p0 = st.make_params(pkey)
        dp = jax.block_until_ready(
            chk.stacked_delta_norms(st.state["params"], p0))
        del p0
        st.losses = [float(x) for x in losses]
        st.grad_norms = chk.unstack(g1, st.agents)
        st.update_norms = chk.unstack(dp, st.agents)
        st.first_grads = [jax.tree.map(lambda x, a=a: x[a], first)
                          for a in range(st.agents)]
        st.check_s += time.perf_counter() - t


def setup(run) -> State:
    st = build(run.cell)
    start(st, run.seed)
    run.check_s += st.check_s
    return st


def tokens_per_step(tr: dict) -> int:
    return int(tr["agents"]) * int(tr["per_agent_batch"]) * int(tr["seq_len"])


def measure(run, st: State) -> None:
    tr = run.cell.traffic
    pool = len(st.batches)
    steps, pending, done = 0, None, []
    with jax.set_mesh(st.mesh), run.window():
        t_start = time.perf_counter()
        deadline = t_start + run.window_seconds
        while True:
            with span("step"):
                st.state, met = st.step(
                    st.state, st.batches[(CHECKED_STEPS + steps) % pool])
            steps += 1
            if pending is not None:
                with span("collect"):
                    pending.block_until_ready()
                done.append(time.perf_counter())
            pending = met["loss"]
            st.window_losses.append(pending)
            if time.perf_counter() >= deadline:
                break
        with span("collect"):
            pending.block_until_ready()
        elapsed = time.perf_counter() - t_start
        done.append(t_start + elapsed)
    tokens = steps * tokens_per_step(tr)
    run.window_s = elapsed
    run.attempted = steps
    run.counts = {
        "steps": steps,
        "tokens": tokens,
        "flops_per_token": flops.train_flops_per_token(
            run.cell.config, int(tr["seq_len"])),
    }
    run.note_gaps(t_start, done)
    run.end_to_end["train_tokens_per_s"] = tokens / elapsed


def agent_slice(x, a: int):
    """Agent ``a``'s slice of a leaf stacked on a leading agent axis, on
    the device that holds it."""
    for sh in x.addressable_shards:
        lo = sh.index[0].start or 0
        hi = sh.index[0].stop or x.shape[0]
        if lo <= a < hi:
            return sh.data[a - lo]
    raise ValueError(f"agent {a} is on no local device")


def reference(st: State, precision: str = "highest", rows: int | None = None,
              against: list | None = None, keep_first: bool = False) -> dict:
    """The reference's three steps from the run's weights and batches:
    losses, first-gradient norms and update norms, per agent (with
    ``keep_first``, its first gradient too, on the host). With
    ``against`` (per agent, another run's first gradient on the host),
    also each leaf's norm of the difference. ``rows`` (fewer than the
    batch) plants the fault the check must catch."""
    cfg, tr = st.cfg, st.traffic
    dims = reference_dims(cfg)
    agents = st.agents
    rows = rows or int(tr["per_agent_batch"])
    lr, mu = float(cfg["learning_rate"]), float(cfg["momentum"])
    pkey, tkey = st.keys()
    p0 = st.make_params(pkey)
    start_p = [jax.tree.map(lambda x, a=a: agent_slice(x, a), p0)
               for a in range(agents)]
    del p0
    devices = [next(iter(jax.tree.leaves(p)[0].devices())) for p in start_p]
    params = [jax.tree.map(lambda x: x.astype(jnp.float32), p)
              for p in start_p]
    moms = [jax.tree.map(jnp.zeros_like, p) for p in params]
    acc_fn = qwen.accumulator(dims, precision)
    losses, grad_norms, grad_diffs, first = [], [], None, None
    for s in range(CHECKED_STEPS):
        toks = st.make_tokens(tkey, s)
        batch = [agent_slice(toks, a)[0] for a in range(agents)]  # [B, S+1]
        accs = [jax.tree.map(jnp.zeros_like, p) for p in params]
        step_losses = [[] for _ in range(agents)]
        for i in range(rows):
            for a in range(agents):
                accs[a], loss = acc_fn(accs[a], params[a], batch[a][i],
                                       1.0 / rows)
                step_losses[a].append(loss)
        losses.append(float(np.mean([np.mean([float(x) for x in ls])
                                     for ls in step_losses])))
        if s == 0:
            grad_norms = [chk.flat(chk.agent_norms(g)) for g in accs]
            first = [jax.device_get(g) for g in accs] if keep_first else None
            if against is not None:
                grad_diffs = [chk.flat(chk.agent_delta_norms(
                    g, jax.device_put(against[a], devices[a])))
                    for a, g in enumerate(accs)]
        for a in range(agents):
            params[a], moms[a] = qwen.sgd_update(params[a], moms[a], accs[a],
                                                 lr, mu)
        del accs
    update_norms = [chk.flat(chk.agent_delta_norms(params[a], start_p[a]))
                    for a in range(agents)]
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "first_grads": first,
            "grad_diffs": grad_diffs}


def readings(got: dict, want: dict) -> dict:
    """The numbers compared: the worst step's loss error, the worst leaf
    gap of the first gradient, the worst leaf gap of the three steps'
    change, and (where ``want`` was run against ``got``'s first
    gradient) the worst leaf's norm of the first gradient's
    difference."""
    errs = [abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])]
    errs = [e if np.isfinite(e) else float("inf") for e in errs]
    grad, grad_at = chk.worst_gap(got["grad_norms"], want["grad_norms"])
    upd, upd_at = chk.worst_gap(got["update_norms"], want["update_norms"],
                                chk.still_leaves(want["grad_norms"]))
    out = {}
    if want.get("grad_diffs") is not None:
        out["grad_diff"], out["grad_diff_at"] = chk.worst_share(
            want["grad_diffs"], want["grad_norms"])
    return {**out, "loss_rel_err": max(errs),
            "grad_norm_gap": grad, "update_norm_gap": upd,
            "grad_norm_gap_at": grad_at, "update_norm_gap_at": upd_at}


def program(st: State) -> dict:
    return {"losses": st.losses, "grad_norms": st.grad_norms,
            "update_norms": st.update_norms, "first_grads": st.first_grads}


def release(st: State) -> None:
    """Drop the program's state so the reference has the chip."""
    st.state = None
    st.batches = []
    st.step = None


def check(run, st: State) -> None:
    nonfinite = sum(not np.isfinite(float(x)) for x in st.window_losses)
    got = program(st)
    release(st)
    t0 = time.perf_counter()
    want = reference(st, against=got["first_grads"])
    numbers = readings(got, want)
    print(f"reference {time.perf_counter() - t0:.1f} s; losses: program {got['losses']} reference {want['losses']}; "
          f"worst gradient leaf {numbers['grad_norm_gap_at']}, worst "
          f"update leaf {numbers['update_norm_gap_at']}", file=sys.stderr)
    for name, limit in run.cell.limits.items():
        run.checks.append(Check(name, float(numbers[name]), float(limit)))
    run.checks.append(Check("nonfinite_losses", float(nonfinite), 0.0))
