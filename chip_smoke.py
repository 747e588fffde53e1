#!/usr/bin/env python3
"""Chip smoke test: the design -> price -> train path on one TPU chip.

    python chip_smoke.py               # one chip: price, design, train
    python chip_smoke.py --four-chips  # four chips: sparse vs dense gossip

Every phase runs in this one process (a chip belongs to one process at
a time) and raises on a failed check, so the script exits 0 only when
all of them pass. It refuses to run anywhere but a TPU: with any other
first device it exits non-zero before the first phase. The last line
of stdout is ``{"ok": true, "device": {...}}``; everything else comes
before it.

Phases (one chip):

price   ``jax_engine.simulate_rollout_batch`` at the 220-agent star x 256
        Markov-fading rollouts of ``benchmarks/rollout_scale.py``, first
        launch (compile) then warm; each of the first 32 rollouts'
        makespans checked against ``engine="batched"`` on the host.
design  ``design("fmmd-wp")`` on the Roofnet-like 8-agent instance of
        ``examples/train_dfl.py``, priced by ``evaluate_design`` with 256
        stochastic rollouts on ``engine="jax"``; tau_mean/p95/p99 checked
        against ``engine="batched"`` on the same seeds.
train   five ``launch/train.py`` steps of qwen2-0.5b at its published
        widths on a 1x1 ("data", "model") mesh, seq 2048 x 4 sequences of
        seeded synthetic tokens: the loss stays finite and falls, and the
        first and last steps' losses match a float32 forward of the
        same parameters and batch.

``--four-chips`` runs only the 4-agent path: one qwen2-0.5b agent per
chip, a sparse 4-ring mixing matrix, one step with ``gossip="sparse"``
(ppermute schedule) and one with ``gossip="dense"`` from the same state
and batch, twice: at learning rate 0 from distinct agent inits (the
gossip alone) and at the configured learning rate from the shared init
(the whole step; see ``four_chips``). The parameters must agree, every
agent must sit on its own chip, and the sparse program must hold
``collective-permute``.

The phases are plain functions with size arguments so the CPU tests can
rehearse them at smoke size; only ``main`` checks the device and turns
on the compilation cache.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Pricing parity bound: per-rollout makespan and design tau statistics
# against the numpy engine (the repo's stated jax-engine contract).
PRICE_RTOL = 1e-9
# bfloat16 train-step loss vs a float32 forward of the same parameters:
# 1.8e-5 relative on a TPU v5e, so 1e-3 leaves ~50x headroom while a
# broken layer or a wrong dtype on the bf16 path still trips it.
LOSS_RTOL = 1e-3
# Sparse (ppermute, float32 accumulate in schedule order) and dense
# (float32 einsum) gossip each round a float32 mix to bfloat16 once, so
# they may differ by one bf16 ulp: at most 2**-7 of a leaf's largest
# entry (read on a TPU v5e: 1/234, one ulp).
GOSSIP_RTOL = 2.0**-7
# On top of that ulp, the two steps' local updates may differ by this
# share of a leaf's largest update. They are separate XLA programs whose
# bfloat16 backward passes round differently; read on a TPU v5e: 0.0072,
# at the zero-init key bias, whose true gradient is zero (a key bias
# shifts every logit of a query alike) so its update is rounding residue.
UPDATE_RTOL = 2.0**-5


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ---------------------------------------------------------------------------
# Phase 1: price
# ---------------------------------------------------------------------------


def price(num_agents: int = 220, rollouts: int = 256,
          checked: int = 32) -> dict:
    """Monte-Carlo rollout batch on the device vs the host engine."""
    from benchmarks.rollout_scale import make_batch, make_instance
    from repro.net import jax_engine, simulate
    from repro.net.simulator import compile_incidence

    sol, ov = make_instance(num_agents)
    inc = compile_incidence(sol, ov)
    _tau, batch = make_batch(sol, ov, inc, rollouts)

    t0 = time.perf_counter()
    first = jax_engine.simulate_rollout_batch(sol, ov, batch, incidence=inc)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    priced = jax_engine.simulate_rollout_batch(sol, ov, batch, incidence=inc)
    warm_s = time.perf_counter() - t0
    _check(
        [r.makespan for r in first] == [r.makespan for r in priced],
        "price: compile and warm launches disagree",
    )

    worst, worst_at = 0.0, -1
    for r in range(checked):
        ref = simulate(sol, ov, scenario=batch.realizations[r],
                       engine="batched", incidence=inc).makespan
        err = abs(priced[r].makespan - ref) / abs(ref)
        if not err <= worst:  # NaN lands here too
            worst, worst_at = err, r
    _log("price", agents=num_agents, rollouts=rollouts, checked=checked,
         worst_rel_err=repr(worst), worst_rollout=worst_at,
         first_launch_s=f"{compile_s:.3f}", warm_launch_s=f"{warm_s:.3f}")
    _check(worst <= PRICE_RTOL,
           f"price: rollout {worst_at} makespan off by {worst!r} "
           f"(bound {PRICE_RTOL})")
    return {"worst_rel_err": worst, "worst_rollout": worst_at}


# ---------------------------------------------------------------------------
# Phase 2: design
# ---------------------------------------------------------------------------


def design_phase(num_agents: int = 8, rollouts: int = 256) -> dict:
    """FMMD-WP design priced by stochastic rollouts, jax vs batched."""
    from repro.configs.base import get_config
    from repro.core import ConvergenceConstants, design, evaluate_design
    from repro.models import model as M
    from repro.net import (
        MarkovLinkModel,
        StochasticScenario,
        build_overlay,
        compute_categories,
        lowest_degree_nodes,
        mid_path_edges,
        roofnet_like,
    )

    constants = ConvergenceConstants(epsilon=0.05)
    underlay = roofnet_like(seed=0)
    overlay = build_overlay(underlay, lowest_degree_nodes(underlay,
                                                          num_agents))
    cats = compute_categories(overlay)
    # The payload the train phase's agents would gossip: bf16 qwen2-0.5b.
    kappa = M.parameter_count(get_config("qwen2-0.5b")) * 2
    out = design("fmmd-wp", cats, kappa, num_agents, overlay=overlay,
                 iterations=12, constants=constants)
    hops = mid_path_edges(overlay, out.design.activated_links)
    _check(len(hops) > 0, "design: no mid-path hop to modulate")
    sto = StochasticScenario(
        links=(MarkovLinkModel(
            edges=tuple(hops), scales=(1.0, 0.2),
            transition=((0.8, 0.2), (0.3, 0.7)),
        ),),
        step=max(out.tau / 2, 1.0), horizon=8 * max(out.tau, 1.0),
    )
    priced = {
        engine: evaluate_design(
            out.design, cats, kappa, num_agents, constants,
            overlay=overlay, stochastic=sto,
            stochastic_rollouts=rollouts, engine=engine,
        )
        for engine in ("jax", "batched")
    }
    errs = {}
    for field in ("tau_mean", "tau_p95", "tau_p99"):
        got = getattr(priced["jax"], field)
        ref = getattr(priced["batched"], field)
        _check(np.isfinite(got) and np.isfinite(ref),
               f"design: {field} not finite (jax {got!r}, host {ref!r})")
        errs[field] = abs(got - ref) / abs(ref)
    worst_field = max(errs, key=errs.get)
    _log("design", agents=num_agents, rollouts=rollouts,
         links=len(out.design.activated_links), rho=f"{out.rho:.4f}",
         tau_mean=repr(priced["jax"].tau_mean),
         tau_p99=repr(priced["jax"].tau_p99),
         worst_rel_err=repr(errs[worst_field]), worst_field=worst_field)
    _check(errs[worst_field] <= PRICE_RTOL,
           f"design: {worst_field} off by {errs[worst_field]!r} "
           f"(bound {PRICE_RTOL})")
    return {"worst_rel_err": errs[worst_field], "worst_field": worst_field}


# ---------------------------------------------------------------------------
# Phase 3: train
# ---------------------------------------------------------------------------


def _tokens(cfg, num_agents: int, per_agent: int, seq: int,
            seed: int = 0) -> np.ndarray:
    """[A, 1, per_agent, seq + 1] int32 seeded synthetic tokens, one
    non-IID stream per agent (microbatch axis of size 1)."""
    from repro.data import DataConfig, SyntheticTokenStream

    stream = SyntheticTokenStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, num_agents=num_agents,
        seed=seed,
    ))
    return np.stack([
        stream.batch(a, 0, per_agent, seq)[None] for a in range(num_agents)
    ])


def _reference_loss(cfg, params, tokens: np.ndarray) -> float:
    """float32 forward of one agent's ``params`` on ``tokens``
    [B, S + 1] under highest matmul precision — one sequence per call
    so the float32 logits stay a quarter of the batch's."""
    from repro.models import model as M

    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    loss = jax.jit(
        lambda p, t: M.loss(cfg32, p, {"tokens": t}, remat=False)[1]["ce"]
    )
    with jax.default_matmul_precision("highest"):
        per_seq = [float(loss(p32, tokens[i:i + 1]))
                   for i in range(tokens.shape[0])]
    return float(np.mean(per_seq))


def train(cfg=None, seq: int = 2048, batch: int = 4, steps: int = 5,
          seed: int = 0) -> dict:
    """``steps`` train steps of ``cfg`` (default qwen2-0.5b) on one
    device, one fixed batch."""
    from repro.configs.base import ShapeConfig, get_config, get_train_config
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build_train_artifacts

    cfg = cfg or get_config("qwen2-0.5b")
    tcfg = get_train_config("qwen2-0.5b")
    mesh = make_test_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("smoke", seq, batch, "train")
    # Built outside set_mesh: the eager init-key draw needs no mesh.
    art = build_train_artifacts(cfg, tcfg, shape, mesh)
    tokens = _tokens(cfg, 1, batch, seq, seed)
    _check(tokens.shape == art.batch_shapes["tokens"].shape,
           f"train: batch {tokens.shape} != {art.batch_shapes['tokens']}")
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        step = art.jit().lower(art.state_shapes, art.batch_shapes).compile()
        compile_s = time.perf_counter() - t0
        state = art.init_state(jax.random.key(seed))
        device_batch = jax.device_put({"tokens": tokens},
                                      art.batch_shardings)
        losses, refs = [], {}
        for i in range(steps):
            if i in (0, steps - 1):  # before the step donates the state
                refs[i] = _reference_loss(
                    cfg, jax.tree.map(lambda x: x[0], state["params"]),
                    tokens[0, 0],
                )
            state, metrics = step(state, device_batch)
            losses.append(float(metrics["loss"]))
    stats = jax.devices()[0].memory_stats() or {}
    rels = {i: abs(losses[i] - ref) / abs(ref) for i, ref in refs.items()}
    rel = max(rels.values())
    _log("train", model=cfg.name, seq=seq, batch=batch,
         losses=[round(x, 5) for x in losses],
         ref_losses={i: round(r, 5) for i, r in refs.items()},
         loss_rel_err={i: f"{r:.3e}" for i, r in rels.items()},
         compile_s=f"{compile_s:.3f}",
         peak_bytes_in_use=stats.get("peak_bytes_in_use", "n/a"))
    _check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    _check(losses[-1] < losses[0], f"train: loss did not fall {losses}")
    for i, r in rels.items():
        _check(r <= LOSS_RTOL,
               f"train: step {i} loss {losses[i]} vs float32 reference "
               f"{refs[i]} (rel {r:.3e} > {LOSS_RTOL})")
    return {"losses": losses, "ref_losses": refs, "loss_rel_err": rel}


# ---------------------------------------------------------------------------
# --four-chips: sparse vs dense gossip across four devices
# ---------------------------------------------------------------------------


def _agent_devices(params) -> dict:
    """agent index -> set of devices holding that agent's slice, over
    every leaf of the stacked ``params``."""
    where: dict[int, set] = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            a = shard.index[0].start or 0
            where.setdefault(a, set()).add(shard.device)
    return where


@jax.jit
def _leaf_stats(a, b, p0):
    """Per leaf, in float32 and reduced on device: (max|a - b|, max|b|,
    max|b - p0|)."""
    def stats(x, y, z):
        x, y, z = (t.astype(jnp.float32) for t in (x, y, z))
        return (jnp.max(jnp.abs(x - y)), jnp.max(jnp.abs(y)),
                jnp.max(jnp.abs(y - z)))

    return jax.tree.map(stats, a, b, p0)


def _sparse_dense_step(cfg, tcfg, shape, mesh, w, tokens, init):
    """One step with ``gossip="sparse"`` and one with ``"dense"`` from the
    state ``init(arts)`` makes, on one batch. Returns, per leaf name,
    (max|sparse - dense|, max|dense|, max|dense - start|); whether the
    sparse program holds ``collective-permute``; and agent -> devices of
    the sparse step's parameters."""
    from repro.launch.train import build_train_artifacts

    arts = {
        mode: build_train_artifacts(
            cfg, dataclasses.replace(tcfg, gossip=mode), shape, mesh, w
        )
        for mode in ("sparse", "dense")
    }
    with jax.set_mesh(mesh):
        compiled = {
            mode: art.jit(donate=False).lower(
                art.state_shapes, art.batch_shapes
            ).compile()
            for mode, art in arts.items()
        }
        has_permute = "collective-permute" in compiled["sparse"].as_text()
        state = init(arts["sparse"])
        batch = jax.device_put({"tokens": tokens},
                               arts["sparse"].batch_shardings)
        out = {mode: fn(state, batch)[0]["params"]
               for mode, fn in compiled.items()}
    stats = jax.tree_util.tree_flatten_with_path(
        _leaf_stats(out["sparse"], out["dense"], state["params"]),
        is_leaf=lambda x: isinstance(x, tuple),
    )[0]
    per_leaf = {jax.tree_util.keystr(path): tuple(float(v) for v in s)
                for path, s in stats}
    return per_leaf, has_permute, _agent_devices(out["sparse"])


def _worst(per_leaf: dict, rel) -> tuple[float, str]:
    """Largest ``rel(gap, scale, moved)`` over the leaves, and its leaf."""
    worst, where = 0.0, ""
    for leaf, s in per_leaf.items():
        r = rel(*s)
        if not r <= worst:  # NaN lands here too
            worst, where = r, leaf
    return worst, where


def _tiny(x: float) -> float:
    return max(x, np.finfo(np.float32).tiny)


def four_chips(cfg=None, seq: int = 2048, per_agent: int = 4,
               seed: int = 0) -> dict:
    """D-PSGD steps of four agents on a (4, 1) mesh, gossiping over a
    sparse 4-ring with the ppermute schedule and with the dense einsum,
    compared twice:

    gossip  distinct agent inits at learning rate 0, so both programs'
            local updates return the state bitwise and the mixes are the
            one difference: within ``GOSSIP_RTOL`` of each leaf's
            largest entry.
    step    the shared init of a D-PSGD start at the configured learning
            rate: the whole step (gradients, ``sgd.update``, gossip).
            The two programs' bfloat16 backward passes need not round
            alike, so beyond the gossip's ulp the parameters may differ
            by ``UPDATE_RTOL`` of each leaf's largest update.
    """
    from repro.configs.base import ShapeConfig, get_config, get_train_config
    from repro.core.weight_opt import optimize_weights
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as M
    from repro.optim import sgd

    m = 4
    cfg = cfg or get_config("qwen2-0.5b")
    _check(len(jax.devices()) >= m, f"four_chips: {len(jax.devices())} "
           "device(s), need 4")
    mesh = make_test_mesh((m, 1), ("data", "model"))
    w = optimize_weights(
        m, [(0, 1), (1, 2), (2, 3), (0, 3)], steps=150
    ).matrix
    _check(np.count_nonzero(np.abs(w) > 1e-12) < m * m,
           "four_chips: mixing matrix is not sparse")
    shape = ShapeConfig("smoke4", seq, m * per_agent, "train")
    tcfg = get_train_config("qwen2-0.5b")
    tokens = _tokens(cfg, m, per_agent, seq, seed)

    def distinct_init(arts):
        def init(key):
            params = jax.vmap(lambda k: M.init(cfg, k))(
                jax.random.split(key, m))
            return {"params": params, "opt": jax.vmap(sgd.init)(params),
                    "step": jnp.zeros((), jnp.int32)}

        return jax.jit(init, out_shardings=arts.state_shardings)(
            jax.random.key(seed))

    gossip, has_permute, placed = _sparse_dense_step(
        cfg, dataclasses.replace(tcfg, learning_rate=0.0), shape, mesh, w,
        tokens, distinct_init,
    )
    gossip_rel, gossip_leaf = _worst(gossip, lambda g, s, u: g / _tiny(s))
    moved, _ = _worst(gossip, lambda g, s, u: u / _tiny(s))
    step, step_permute, _ = _sparse_dense_step(
        cfg, tcfg, shape, mesh, w, tokens,
        lambda arts: arts.init_state(jax.random.key(seed)),
    )
    step_rel, step_leaf = _worst(step, lambda g, s, u: g / _tiny(s))
    # Beyond the gossip's ulp, as a share of the leaf's largest update.
    update_rel, update_leaf = _worst(
        step, lambda g, s, u: max(g - GOSSIP_RTOL * s, 0.0) / _tiny(u)
    )
    stepped, _ = _worst(step, lambda g, s, u: u)
    devices = [d for a in sorted(placed) for d in placed[a]]
    _log("four_chips", agents=m, model=cfg.name, seq=seq,
         per_agent=per_agent, collective_permute=has_permute and step_permute,
         agent_devices={a: sorted(str(x) for x in v)
                        for a, v in sorted(placed.items())},
         gossip_rel=repr(gossip_rel), gossip_leaf=gossip_leaf,
         gossip_moved_rel=repr(moved), step_rel=repr(step_rel),
         step_leaf=step_leaf, update_rel=repr(update_rel),
         update_leaf=update_leaf)
    _check(has_permute and step_permute, "four_chips: no "
           "collective-permute in the sparse-gossip program")
    _check(sorted(placed) == list(range(m))
           and all(len(v) == 1 for v in placed.values())
           and len(set(devices)) == m,
           f"four_chips: agents not one per device: {placed}")
    _check(moved > 10 * GOSSIP_RTOL,
           f"four_chips: gossip left the parameters in place ({moved!r})")
    _check(gossip_rel <= GOSSIP_RTOL,
           f"four_chips: sparse vs dense gossip differ by {gossip_rel!r} "
           f"at {gossip_leaf} (bound {GOSSIP_RTOL})")
    _check(stepped > 0, "four_chips: the step left the parameters in place")
    _check(update_rel <= UPDATE_RTOL,
           f"four_chips: sparse vs dense steps' updates differ by "
           f"{update_rel!r} at {update_leaf} (bound {UPDATE_RTOL})")
    return {"gossip_rel": gossip_rel, "gossip_moved_rel": moved,
            "step_rel": step_rel, "update_rel": update_rel,
            "collective_permute": has_permute and step_permute}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sparse-vs-dense gossip path")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first device is {dev.platform!r}, not a TPU; "
              "refusing to run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    _log("setup", device_kind=dev.device_kind, count=len(jax.devices()),
         compile_cache=use_compile_cache())
    if args.four_chips:
        four_chips()
    else:
        price()
        design_phase()
        train()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
