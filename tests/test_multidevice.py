"""Multi-device coverage via subprocess (needs its own XLA device count)."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import gossip
from repro.core.weight_opt import optimize_weights
from repro.launch.mesh import make_test_mesh
from repro.launch.train import build_train_artifacts
from repro.launch.fabric import design_mixing_matrix
from repro.configs.base import get_config, get_train_config, get_shape

# 1) sparse shard_map gossip == dense einsum
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
m = 4
links = [(0, 1), (1, 2), (2, 3), (0, 3)]
W = optimize_weights(m, links, steps=150).matrix
sched = gossip.build_schedule(W)
params = {"a": jax.random.normal(jax.random.key(0), (4, 8, 6))}
specs = {"a": P(("pod", "data"), None, "model")}
sharded = jax.device_put(
    params, {k: NamedSharding(mesh, s) for k, s in specs.items()}
)
dense = gossip.mix_dense(params, jnp.asarray(W))
with jax.set_mesh(mesh):
    sparse = gossip.mix_sparse_shardmap(sharded, sched, mesh,
                                        ("pod", "data"), specs)
err = float(jnp.max(jnp.abs(dense["a"] - sparse["a"])))
assert err < 1e-5, f"gossip mismatch {err}"

# 2) end-to-end distributed train step: loss decreases, ppermute in HLO
cfg = get_config("qwen2-0.5b", smoke=True)
tcfg = dataclasses.replace(get_train_config("qwen2-0.5b"), microbatch=2)
shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                            global_batch=16)
mesh2 = make_test_mesh((4, 2), ("data", "model"))
W2, _ = design_mixing_matrix(4, pods=1, kappa_bytes=1e6)
with jax.set_mesh(mesh2):
    art = build_train_artifacts(cfg, tcfg, shape, mesh2, W2)
    compiled = art.jit(donate=False).lower(
        art.state_shapes, art.batch_shapes
    ).compile()
    state = art.init_state(jax.random.key(0))
    batch = jax.device_put(
        {"tokens": jax.random.randint(
            jax.random.key(1), art.batch_shapes["tokens"].shape, 0,
            cfg.vocab_size)},
        art.batch_shardings,
    )
    losses = []
    for i in range(8):
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
assert losses[-1] < losses[0], losses

# 3) chip_smoke.py --four-chips at smoke size: sparse == dense gossip,
#    one agent per device, collective-permute in the program
import chip_smoke
smoke_cfg = dataclasses.replace(
    cfg, param_dtype="bfloat16", compute_dtype="bfloat16"
)
four = chip_smoke.four_chips(cfg=smoke_cfg, seq=32, per_agent=2)
assert four["collective_permute"], four
print("MULTIDEVICE_OK")
"""


def test_multidevice_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert "MULTIDEVICE_OK" in res.stdout, res.stdout + res.stderr
