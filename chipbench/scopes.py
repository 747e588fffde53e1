"""Device op time of the train step by the program scope that issued it.

The program tags its step with named scopes (``jax.named_scope``:
``grads``, ``grad_accumulate``, ``optimizer``, ``gossip``; ``embed``,
``blocks``, ``attention``, ``mlp``, ``head_loss``, …), which the compiler
keeps as each HLO instruction's ``op_name`` metadata.
``repro.launch.train.op_scopes(compiled)`` maps every instruction of the
compiled step to a ``(phase, part)``; the device trace names its ops by
the same instructions (``trace.op_name``), so the join is exact.

The map is taken from the cell's step built again by its driver
(``build``), which the persistent compilation cache that set-up filled
hands back as the same executable. This happens in a traced run only,
after the window and the check. A program without ``op_scopes`` gives no
map, and an executable whose instructions carry no program scope (a
compile cache filled before the program opened its scopes: the cache key
ignores metadata) gives nothing to read; in both cases every reader here
returns None, never a reading of 0 ms.

The first reading also prints one ``scopes`` line to stderr: ms a step
for each (phase, part) with time, and each of the breakdown's top ops
with its (phase, part).
"""

from __future__ import annotations

import json
import sys
import time

UNSCOPED = ("unscoped", "unscoped")


def scope_map(run) -> dict | None:
    """``{hlo_instruction_name: (phase, part)}`` of the run's step, or
    None where the program cannot tag it."""
    try:
        from repro.launch.train import op_scopes
    except ImportError:
        return None
    from chipbench.bench import Spec

    return op_scopes(Spec().driver(run.cell).build(run.cell).step)


def step_times(reading) -> dict | None:
    """``{(phase, part): ns}`` of device op time per step, summed per
    device and averaged over the devices (ops not in the map count as
    unscoped); None where there is no map, no scoped instruction in it,
    no step or no op time. Computed once per reading."""
    if "_step_times" in vars(reading):
        return reading._step_times
    reading._step_times = None
    steps = reading.run.counts.get("steps", 0)
    devices = reading.summary.devices
    if not steps or not devices:
        return None
    t0 = time.perf_counter()
    scopes = scope_map(reading.run)
    if not scopes or all(v == UNSCOPED for v in scopes.values()):
        return None
    map_s = time.perf_counter() - t0
    tot: dict = {}
    for d in devices.values():
        for name, ns in d.op_ns.items():
            key = scopes.get(name, UNSCOPED)
            tot[key] = tot.get(key, 0.0) + ns
    if not any(tot.values()):
        return None
    per = len(devices) * steps
    reading._step_times = {k: v / per for k, v in tot.items()}
    report(reading, scopes, map_s)
    return reading._step_times


def phase_ms(reading, phase: str) -> float | None:
    times = step_times(reading)
    if times is None:
        return None
    return sum(v for (p, _), v in times.items() if p == phase) / 1e6


def part_ms(reading, part: str) -> float | None:
    times = step_times(reading)
    if times is None:
        return None
    return sum(v for (_, q), v in times.items() if q == part) / 1e6


def report(reading, scopes: dict, map_s: float) -> None:
    """The ``scopes`` line on stderr; ``map_s`` is what taking the map
    cost (seconds, host clock)."""
    s, steps = reading.summary, reading.run.counts["steps"]
    times = reading._step_times
    line = {
        "steps": steps,
        "map_s": map_s,
        "op_ms_per_step": sum(times.values()) / 1e6,
        "busy_ms_per_step": s.busy_ns / steps / 1e6,
        "ms_per_step": {f"{p}/{q}": v / 1e6 for (p, q), v in
                        sorted(times.items(), key=lambda kv: -kv[1]) if v},
        "top_ops": [[n, sec, *scopes.get(n, UNSCOPED)]
                    for n, sec in s.top_ops()],
    }
    print("scopes " + json.dumps(line), file=sys.stderr, flush=True)
