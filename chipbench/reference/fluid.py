"""Plain reference of the fluid network simulator: max-min fair sharing.

The semantics the pricing engine states, written out directly. Every
branch carries its flow's bytes over its directed edges. Between events
each active branch gets its max-min fair rate, found by progressive
filling: raise every unfrozen branch's rate together, and when an edge
runs out of capacity, freeze the branches that cross it at the level
reached. Time then advances to the next branch completion or capacity
boundary, whichever comes first; a branch whose remaining bytes fall to
``1e-9`` of its size or below is done at that time. A flow completes
when its last branch does. Capacities are piecewise constant on the
grid ``starts``.

Imports nothing of the program. ``dtype`` sets the precision every
quantity is held in (the control runs it in float32).
"""

from __future__ import annotations

import numpy as np

FINISH_SHARE = 1e-9


def maxmin_rates(active, path, caps, dtype=np.float64):
    """Max-min fair rates of the ``active`` branches; ``path[b]`` lists the
    edges branch b crosses (-1 pads), ``caps`` the edge capacities."""
    num_e = caps.size
    rates = np.zeros(active.size, dtype=dtype)
    unfrozen = active.copy()
    cap_left = caps.astype(dtype).copy()
    padded = np.where(path >= 0, path, num_e)  # pads hit a spare edge
    counts = np.bincount(padded[unfrozen].ravel(), minlength=num_e + 1)
    while unfrozen.any():
        with np.errstate(divide="ignore", invalid="ignore"):
            share = np.where(counts[:num_e] > 0,
                             cap_left / counts[:num_e].astype(dtype),
                             np.inf).astype(dtype)
        level = share.min()
        tight = np.append(share == level, False)
        freeze = unfrozen & tight[padded].any(axis=1)
        rates[freeze] = level
        gone = np.bincount(padded[freeze].ravel(), minlength=num_e + 1)
        cap_left -= (level * gone[:num_e]).astype(dtype)
        counts -= gone
        unfrozen &= ~freeze
    return rates


def simulate(flow, path, sizes, starts, caps, num_flows,
             dtype=np.float64, max_events=100_000):
    """Per-flow completion times ``[num_flows]`` (NaN for a flow with no
    branch) of one realization. ``caps[p]`` holds on
    ``[starts[p], starts[p + 1])``, the last row for ever after."""
    sizes = np.asarray(sizes, dtype=dtype)
    starts = np.asarray(starts, dtype=dtype)
    caps = np.asarray(caps, dtype=dtype)
    remaining = sizes.copy()
    done = np.full(sizes.size, np.nan, dtype=dtype)
    active = np.ones(sizes.size, dtype=bool)
    t = dtype(0.0)
    phase = 0
    for _ in range(max_events):
        if not active.any():
            break
        while phase + 1 < starts.size and starts[phase + 1] <= t:
            phase += 1
        t_next = starts[phase + 1] if phase + 1 < starts.size else np.inf
        rates = maxmin_rates(active, path, caps[phase], dtype)
        if not np.any(rates[active] > 0):
            if np.isinf(t_next):
                raise RuntimeError("branches starved for ever")
            t = t_next
            continue
        dt = np.min(remaining[active] / rates[active])
        if t_next - t < dt:
            dt = t_next - t
            t = t_next
        else:
            t = dtype(t + dt)
        remaining[active] -= rates[active] * dt
        finished = active & (remaining <= FINISH_SHARE * sizes)
        done[finished] = t
        active &= ~finished
    else:
        raise RuntimeError(f"no end after {max_events} events")
    out = np.full(num_flows, np.nan, dtype=np.float64)
    for h in range(num_flows):
        sel = flow == h
        if sel.any():
            out[h] = float(done[sel].max())
    return out
