"""Device op time a step of the forward recomputed in the backward under
remat (a ``rematted_computation`` scope), by ``chipbench/scopes.py``.
A fusion takes its root's name, so recomputed work fused into a backward
fusion counts as backward: this is a lower bound on what remat costs."""

from chipbench import scopes


def read(reading):
    return scopes.phase_ms(reading, "recompute")
