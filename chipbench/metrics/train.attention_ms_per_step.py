"""Device op time a step of the attention mixer (scope ``attention``) in
the forward, the recompute and the backward, by ``chipbench/scopes.py``."""

from chipbench import scopes


def read(reading):
    return scopes.part_ms(reading, "attention")
