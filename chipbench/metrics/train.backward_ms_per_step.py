"""Device op time a step of the backward pass (ops under a ``transpose(``
wrapper, recomputation left out), by ``chipbench/scopes.py``."""

from chipbench import scopes


def read(reading):
    return scopes.phase_ms(reading, "backward")
