"""Device op time a step of ops the forward pass issued (program scope
``grads``, neither recomputed nor transposed), by ``chipbench/scopes.py``."""

from chipbench import scopes


def read(reading):
    return scopes.phase_ms(reading, "forward")
