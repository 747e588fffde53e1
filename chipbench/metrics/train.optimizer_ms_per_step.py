"""Device op time a step of the optimizer: ``sgd.update`` (scope
``optimizer``) and the float32 gradient accumulation (scope
``grad_accumulate``), by ``chipbench/scopes.py``."""

from chipbench import scopes


def read(reading):
    return scopes.phase_ms(reading, "optimizer")
