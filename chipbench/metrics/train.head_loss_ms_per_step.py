"""Device op time a step of the final norm, the tied unembedding, the
softcap and the float32 cross-entropy (scope ``head_loss``), forward and
backward, by ``chipbench/scopes.py``."""

from chipbench import scopes


def read(reading):
    return scopes.part_ms(reading, "head_loss")
