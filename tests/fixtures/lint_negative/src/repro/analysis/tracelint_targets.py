"""Planted tracelint targets — one per jaxpr-level sub-check: an f32
promotion inside the trace, a host callback inside the "one launch",
and an entry split across two jitted calls (the companion manifest
additionally budgets a target that does not exist)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.tracelint import TraceCase, TraceTarget


@jax.jit
def _promote(x):
    # planted: narrow-float-in-trace (+ narrow-float-literal)
    return x.astype(jnp.float32) * jnp.float32(3.0)


@jax.jit
def _with_callback(x):
    # planted: host-callback
    y = jax.pure_callback(
        lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
    )
    return y + 1.0


@jax.jit
def _half1(x):
    return x * 2.0


@jax.jit
def _half2(x):
    return x + 1.0


def _split(x):
    return _half2(_half1(x))  # planted: multiple-launches


def _args():
    return (np.arange(4, dtype=np.float64),)


TARGETS = (
    TraceTarget(
        name="planted-f32",
        path="src/repro/net/bad_dtype.py",
        scope="price",
        cases=(TraceCase("f32", lambda: (_promote, _args())),),
    ),
    TraceTarget(
        name="planted-callback",
        path="src/repro/net/bad_retrace.py",
        scope="with_callback",
        cases=(TraceCase("cb", lambda: (_with_callback, _args())),),
    ),
    TraceTarget(
        name="planted-split",
        path="src/repro/net/bad_retrace.py",
        scope="split",
        cases=(TraceCase("split", lambda: (_split, _args())),),
    ),
)
