"""Share of the step's device op time that no program scope tags (or whose
op the compiled step does not name), by ``chipbench/scopes.py``: the
guard that the tags still cover the step, not the cost of a layer."""

from chipbench import scopes


def read(reading):
    times = scopes.step_times(reading)
    if times is None:
        return None
    return 100.0 * times.get(scopes.UNSCOPED, 0.0) / sum(times.values())
