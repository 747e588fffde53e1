"""Share of the traced training window in which no op ran on the device,
averaged over the chips: the step's dispatch and the loop's waits."""


def read(reading):
    s = reading.summary
    if s.window_ns <= 0 or not s.busy_ns:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
