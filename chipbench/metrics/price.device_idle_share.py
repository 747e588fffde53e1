"""Share of the traced pricing window in which no op ran on the device:
the time the host side of pricing (padding the batch, unpacking the
results, the benchmark's loop) holds the chip back."""


def read(reading):
    s = reading.summary
    if s.window_ns <= 0 or not s.busy_ns:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
