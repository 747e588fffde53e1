"""Model FLOPs of the steps completed in the traced window (``flops.py``:
6 x matmul parameters plus causal attention's two products, per token;
recomputation not counted), over the window's length, the chips and the
bf16 peak of the device kind (``peaks.json``)."""


def read(reading):
    run = reading.run
    tokens = run.counts.get("tokens", 0)
    if not tokens or not run.window_s > 0:
        return None
    achieved = tokens * run.counts["flops_per_token"] / run.window_s
    peak = run.peaks["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * achieved / peak
