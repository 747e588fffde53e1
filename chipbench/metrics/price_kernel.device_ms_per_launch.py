"""Device busy time inside each ``chipbench.launch`` span of the traced
window, averaged over the launches: what the pricing kernel costs per
256-rollout launch, with the host's share left out."""

from chipbench import trace as tr


def read(reading):
    s = reading.summary
    launches = s.spans_named("chipbench.launch")
    if not launches or not s.busy_ns:
        return None
    per = []
    for lo, hi in launches:
        busy = [tr.length(tr.clip(d.busy, lo, hi)) for d in s.devices.values()]
        per.append(sum(busy) / len(busy))
    return sum(per) / len(per) / 1e6
