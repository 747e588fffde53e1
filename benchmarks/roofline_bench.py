"""Render the per-(arch × shape × mesh) roofline table from the dry-run
records (dryrun_results.json, written by ``repro.launch.dryrun --out``).
One row per cell: the three terms, the dominant bottleneck,
MODEL_FLOPS/HLO_FLOPS, and the roofline fraction."""

import json
import os
import time

from benchmarks.common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..",
                       "dryrun_results.json")


def load(mesh: str = "pod16x16") -> list[dict]:
    with open(RESULTS) as f:
        records = json.load(f)
    return [r for r in records if r.get("mesh") == mesh]


def render(records: list[dict]) -> list[str]:
    lines = []
    hdr = (
        f"| {'arch':24s} | {'shape':11s} | {'compute':>9s} | {'memory':>9s} "
        f"| {'collective':>10s} | {'dominant':10s} | {'MF/HF':>6s} "
        f"| {'roofline':>8s} |"
    )
    lines.append(hdr)
    lines.append("|" + "-" * (len(hdr) - 2) + "|")
    for r in sorted(records, key=lambda x: (x["arch"], x["shape"])):
        if r["status"] != "ok":
            continue
        rf = r["roofline"]
        lines.append(
            f"| {r['arch']:24s} | {r['shape']:11s} "
            f"| {rf['compute_s']*1e3:8.2f}ms | {rf['memory_s']*1e3:8.2f}ms "
            f"| {rf['collective_s']*1e3:9.2f}ms | {rf['dominant']:10s} "
            f"| {rf['useful_flops_fraction']:6.2f} "
            f"| {rf['roofline_fraction']*100:7.2f}% |"
        )
    return lines


def main() -> None:
    t0 = time.perf_counter()
    for mesh in ("pod16x16", "pod2x16x16"):
        records = load(mesh)
        if not records:
            continue
        print(f"# mesh {mesh} ({len(records)} cells)")
        for ln in render(records):
            print(ln)
    ok = [r for r in load("pod16x16") if r["status"] == "ok"]
    best = max(ok, key=lambda r: r["roofline"]["roofline_fraction"])
    emit(
        "roofline_bench",
        1e6 * (time.perf_counter() - t0),
        f"cells={len(ok)};best={best['arch']}x{best['shape']}="
        f"{best['roofline']['roofline_fraction']*100:.1f}%",
    )


if __name__ == "__main__":
    main()
