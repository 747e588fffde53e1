#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (load, weights and inputs from ``--seed``, compile or load from
the persistent cache at ``<checkout>/.jax_cache``, warm-up) is timed as
``setup_s``; then the cell's driver measures for ``--seconds``. With
``--trace 1`` the window runs under the profiler and the cell's
per-layer metrics are read from the trace; otherwise its end-to-end
metrics are reported. After the window the cell's driver frees the
program's state and checks what the timed path produced against the
plain reference; each number compared and its limit go to stderr, as
the last lines, and into the result.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
``checks`` last. With no TPU, or fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """One run of one cell: what its driver module is given and reports."""

    cell: object  # bench.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t0: float = T0
    trace_dir: Path = TRACE_DIR
    # Filled in by the cell's driver module.
    setup_s: float = math.nan
    window_s: float = math.nan
    attempted: int = 0
    failed: int = 0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    # Seconds before the window spent on the check, left out of setup_s.
    check_s: float = 0.0
    window_compiles: list = dataclasses.field(default_factory=list)
    _window_open: bool = False

    @property
    def window_seconds(self) -> float:
        """Length of the measured window: a traced run measures at most
        the mix's ``trace_seconds``, so the trace stays readable."""
        if self.trace:
            return min(self.seconds,
                       float(self.cell.traffic.get("trace_seconds",
                                                   self.seconds)))
        return self.seconds

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self._window_open and ("compile" in event
                                  or "cache_retrieval" in event):
            self.window_compiles.append(event)

    @contextlib.contextmanager
    def window(self):
        """The measured window, under the profiler when tracing; any
        compilation inside it is recorded in ``window_compiles``."""
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._window_open = True
        try:
            if not self.trace:
                yield
                return
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # No Python function tracing: it slows the host side several
            # fold and would inflate the idle share it is read for.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("chipbench.window"):
                    yield
            finally:
                jax.profiler.stop_trace()
        finally:
            self._window_open = False

    def note_gaps(self, t_start: float, done: list) -> None:
        """The host-clock gaps between successive results in the window
        (``done``: when each reached the host): the median, and the
        longest with when it ended, so a slow run shows where it lost
        its time."""
        gaps = np.diff([t_start] + list(done))
        if gaps.size:
            i = int(np.argmax(gaps))
            self.counts.update(median_gap_s=float(np.median(gaps)),
                               longest_gap_s=float(gaps[i]),
                               longest_gap_ends_s=float(done[i] - t_start))

    def read_memory_peak(self) -> None:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peaks, default=0)


def span(name: str):
    """A host span on the profiler's clock (cheap when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench." + name)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader is given."""

    cell: object
    run: Run
    summary: object  # trace.Summary


def per_layer(spec, run: Run) -> tuple[dict, dict, dict]:
    """(metrics, device extras, breakdown) from the traced window."""
    from chipbench import trace as tr

    data = tr.load(tr.find_xplane(run.trace_dir))
    used = {f"/device:{d.platform.upper()}:{d.id}" for d in run.devices}
    data.ops = {k: v for k, v in data.ops.items() if k in used}
    for name in used:
        data.ops.setdefault(name, [])
    summary = tr.reduce(data)
    reading = Reading(run.cell, run, summary)
    metrics = {}
    for m in run.cell.per_layer:
        value = spec.reader(m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extras = {"busy_s": summary.busy_ns / 1e9,
              "window_s": summary.window_ns / 1e9}
    breakdown = {"device_ops": summary.top_ops(),
                 "idle_gaps": summary.top_gaps()}
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    return metrics, extras, breakdown


def execute(spec, run: Run) -> dict:
    """Drive the cell's set-up, window and check; return the result."""
    drv = spec.driver(run.cell)
    state = drv.setup(run)
    gc.collect()  # set-up's garbage is not the window's to collect
    run.setup_s = time.perf_counter() - run.t0 - run.check_s
    drv.measure(run, state)
    run.read_memory_peak()
    drv.check(run, state)

    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(run.checks) and all(c.ok for c in run.checks),
              "attempted": int(run.attempted), "failed": int(run.failed)}
    if run.trace:
        metrics, extras, breakdown = per_layer(spec, run)
        device.update(extras)
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        for m in run.cell.end_to_end:
            if m["name"] == "setup_s":
                continue
            if m["name"] not in run.end_to_end:
                raise KeyError(f"driver {run.cell.driver!r} did not "
                               f"measure {m['name']!r}")
            metrics[m["name"]] = {"value": float(run.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
        breakdown = None
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result


def report(result: dict, run: Run) -> None:
    print(f"compilations in the window: {len(run.window_compiles)} "
          f"{sorted(set(run.window_compiles))}; window {run.counts}",
          file=sys.stderr)
    for c in run.checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # The persistent compilation cache lives at a fixed path inside the
    # checkout; the program's own cache helper takes it from here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench.bench import Spec

    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {len(devices)} {devices[0].platform} device(s); "
              f"{args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), devices=devices[:cell.chips],
              peaks=spec.peaks(devices[0].device_kind))
    report(execute(spec, run), run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
