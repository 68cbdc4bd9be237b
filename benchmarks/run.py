#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run: find the cell, its configuration and its traffic
mix in BENCHMARK.json and the files it names; fail unless the chips are
there; set up (weights from --seed, warm-up of the cell's own shapes);
measure for --seconds; decide `correct` against the plain reference; print
one JSON line. `--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics, read from the program's spans and a
profiler trace of part of the window.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

DRIVERS = {"train": "harness.train_driver",
           "serve_open": "harness.serve_driver",
           "serve_closed": "harness.serve_driver"}


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_PROCESS:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


class Context:
    """What a driver gets: the cell's data, the run's arguments, and the
    hooks that mark the window and run the trace."""

    def __init__(self, bench, cell, config, traffic, limits, seed, seconds,
                 trace, rehearsal=False):
        self.bench, self.cell, self.config = bench, cell, config
        self.traffic, self.limits = traffic, limits
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.rehearsal = rehearsal
        self.setup_s = None
        self.spans = None
        self.log = log

    def start_trace(self):
        """A trace armed for the window that is about to open (traced
        runs), or None."""
        if not self.trace:
            return None
        from harness.tracing import WindowTrace

        start = float(self.traffic.get("trace_start_s", 1.0))
        length = float(self.traffic.get("trace_seconds", 4.0))
        length = min(length, max(0.5, self.seconds - start - 0.5))
        return WindowTrace(start, length)

    def window_opens(self, t0: float, trace=None) -> None:
        """The driver calls this at the window's first instant."""
        self.setup_s = t0 - T_PROCESS
        if trace is not None:
            trace.arm(t0)
        log(f"set-up done in {self.setup_s:.2f} s; window of "
            f"{self.seconds:g} s opens")

    def finish_trace(self, trace):
        """Reduce the trace once the window has closed: the chips' op and
        module events and the trace's own extent."""
        if trace is None:
            return None
        from harness import xplane

        path = trace.finish()
        size = os.path.getsize(path)
        t = time.perf_counter()
        chips, marks = xplane.read(path, marks=("bench_clock_sync",))
        log(f"trace {size / 1e6:.1f} MB read in "
            f"{time.perf_counter() - t:.1f} s: "
            f"{[(c['name'], len(c['ops']), len(c['modules'])) for c in chips]}")
        trace.cleanup()
        # the device's events may reach a little past the host's stop call:
        # the traced window is the longer of the two extents
        window_s = max(trace.t_off - trace.t_on, xplane.extent_seconds(chips))
        return {"chips": chips, "t_on": trace.t_on, "t_off": trace.t_off,
                "window_s": window_s,
                "t_sync": trace.t_sync,
                "sync_ns": marks.get("bench_clock_sync")}


def result_line(ctx, device_info, out) -> dict:
    """The contract's one JSON object."""
    from harness import compare, spec, xplane

    checks = out["checks"]
    group = "per_layer" if ctx.trace else "end_to_end"
    wanted = spec.metrics_for(ctx.bench, group, ctx.cell["name"])
    metrics = {}
    dev = dict(device_info, memory_peak_bytes=int(out["memory_peak_bytes"]))
    line = {"correct": bool(compare.verdict(checks)),
            "attempted": int(out["attempted"]), "failed": int(out["failed"])}
    if ctx.trace:
        traced = out["facts"]["traced"]
        chips = traced["chips"]
        facts = dict(out["facts"], spans=ctx.spans, config=ctx.config,
                     traffic=ctx.traffic, device=device_info,
                     peaks=_peaks(device_info, ctx.rehearsal))
        for m in wanted:
            value = spec.layer_reader(m["name"])(facts)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        dev["busy_s"] = xplane.busy_seconds(chips)
        dev["window_s"] = traced["window_s"]
        line["breakdown"] = {
            "device_ops": xplane.top_ops(chips, 10),
            "idle_gaps": xplane.idle_gaps(
                chips, _host_spans(ctx.spans, traced), 10,
                offset_ns=traced["sync_ns"] or 0.0)}
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in wanted:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = dev
    line["checks"] = {k: v for k, v in checks.items() if not k.startswith("_")}
    return line


def _peaks(device_info, rehearsal):
    from harness import device

    if rehearsal and device_info["kind"] not in device.PEAKS:
        return None
    return device.peaks_of(device_info["kind"])


def _host_spans(spans, traced):
    """The program's spans as (name, start_s, end_s) on the clock whose
    zero is the trace's sync mark."""
    if spans is None or traced.get("t_sync") is None:
        return []
    z = traced["t_sync"]
    return [(n, a - z, b - z) for n, a, b, _f in list(spans.spans)]


def execute(cell_name, seed, seconds, trace, *, bench=None, config=None,
            traffic=None, limits=None, rehearsal=False) -> dict:
    """Run one cell and return the result line. The command passes the
    cell's name only; tests pass a tiny `config` / `traffic` / `limits`
    of their own with `rehearsal=True`, which alone lets a CPU through."""
    import importlib

    from harness import compare, device, spec

    bench = bench or spec.load_benchmark()
    cell = spec.cell_of(bench, cell_name)
    config = config or spec.config_of(bench, cell)
    traffic = traffic or spec.traffic_of(cell)
    limits = limits if limits is not None else compare.limits_of(cell["name"])
    if not rehearsal:
        from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        import jax

        # every program, however quick to compile, is found again by the
        # cell's next run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device_info = device.require_chips(int(cell["chips"]), rehearsal)
    log(f"cell {cell['name']} on {device_info}")
    ctx = Context(bench, cell, config, traffic, limits, seed, seconds,
                  bool(trace), rehearsal)
    if trace:
        from harness import spans

        ctx.spans = spans.install()
    driver = importlib.import_module(DRIVERS[traffic["kind"]])
    out = driver.run(ctx)
    if "_worst" in out["checks"]:
        log(f"worst leaves: {json.dumps(out['checks']['_worst'])}")
    return result_line(ctx, device_info, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = execute(args.workload, args.seed, args.seconds, args.trace)
    from harness import compare

    log(f"done in {time.perf_counter() - T_PROCESS:.1f} s")
    compare.report(line["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
