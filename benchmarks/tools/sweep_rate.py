#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell once, on the chip: the
highest arrival rate the server sustains without a growing backlog. One
server, one window of the cell's mix at each rate; for each rate the tails,
the time the backlog took to drain after the window closed, and whether
the time to first token grew through the window (its last third against
its first). The cell then runs at a fixed four fifths of the knee.

    python benchmarks/tools/sweep_rate.py --workload serve_1p3b_chat \
        --rates 2,3,4,5,6,7 --seconds 20 --out chiprun_out/sweep.json
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_100_000_033)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)

    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    from harness import device, serve_driver as sd, spec, traffic

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, a.workload)
    config, mix = spec.config_of(bench, cell), spec.traffic_of(cell)
    print(device.require_chips(1), file=sys.stderr)
    log = lambda m: print(f"[sweep] {m}", file=sys.stderr, flush=True)
    served = sd.Served(config, a.seed, log)
    served.warm_request(served.dims["V"])
    rows = []
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        load = sd.run_load(served, dict(mix, rate_per_s=rate), a.seed + i,
                           a.seconds, 60.0)
        m = sd.client_metrics(load, mix["kind"])
        recs, t0 = load["records"], load["t0"]
        ttft = [(r["due_s"], 1e3 * (r["t_tokens"][0] - t0 - r["due_s"]))
                for r in recs if r["t_tokens"]]
        third = a.seconds / 3
        early = [x for d, x in ttft if d < third]
        late = [x for d, x in ttft if d >= 2 * third]
        done = max((r["t_done"] or 0) for r in recs) - t0 - a.seconds
        row = {"rate": rate, "attempted": m["attempted"], "failed": m["failed"],
               "finished_in_window": len(m["finished"]),
               "tokens_per_s": m["serve_tokens_per_s"],
               "ttft_ms_p50": m["ttft_ms_p50"], "ttft_ms_p90": m["ttft_ms_p90"],
               "tpot_ms_p95": m["tpot_ms_p95"],
               "ttft_ms_p50_first_third": traffic.percentile(early, 50),
               "ttft_ms_p50_last_third": traffic.percentile(late, 50),
               "drain_s_after_close": done, "late_ms_max": m["late_ms_max"],
               "compiles": served.compiles_since_warm()}
        rows.append(row)
        log(json.dumps(row))
        time.sleep(1.0)
    served.stop()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
