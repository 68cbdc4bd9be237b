#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers its limits are
set from, and hold each reading to the limits as committed
(benchmarks/limits/<cell>.json) through the run's own `train_checks` /
`serve_checks` and `compare.verdict`: the program's readings over a dozen
seeds (sound runs: `correct` has to come out true), the control's (the
reference put in the program's place and computed in float8: it has to
come out false on every seed), and for a training cell the half-batch
fault's (false too). One process, so the set-up and the compiled programs
are paid once.

    python benchmarks/tools/limits.py --workload <cell> --seeds 12 \
        [--control-seeds 3] [--seconds 15] --out chiprun_out/limits_<cell>.json
    python benchmarks/tools/limits.py --workload <cell> --again <that file>

`--again` holds the readings a call kept to the limits as they are now,
with no chip: after a limit is moved, every verdict is taken again.
The benchmark's own runs never run this. PERF.md holds what it printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg):
    print(f"[limits {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def plain(readings):
    """Readings as plain lists, so that they can be held to other limits
    later without another chip run."""
    if isinstance(readings, dict):
        return {k: plain(v) for k, v in readings.items()}
    return np.asarray(readings).tolist()


def judged(kind, seed, checks):
    """One line of the record: what was read, beside its limit, and the
    verdict of the run's own comparison."""
    from harness import compare

    row = {"kind": kind, "seed": seed, "correct": bool(compare.verdict(checks)),
           **{k: v for k, v in checks.items() if not k.startswith("_")}}
    if "_worst" in checks:
        row["loss_gaps_not_compared"] = checks["_worst"]["loss_gaps_not_compared"]
    log(json.dumps(row))
    return row


def train(cell, config, mix, seeds, control_seeds, limits):
    import jax

    from harness import compare, spec, train_driver as td

    family = spec.family_of(config)
    dims, hp = family.dims_of(config), config["training"]
    B, T = int(mix["batch"]), int(mix["seq_len"])
    net = family.training_net(config, seeds[0], dims)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), net.params)
    net.params = None
    rows, kept, held = [], [], {}
    for seed in seeds:
        family.give_weights(net, seed, dims, like=shapes)
        net.opt_state = net.tx.init(net.params)
        feed = td.StepFeed(seed, B, T, dims["V"])
        prog = td.program_readings(family, net, feed, seed, dims, hp)
        net.params = net.opt_state = None
        r = family.reference_readings(seed, dims, hp, feed.kept,
                                      td.proj_key(seed))
        rows.append(judged("program", seed, compare.train_checks(prog, r, limits)))
        kept.append({"kind": "program", "seed": seed, "readings": plain(prog),
                     "reference": plain(r)})
        if seed in control_seeds:
            held[seed] = (feed.kept, r)
    for seed, (batches, r) in held.items():
        for kind, how in (("control_fp8", {"lowprec": True}),
                          ("fault_half_batch", {"rows": B // 2})):
            got = family.reference_readings(seed, dims, hp, batches,
                                            td.proj_key(seed), **how)
            rows.append(judged(kind, seed, compare.train_checks(got, r, limits)))
            kept.append({"kind": kind, "seed": seed, "readings": plain(got)})
    return rows, kept


def train_again(kept, limits):
    from harness import compare

    refs = {k["seed"]: k["reference"] for k in kept if k["kind"] == "program"}
    return [judged(k["kind"], k["seed"], compare.train_checks(
        k["readings"], refs[k["seed"]], limits)) for k in kept]


def serve(cell, config, mix, seeds, seconds, limits):
    from harness import serve_driver as sd, spec

    family = spec.family_of(config)
    dims = family.dims_of(config)
    held = []
    for seed in seeds:
        served = sd.Served(config, seed, log)
        served.warm_request(dims["V"])
        load = sd.run_load(served, mix, seed, seconds, float(mix["grace_s"]))
        m = sd.client_metrics(load, mix["kind"])
        compiles = served.compiles_since_warm()
        served.stop()
        del served
        sample = sd.pick_sample(m["finished"], seed, int(mix["check_requests"]),
                                int(limits["min_sample_tokens"]))
        held.append((seed, sample, compiles))
        log(f"served seed {seed}: {m['attempted']} requests, {m['failed']} "
            f"failed, {len(m['finished'])} finished, tpot95 {m['tpot_ms_p95']}, "
            f"tokens/s {m['serve_tokens_per_s']}")
    rows, kept = [], []
    for seed, sample, compiles in held:
        prompts = sd.prompts_of(mix, seed, dims["V"], seconds)
        slim = [{k: r[k] for k in ("id", "tokens", "max_new", "error",
                                   "prompt_len")} for r in sample]
        distinct = len({t for r in sample for t in r["tokens"]})
        log(f"seed {seed}: sample of {len(sample)} requests, "
            f"{sum(len(r['tokens']) for r in sample)} served tokens, "
            f"{distinct} distinct")
        for kind, low in (("program", False), ("control_fp8", True)):
            gaps = family.served_gaps(sample, prompts, seed, dims, lowprec=low)
            rows.append(judged(kind, seed, sd.serve_checks(
                sample, gaps, compiles, limits)))
            kept.append({"kind": kind, "seed": seed, "sample": slim,
                         "compiles": compiles, "distinct_tokens": distinct,
                         "gaps": [g.tolist() for g in gaps]})
    return rows, kept


def serve_again(kept, limits):
    from harness import serve_driver as sd

    return [judged(k["kind"], k["seed"], sd.serve_checks(
        k["sample"], [np.asarray(g) for g in k["gaps"]], k["compiles"], limits))
        for k in kept]


def summary(rows):
    """For each kind of run and each number compared: the least and the
    largest reading, and how many runs came out correct."""
    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        mine = [r for r in rows if r["kind"] == kind]
        names = [k for k, v in mine[0].items() if isinstance(v, list)
                 and len(v) == 2 and k != "loss_gaps_not_compared"]
        out[kind] = {"runs": len(mine),
                     "correct": sum(r["correct"] for r in mine),
                     **{n: {"min": min(r[n][0] for r in mine),
                            "max": max(r[n][0] for r in mine),
                            "limit": mine[0][n][1]} for n in names}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="training: seeds that also run the control and "
                    "the half-batch fault (a serving cell runs its control "
                    "on every seed)")
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--again", help="a file an earlier call wrote: hold "
                    "its readings to the limits as they are now")
    ap.add_argument("--out")
    a = ap.parse_args(argv)

    from harness import compare, spec

    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, a.workload)
    config, mix = spec.config_of(bench, cell), spec.traffic_of(cell)
    limits = compare.limits_of(cell["name"])
    training = mix["kind"] == "train"
    if a.again:
        with open(a.again) as fh:
            kept = json.load(fh)["kept"]
        rows = (train_again if training else serve_again)(kept, limits)
    else:
        from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
        from harness import device

        enable_compile_cache()
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"{cell['name']} on {device.require_chips(int(cell['chips']))}")
        seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
        if training:
            rows, kept = train(cell, config, mix, seeds,
                               seeds[:a.control_seeds], limits)
        else:
            rows, kept = serve(cell, config, mix, seeds, a.seconds, limits)
    out = {"limits": limits, "summary": summary(rows), "rows": rows}
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as fh:
            json.dump(dict(out, kept=kept), fh)
    print(json.dumps(out["summary"], indent=1))
    sound = all(r["correct"] == (r["kind"] == "program") for r in rows)
    log("every sound run correct, every control and fault not correct"
        if sound else "NOT SEPARATED: see the rows above")
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
