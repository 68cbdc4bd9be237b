#!/usr/bin/env python3
"""Read, on the chip and at a serving cell's own size, what `tools/limits.py`
reads (the program's readings and the float8 control's, a seed at a time),
and beside them the faults the cell's family can put in the program's
place: for each name in the family's `FAULTS`, the tokens its reference
run as that wrong model puts first at each served position of the first
seed's sample (`served_gaps(..., fault=name)`), held to the committed
limits through the run's own `serve_checks`. Every sound run has to read
`correct` true, every control and every fault false. One process.

    python benchmarks/tools/faults.py --workload <cell> --seeds 2 \
        [--seconds 20] --out faults_<cell>.json

The file it writes is one `tools/limits.py --again` reads: after a limit is
moved, every verdict is taken again with no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.dirname(BENCH_DIR), os.path.dirname(
        os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import limits as lim  # noqa: E402


def readings(cell, config, mix, seeds, seconds, limits):
    """(rows, kept): `tools/limits.py`'s serving readings over `seeds`,
    then each of the family's faults over the first seed's sample."""
    from harness import serve_driver as sd, spec

    family = spec.family_of(config)
    dims = family.dims_of(config)
    rows, kept = lim.serve(cell, config, mix, seeds, seconds, limits)
    first = next(k for k in kept if k["kind"] == "program")
    prompts = sd.prompts_of(mix, first["seed"], dims["V"], seconds)
    for fault in family.FAULTS:
        gaps = family.served_gaps(first["sample"], prompts, first["seed"],
                                  dims, fault=fault)
        rows.append(lim.judged("fault_" + fault, first["seed"],
                               sd.serve_checks(first["sample"], gaps,
                                               first["compiles"], limits)))
        kept.append(dict(first, kind="fault_" + fault,
                         gaps=[g.tolist() for g in gaps]))
    return rows, kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    a = ap.parse_args(argv)

    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    from harness import compare, device, spec

    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, a.workload)
    config, mix = spec.config_of(bench, cell), spec.traffic_of(cell)
    limits = compare.limits_of(cell["name"])
    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    lim.log(f"{cell['name']} on {device.require_chips(int(cell['chips']))}")
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    rows, kept = readings(cell, config, mix, seeds, a.seconds, limits)
    out = {"limits": limits, "summary": lim.summary(rows), "rows": rows}
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as fh:
            json.dump(dict(out, kept=kept), fh)
    print(json.dumps(out["summary"], indent=1))
    sound = all(r["correct"] == (r["kind"] == "program") for r in rows)
    lim.log("every sound run correct, every control and fault not correct"
            if sound else "NOT SEPARATED: see the rows above")
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
