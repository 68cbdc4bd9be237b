"""A traced window's device time by program and by region of the program.

A profiler trace names each op by its HLO instruction alone. The program
says which region each instruction lies in: a `regions` event a warmed
program ({"entry", "shape", "module", "ops": {instruction: "top" or
"top/child"}}: telemetry/costbook.py). Each matched program
(harness/programs.py) reads the table of its own entry and shape (a
chunk's bucket), and each of its ops' self time (its duration less what
the ops nested in it cover: a `while` and its body's ops) goes to the
op's top-level region, or to `other` where the table has none for it. A
program whose table is missing (a program that records none) reads its
busy time alone.

One sorted sweep over the ops: self times from a stack of the enclosing
ops, the program an op belongs to from a pointer over the programs in
start order. A program's busy time is the union of its ops' intervals,
which the self times of its ops add up to.
"""

from __future__ import annotations

from collections import defaultdict

from harness import programs as programs_mod

ENTRY = {"decode_step": "decode", "prefill_chunk": "prefill",
         "verify_step": "verify"}
OTHER = "other"


def tables(log) -> dict:
    """{(entry, shape tuple): {instruction: region}} of the program's
    `regions` events."""
    if log is None:
        return {}
    return {(f.get("entry"), tuple(f.get("shape") or ())): f.get("ops") or {}
            for _kind, _t, f in log.of_kind("regions")}


def _table_of(program, found: dict):
    entry = ENTRY[program["kind"]]
    if entry == "prefill":
        return found.get((entry, tuple(program["span"].get("bucket") or ())))
    return next((ops for (e, _s), ops in found.items() if e == entry), None)


def self_times(ops) -> list:
    """[(name, start_ns, end_ns, self_ns, detail)] of one line's op
    events in start order; an enclosing op keeps what its children
    leave."""
    out, stack = [], []     # stack of indices into out, innermost last
    for name, start, dur, detail in sorted(ops, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= out[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= dur
        out.append([name, start, end, dur, detail])
        stack.append(len(out) - 1)
    return [(n, a, b, max(s, 0.0), x) for n, a, b, s, x in out]


def reduce(chip, programs: list, found: dict) -> list:
    """Per matched program, in start order: {"kind", "span", "busy_s",
    "regions": {top-level region or `other`: self seconds} or None where
    the program has no table}."""
    progs = sorted(programs, key=lambda p: p["start_ns"])
    tabled = [_table_of(p, found) for p in progs]
    out = [{"kind": p["kind"], "span": p["span"], "busy_s": 0.0,
            "regions": None if t is None else defaultdict(float)}
           for p, t in zip(progs, tabled)]
    covered = [None] * len(progs)   # the end of each program's busy union
    # the CPU's op events name their module (the rehearsal's one event a
    # module spans all its runs): there the module says whose an op is
    named = {p["module"]: i for i, p in enumerate(progs)}
    k = 0
    for name, a, b, self_ns, module in self_times(chip["ops"]):
        i = named.get(module)
        if i is None:
            while k < len(progs) \
                    and a >= progs[k]["start_ns"] + progs[k]["dur_ns"]:
                k += 1
            if k == len(progs) or a < progs[k]["start_ns"]:
                continue
            i = k
        end = a if covered[i] is None else covered[i]
        out[i]["busy_s"] += max(0.0, b - max(a, end)) / 1e9
        covered[i] = max(end, b)
        if tabled[i] is not None:
            region = tabled[i].get(name, OTHER).split("/")[0]
            out[i]["regions"][region] += self_ns / 1e9
    for p in out:
        if p["regions"] is not None:
            p["regions"] = dict(p["regions"])
    return out


def programs(facts) -> list:
    """`reduce` of the traced window's matched programs (computed once a
    run: every reader of a program's device time asks)."""
    memo = facts.get("_device_programs")
    if memo is None:
        joined = programs_mod.join(facts)
        t = facts.get("traced") or {}
        memo = [] if not joined else reduce(
            t["chips"][0], joined["programs"], tables(facts.get("spans")))
        facts["_device_programs"] = memo
    return memo


def of_kind(facts, kind: str) -> list:
    return [p for p in programs(facts) if p["kind"] == kind]


def region_seconds(progs: list, region: str) -> list:
    """The region's self seconds in each program that has a table, or []
    where no table of them names the region."""
    tabled = [p for p in progs if p["regions"] is not None]
    if not any(region in p["regions"] for p in tabled):
        return []
    return [p["regions"].get(region, 0.0) for p in tabled]


def ktok(progs: list) -> float:
    """The real prompt tokens of the chunks, in thousands."""
    return sum(p["span"].get("n_real", 0) for p in progs) / 1e3


def breakdown(facts, n: int = 16) -> list:
    """[["decode/attention", seconds], ...]: the matched programs' device
    time by kind of program and top-level region, longest first."""
    totals = defaultdict(float)
    for p in programs(facts):
        for region, s in (p["regions"] or {OTHER: p["busy_s"]}).items():
            totals[f"{ENTRY[p['kind']]}/{region}"] += s
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]
