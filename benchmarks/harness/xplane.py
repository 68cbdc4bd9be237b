"""From a profiler trace (`.xplane.pb`) to numbers.

`jax.profiler.ProfileData` reads the file with nothing but JAX. On a TPU
each chip is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one
event per executed HLO operation, named by the whole instruction (a
`while` encloses its body's events), its line `XLA Modules` one event per
executed program (`jit_step(<fingerprint>)`). A Pallas kernel is a
custom-call to `tpu_custom_call` whose instruction is named after the
kernel's `name=` (`%jvp_flash_fwd_.18`, `%flash_bwd_dkv.3`).

    busy      the union of the op intervals on a chip: seconds in which
              an operation ran there
    self time an op's duration minus what its children cover, so that a
              loop and its body are not both counted
    kernels   summed durations of the events whose name or statistics
              match a pattern (leaf events; no double counting)

On the CPU (tests' rehearsal only) there is no device plane; the XLA CPU
client's thread lines, whose events carry `hlo_op`, stand in so that the
same code path runs.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def short_name(text: str) -> str:
    """An op event is named by its whole HLO instruction, `%name = type
    op(operands), attributes`: the instruction's name, without the `%`."""
    return text.split(" = ", 1)[0].lstrip("%")


FUSION_KIND = re.compile(r" fusion\(.*, kind=(k\w+)")


def is_kernel(detail: str) -> bool:
    return KERNEL_TARGET in detail


def _events(line, ops: bool):
    """(short name, start_ns, dur_ns, detail): for a Pallas kernel the
    detail is the whole instruction, which holds its shapes, and its short
    name is made from the kernel's `name=` (`jvp_flash_fwd_.18`,
    `flash_bwd_dkv.3`); for an XLA fusion the detail is its kind (`kOutput`
    fuses into a matrix product or convolution, `kLoop` is elementwise,
    `kInput` a reduction)."""
    out = []
    for e in line.events:
        text = e.name
        detail = ""
        if ops and KERNEL_TARGET in text:
            detail = text
        elif ops:
            m = FUSION_KIND.search(text)
            detail = m.group(1) if m else ""
        out.append((short_name(text) if ops else text, float(e.start_ns),
                    float(e.duration_ns), detail))
    return out


def read(path: str, marks=()) -> tuple:
    """([{"name", "ops": [(name, start_ns, dur_ns, detail)], "modules":
    [(name, start_ns, dur_ns, detail)]}] — one entry per chip,
    {mark: start_ns of the host event of that name})."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, cpu_ops, found = [], [], {}
    for plane in pd.planes:
        if marks and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in marks:
                        found.setdefault(e.name, float(e.start_ns))
        if plane.name.startswith("/device:TPU:"):
            chip = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip["ops"] = _events(line, True)
                elif line.name == MODULES_LINE:
                    chip["modules"] = _events(line, False)
            if chip["ops"] or chip["modules"]:
                chips.append(chip)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    for e in line.events:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            cpu_ops.append((e.name, float(e.start_ns),
                                            float(e.duration_ns),
                                            str(stats.get("hlo_module", ""))))
    if not chips and cpu_ops:
        mods = defaultdict(list)
        for name, start, dur, module in cpu_ops:
            mods[module].append((start, start + dur))
        chips = [{"name": "/host:CPU", "ops": sorted(cpu_ops, key=_start),
                  "modules": [(m, min(a for a, _ in iv),
                               max(b for _, b in iv) - min(a for a, _ in iv),
                               "") for m, iv in mods.items()]}]
    return chips, found


def _start(ev):
    return ev[1]


def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e9


def self_times(ops) -> list:
    """[(name, self_ns, is_leaf, detail)] for events of one line: an
    enclosing event (a `while`, a `conditional`) keeps only what its
    children leave."""
    out, stack = [], []   # stack of [end_ns, index into out]
    for name, start, dur, detail in sorted(ops, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= dur
            parent[2] = False
        out.append([name, dur, True, detail])
        stack.append([end, len(out) - 1])
    return [(n, max(s, 0.0), leaf, d) for n, s, leaf, d in out]


def busy_seconds(chips) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not chips:
        return 0.0
    return sum(union_seconds((s, s + d) for _n, s, d, _x in c["ops"])
               for c in chips) / len(chips)


def idle_share_percent(traced) -> float | None:
    """The share of the traced window in which no operation ran on the
    chips: 1 - busy / window, in percent; None where nothing was traced."""
    if not traced or not traced.get("chips") or traced.get("t_on") is None:
        return None
    busy = busy_seconds(traced["chips"])
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / traced["window_s"])


def extent_seconds(chips) -> float:
    """From the first op's start to the last op's end, the longest over
    the chips."""
    spans = [(min(s for _n, s, _d, _x in c["ops"]),
              max(s + d for _n, s, d, _x in c["ops"]))
             for c in chips if c["ops"]]
    return max((b - a) / 1e9 for a, b in spans) if spans else 0.0


def kernel_seconds(chips, pattern: str) -> tuple:
    """(seconds, events) of the leaf op events on the first chip whose
    name or statistics match the regular expression."""
    if not chips:
        return 0.0, 0
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for name, self_ns, leaf, detail in self_times(chips[0]["ops"]):
        if leaf and (rx.search(name) or rx.search(detail)):
            total += self_ns
            n += 1
    return total / 1e9, n


def module_durations(chips, pattern: str) -> list:
    """Durations in seconds of the executed programs on the first chip
    whose name matches, in time order."""
    if not chips:
        return []
    rx = re.compile(pattern)
    return [d / 1e9 for n, _s, d, _x in sorted(chips[0]["modules"], key=_start)
            if rx.search(n)]


def top_ops(chips, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most self time on
    the first chip, kernels under their own names."""
    if not chips:
        return []
    totals = defaultdict(float)
    for name, self_ns, _leaf, detail in self_times(chips[0]["ops"]):
        label = re.sub(r"[.\d]+$", "", name)
        if detail:
            label += " (kernel)" if is_kernel(detail) else f" ({detail})"
        totals[label] += self_ns
    best = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(chips, host_spans, n: int = 10, offset_ns: float = 0.0) -> list:
    """[[what the host was doing, seconds]]: the idle gaps between device
    operations on the first chip, summed by the name of the host span
    (name, start_s, end_s on the clock whose zero is the trace's start)
    that covers the middle of the gap, longest first."""
    if not chips or not chips[0]["ops"]:
        return []
    iv = sorted((s, s + d) for _n, s, d, _x in chips[0]["ops"])
    gaps, end = [], iv[0][1]
    for a, b in iv[1:]:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    totals = defaultdict(float)
    for a, b in gaps:
        mid = ((a + b) / 2.0 - offset_ns) / 1e9
        who = "unattributed"
        for name, s0, s1 in host_spans:
            if s0 <= mid <= s1:
                who = name
                break
        totals[who] += (b - a) / 1e9
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]
