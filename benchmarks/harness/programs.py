"""Each device program of the traced window joined to the host span that
dispatched it.

The serving engine's loop runs one program ahead (serving/engine.py): a
model step's span (`decode_step`, `prefill_chunk`, `verify_step`) names
the program it LAUNCHED (`program`, with the chunk's `n_real` and
`bucket`), and its child `dispatch` says when (`t0`). The chip runs
programs in the order they were enqueued, and no program starts before
its dispatch. A busy loop dispatches program n + 1 only after it has
fetched program n - 1, which is after program n has started. So the
program of a module event (`jit_counted_step(...)`, `..._prefill`,
`..._verify` on the trace's `XLA Modules` line) is the span of its own
kind dispatched last before the module started, on the host clock that
`bench_clock_sync` lays over the trace's (facts["traced"]: `t_sync`,
`sync_ns`). TOLERANCE_S of clock error is allowed; a module whose span is
already taken, or that starts before any dispatch of its kind, stays
unmatched. A module counts where it starts inside the trace and before
its stop (`host_loop.quiet_window`)."""

from __future__ import annotations

import bisect

from harness import host_loop

# module name (a part of it) -> the kind of span that dispatches it
KINDS = {"counted_step": "decode_step", "counted_prefill": "prefill_chunk",
         "counted_verify": "verify_step"}
TOLERANCE_S = 1e-4


def kind_of(module_name: str) -> str | None:
    for part, span in KINDS.items():
        if part in module_name:
            return span
    return None


def dispatches(log) -> dict:
    """{span kind: ([dispatch t0], [span fields])}, each in dispatch
    order: the model-step spans with a `dispatch` child."""
    entries = list(log.spans)
    t0 = {s[3].get("parent_id"): host_loop.interval(s)[0]
          for s in entries if s[0] == "dispatch"}
    out = {}
    for name, _a, _b, f in sorted(
            (s for s in entries
             if s[0] in KINDS.values() and s[3].get("span_id") in t0),
            key=lambda s: t0[s[3]["span_id"]]):
        starts, spans = out.setdefault(name, ([], []))
        starts.append(t0[f["span_id"]])
        spans.append(f)
    return out


def join(facts) -> dict | None:
    """{"programs": [{"kind", "span", "module", "start_ns", "dur_ns",
    "lag_s"}] of the matched modules in time order, "matched",
    "unmatched", "min_lag_s" (the least module start minus dispatch:
    under 0 is the clocks' error)}, or None where nothing was traced."""
    t, log = facts.get("traced"), facts.get("spans")
    if not t or not t.get("chips") or log is None \
            or t.get("t_sync") is None:
        return None
    z, z_ns = t["t_sync"], t.get("sync_ns") or 0.0
    lo = t["t_on"]
    hi = host_loop.quiet_window(facts)[1] if "window" in facts else t["t_off"]
    by_kind = dispatches(log)
    used = {k: -1 for k in by_kind}
    programs, unmatched = [], 0
    for name, s_ns, d_ns, _x in sorted(t["chips"][0]["modules"],
                                       key=lambda m: m[1]):
        kind = kind_of(name)
        at = z + (s_ns - z_ns) / 1e9
        if kind is None or not lo <= at <= hi:
            continue
        starts, spans = by_kind.get(kind, ([], []))
        i = bisect.bisect_right(starts, at + TOLERANCE_S) - 1
        if i <= used.get(kind, -1):
            unmatched += 1
            continue
        used[kind] = i
        programs.append({"kind": kind, "span": spans[i], "module": name,
                         "start_ns": s_ns, "dur_ns": d_ns,
                         "lag_s": at - starts[i]})
    return {"programs": programs, "matched": len(programs),
            "unmatched": unmatched,
            "min_lag_s": min((p["lag_s"] for p in programs), default=None)}
