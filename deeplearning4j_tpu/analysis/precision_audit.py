"""Stage 5: precision-flow audit (dtype dataflow + frozen quantization
manifest).

The trace-level twin of the G031-G034 AST rules (precision_rules.py).
Walks every stage-2 entry point's closed jaxpr (shared trace — see
jaxpr_audit.closed_jaxpr) plus the decode/sampling extras below, and
distills a per-entry **precision profile**: the dtype of every
`dot_general`, additive reduction, scan carry, collective, and
`convert_element_type` the program issues, plus the count of
quantize/dequantize converts along the int8 cache path. The profiles
are frozen in analysis/precision_budget.json — the same
freeze/drift/refreeze contract as the stage-2 op budget and stage-3
collective signatures, per the ZeRO-style discipline (arXiv 2004.13336)
of auditing mixed-precision decisions instead of letting them be
emergent:

- P001: sub-f32 accumulation in a reduction chain — an add-accumulated
  scan carry, an additive reduce whose operand is (through shape/convert
  hops) a dot_general or another reduce, a cumulative op, or a psum
  operand, any of them in bfloat16/f16/f8. A single standalone reduce in
  bf16 is NOT a finding, and scopes containing `add_any` are exempt
  from the reduce-chain check: add_any exists ONLY as autodiff's
  transpose-rule gradient fan-in, so its presence marks a backward
  region whose bf16 bias-grad sums mirror the model's chosen training
  dtype (the bench LM modes trace in bf16 by design; the f32 answer
  there is master weights, not rewriting transpose rules). The
  discipline P001 enforces — accumulate in f32, downcast once — is for
  HAND-WRITTEN forward chains: kernels, scans, cumulatives, psums.
- P002: broken quantize<->dequantize pairing on the int8 path — an
  int8->float convert with no scale-multiply consumer (a raw-code read),
  or a float->int8 requantize in a read-modify-write scope whose value
  was never masked past the write head (`jnp.where`/select_n — stale
  garbage inflates the page maxabs and crushes fresh precision; see
  ops/decode_attention.quantized_cache_update).
- P003: convert churn — a convert_element_type whose producer is
  another convert, whose output dtype round-trips back to the inner
  input's dtype, and whose intermediate has NO other consumer. Pure
  HBM-bandwidth ping-pong. An intermediate that other ops (e.g. a VJP
  kernel expecting the working dtype) also read is a real value, not
  churn, and autodiff scopes (add_any present) are exempt like P001 —
  their convert pairs are residual plumbing XLA CSEs away.
- P004: dtype-widening collective — a psum/all-gather/... operand
  strictly wider than the entry's widest floating input. Widening on
  the wire multiplies interconnect bytes silently.
- P005: rank-divergent precision profile — the profile re-derived under
  simulated process_index 0 vs 1 (collective_audit's simulation)
  differs. Like stage 3's C003 this is deadlock-class: replicas that
  disagree about dtype flow compile different programs.
- PB01: profile drift vs the frozen manifest (or an entry missing from
  it). Regenerate deliberately: `tools/graftlint.py --update-precision`.

External fixture entries: a .py passed to `graftlint --stage precision`
that defines ``GRAFTLINT_PRECISION_ENTRIES = {name: builder}``
(builder() -> (fn, args)) gets profiled and P-rule checked without the
frozen-manifest requirement — the demo path for the bf16-accumulation
finding.

jax and the model stack load lazily; importing this module is cheap and
jax-free (the AST stages never touch it).
"""

from __future__ import annotations

import json
import os
import re

from deeplearning4j_tpu.analysis.core import Finding

BUDGET_PATH = os.path.join(os.path.dirname(__file__),
                           "precision_budget.json")

# the hook external fixture modules expose: {entry_name: builder}
ENTRY_HOOK = "GRAFTLINT_PRECISION_ENTRIES"

# Entries beyond the stage-2 set: the int8 paged-cache decode path and
# the two serving-side fused kernels the manifest must cover (ISSUE 20
# acceptance). These also carry the per-entry rank-divergence check
# (P005) — cheap traces, unlike the LM steps, whose rank story stage 3
# already owns.
PRECISION_EXTRA = (
    "decode_attention/cached",
    "decode_attention/q8",
    "decode_attention/q8_update",
    "fused_sampling/sample",
    "fused_neg_softmax/scores",
)

# Additive reductions — where evaluation ORDER compounds rounding.
# max/min/argmax are exact at any width and exempt.
_ADDITIVE_REDUCES = frozenset({"reduce_sum", "reduce_prod", "add_any"})
_CUMULATIVE = frozenset({"cumsum", "cumprod", "cumlogsumexp"})

# Reduction-style collectives whose operand is an accumulator.
_ACC_COLLECTIVES = frozenset({"psum", "psum_scatter", "reduce_scatter"})

# Shape/layout/width hops that carry an accumulation chain through
# without introducing new math — the P001 chain walk crosses these only.
_CHAIN_HOPS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "convert_element_type", "slice", "dynamic_slice", "rev", "copy",
})

# Pass-through hops for the P002a dequant->scale-multiply consumer walk.
_DEQUANT_HOPS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
})

_P002A_DEPTH = 6
_P002B_DEPTH = 14


def entry_names() -> list[str]:
    """Auditable stage-5 entry points (stable order): every stage-2
    entry plus the decode/sampling extras. Safe to call without jax."""
    from deeplearning4j_tpu.analysis import jaxpr_audit

    return jaxpr_audit.entry_names() + list(PRECISION_EXTRA)


# ------------------------------------------------------- extra builders

def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _build_extra(name):
    """-> (fn, args tuple) for one PRECISION_EXTRA entry, abstract
    inputs (serving-scale-ish shapes, nothing executes)."""
    import jax.numpy as jnp

    f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32
    B, S, H, D, PS = 2, 256, 2, 64, 64
    n_pages = S // PS
    if name == "decode_attention/cached":
        from deeplearning4j_tpu.ops.decode_attention import decode_attention

        return decode_attention, (
            _sds((B, H, D), f32), _sds((B, S, H, D), f32),
            _sds((B, S, H, D), f32), _sds((B,), i32))
    if name == "decode_attention/q8":
        from deeplearning4j_tpu.ops.decode_attention import \
            cache_attention_q8

        return (lambda q, kc, vc, ks, vs, lim: cache_attention_q8(
            q, kc, vc, ks, vs, lim, PS)), (
            _sds((B, H, 1, D), f32), _sds((B, S, H, D), i8),
            _sds((B, S, H, D), i8), _sds((B, n_pages, H), f32),
            _sds((B, n_pages, H), f32), _sds((B, 1), i32))
    if name == "decode_attention/q8_update":
        from deeplearning4j_tpu.ops.decode_attention import \
            quantized_cache_update

        T = 8
        return (lambda c, s, nv, r, p: quantized_cache_update(
            c, s, nv, r, p, PS)), (
            _sds((B, S, H, D), i8), _sds((B, n_pages, H), f32),
            _sds((B, T, H, D), f32), _sds((B,), i32), _sds((B, T), i32))
    if name == "fused_sampling/sample":
        from deeplearning4j_tpu.ops.fused_sampling import fused_sample

        V = 1024
        return (lambda lg, nz: fused_sample(lg, nz, temperature=0.8,
                                            top_k=64, top_p=0.9)), (
            _sds((8, V), f32), _sds((8, V), f32))
    if name == "fused_neg_softmax/scores":
        from deeplearning4j_tpu.ops.fused_neg_softmax import \
            neg_softmax_scores

        return neg_softmax_scores, (
            _sds((8, 128), f32), _sds((8, 128), f32),
            _sds((8, 5, 128), f32))
    raise KeyError(name)


def trace_closed(name):
    """Closed jaxpr for any stage-5 entry — the stage-2 names go
    through jaxpr_audit's memo cache (one trace serves both stages in
    `--stage all`); the extras trace here."""
    from deeplearning4j_tpu.analysis import jaxpr_audit

    if name in PRECISION_EXTRA:
        import jax

        fn, args = _build_extra(name)
        return jax.make_jaxpr(fn)(*args)
    return jaxpr_audit.closed_jaxpr(name)


# ------------------------------------------------------------ profiling

def _iter_scopes(jaxpr):
    """Every jaxpr SCOPE (the outer jaxpr plus each pjit/scan/cond/
    pallas sub-jaxpr). Producer/consumer relations only hold within one
    scope, so the dataflow walks analyze scopes independently."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_scopes(inner)
                elif hasattr(sub, "eqns"):
                    yield from _iter_scopes(sub)


def _is_var(v):
    # jax Literal carries .val; Var does not
    return hasattr(v, "aval") and not hasattr(v, "val")


def _dt(v) -> str:
    dtype = getattr(getattr(v, "aval", None), "dtype", None)
    return str(dtype) if dtype is not None else "?"


def _is_sub_f32(v) -> bool:
    import numpy as np

    dtype = getattr(getattr(v, "aval", None), "dtype", None)
    if dtype is None:
        return False
    dtype = np.dtype(dtype) if not hasattr(dtype, "itemsize") else dtype
    try:
        import jax.numpy as jnp

        floating = jnp.issubdtype(dtype, np.floating)
    except Exception:
        floating = np.issubdtype(dtype, np.floating)
    return bool(floating) and dtype.itemsize < 4


def _is_float(v) -> bool:
    import numpy as np

    dtype = getattr(getattr(v, "aval", None), "dtype", None)
    if dtype is None:
        return False
    try:
        import jax.numpy as jnp

        return bool(jnp.issubdtype(dtype, np.floating))
    except Exception:
        return bool(np.issubdtype(dtype, np.floating))


def _float_width(v) -> int:
    """Itemsize of a floating aval, 0 otherwise."""
    if not _is_float(v):
        return 0
    return getattr(v.aval.dtype, "itemsize", 0)


def _producers(scope) -> dict:
    return {out: eqn for eqn in scope.eqns for out in eqn.outvars
            if _is_var(out)}


def _consumers(scope) -> dict:
    cons: dict = {}
    for eqn in scope.eqns:
        for v in eqn.invars:
            if _is_var(v):
                cons.setdefault(v, []).append(eqn)
    return cons


def _chain_hits(var, producers, targets, *, hops, depth=24) -> bool:
    """Walk var's producer chain crossing only `hops` prims; True when a
    producer primitive lands in `targets`."""
    seen = 0
    while _is_var(var) and seen < depth:
        eqn = producers.get(var)
        if eqn is None:
            return False
        prim = eqn.primitive.name
        if prim in targets:
            return True
        if prim not in hops:
            return False
        var = next((v for v in eqn.invars if _is_var(v)), None)
        seen += 1
    return False


def _chain_reaches_var(var, producers, target, *, hops, depth=24) -> bool:
    """Like `_chain_hits` but looking for a specific VAR (the scan carry
    invar) instead of a primitive."""
    seen = 0
    while _is_var(var) and seen < depth:
        if var is target:
            return True
        eqn = producers.get(var)
        if eqn is None:
            return False
        if eqn.primitive.name not in hops:
            return False
        var = next((v for v in eqn.invars if _is_var(v)), None)
        seen += 1
    return False


def _eqn_contains(eqn, target: str) -> bool:
    """Does the eqn ITSELF match `target`, or (for call-like eqns —
    jnp.where/round arrive as `pjit[name=_where]` wrappers) any eqn of
    its sub-jaxprs, recursively?"""
    if eqn.primitive.name == target:
        return True
    for val in eqn.params.values():
        for sub in (val if isinstance(val, (list, tuple)) else [val]):
            inner = getattr(sub, "jaxpr", None)
            body = inner if inner is not None and hasattr(inner, "eqns") \
                else (sub if hasattr(sub, "eqns") else None)
            if body is not None and any(_eqn_contains(e, target)
                                        for e in body.eqns):
                return True
    return False


def _reaches_prim(var, producers, target: str, depth: int) -> bool:
    """Bounded BFS through ALL producers: does `target` appear anywhere
    in var's (shallow) history? Call-like eqns (pjit wrappers) are
    transparent. Conservative in the safe direction — a hit through an
    unrelated operand only *suppresses* a finding."""
    frontier, seen = [var], set()
    for _ in range(depth):
        nxt = []
        for v in frontier:
            if not _is_var(v) or v in seen:
                continue
            seen.add(v)
            eqn = producers.get(v)
            if eqn is None:
                continue
            if _eqn_contains(eqn, target):
                return True
            nxt.extend(eqn.invars)
        if not nxt:
            return False
        frontier = nxt
    return False


def _scale_multiplied(var, consumers, depth=_P002A_DEPTH) -> bool:
    """P002a consumer walk: the dequantized codes must hit a `mul`
    (the per-page scale) within a few pass-through hops."""
    frontier = [var]
    for _ in range(depth):
        nxt = []
        for v in frontier:
            for eqn in consumers.get(v, ()):
                prim = eqn.primitive.name
                if prim == "mul":
                    return True
                if prim in _DEQUANT_HOPS:
                    nxt.extend(o for o in eqn.outvars if _is_var(o))
        if not nxt:
            return False
        frontier = nxt
    return False


def _bump(d: dict, key: str) -> None:
    d[key] = d.get(key, 0) + 1


def profile_closed(closed, name: str):
    """-> (profile dict, P001-P004 findings) for one closed jaxpr.

    The profile is the frozen-manifest unit: dtype-keyed counts of
    dots / additive reductions / scan carries / collectives / converts,
    plus round-trip and quantize/dequantize tallies. JSON-stable and
    rank-comparable (P005 diffs two of these)."""
    from deeplearning4j_tpu.analysis.collective_audit import \
        JAXPR_COLLECTIVES

    profile = {"dots": {}, "reductions": {}, "scan_carries": {},
               "collectives": {}, "converts": {},
               "convert_round_trips": 0, "q8": {"quantize": 0,
                                                "dequantize": 0}}
    findings: list[Finding] = []
    flagged: set[str] = set()

    def flag(rule, message, fixit, snippet):
        if snippet in flagged:       # one finding per (rule, site class)
            return
        flagged.add(snippet)
        findings.append(Finding(rule, name, 0, 0, message, fixit,
                                snippet=snippet, stage="precision"))

    # widest floating ENTRY input — the P004 reference width
    in_width = max((_float_width(v) for v in closed.jaxpr.invars),
                   default=0)

    for scope in _iter_scopes(closed.jaxpr):
        producers = _producers(scope)
        consumers = _consumers(scope)
        # add_any exists only as autodiff's gradient fan-in — its
        # presence marks a backward region, exempt from the chain and
        # churn checks (see the module docstring)
        backward_scope = any(e.primitive.name == "add_any"
                             for e in scope.eqns)
        scope_deq = []            # int8->float converts in this scope
        scope_req = []            # float->int8 converts in this scope

        for eqn in scope.eqns:
            prim = eqn.primitive.name
            out = eqn.outvars[0] if eqn.outvars else None

            if prim == "dot_general":
                ins = ",".join(_dt(v) for v in eqn.invars[:2])
                _bump(profile["dots"], f"{ins}->{_dt(out)}")

            elif prim in _ADDITIVE_REDUCES or prim in _CUMULATIVE:
                _bump(profile["reductions"], f"{prim}:{_dt(out)}")
                if out is not None and _is_sub_f32(out) \
                        and not backward_scope:
                    if prim in _CUMULATIVE:
                        flag("P001",
                             f"`{prim}` accumulates in {_dt(out)} — a "
                             "cumulative chain compounds sub-f32 "
                             "rounding at every step",
                             "compute the cumulative op in f32 "
                             "(preferred_element_type / astype) and "
                             "downcast the result",
                             f"cum-subf32:{prim}:{_dt(out)}")
                    else:
                        operand = next((v for v in eqn.invars
                                        if _is_var(v)), None)
                        if operand is not None and _chain_hits(
                                operand, producers,
                                {"dot_general"} | _ADDITIVE_REDUCES,
                                hops=_CHAIN_HOPS):
                            flag("P001",
                                 f"`{prim}` in {_dt(out)} directly over "
                                 "a dot_general/reduce — a chained "
                                 "reduction accumulating below f32",
                                 "accumulate in f32 "
                                 "(preferred_element_type=jnp.float32 "
                                 "on the dot, or reduce before the "
                                 "downcast)",
                                 f"chain-subf32:{prim}:{_dt(out)}")

            elif prim in ("scan", "while"):
                # a `while` (a fori_loop with a traced bound: the decode
                # walk over live key blocks) carries all its outputs
                if prim == "scan":
                    ncarry = eqn.params.get("num_carry", 0)
                    nconst = eqn.params.get("num_consts", 0)
                    body = eqn.params.get("jaxpr")
                else:
                    nconst = eqn.params.get("body_nconsts", 0)
                    body = eqn.params.get("body_jaxpr")
                    ncarry = len(eqn.outvars)
                inner = getattr(body, "jaxpr", body)
                if inner is not None and hasattr(inner, "outvars"):
                    body_prod = _producers(inner)
                    for i, cv in enumerate(inner.outvars[:ncarry]):
                        _bump(profile["scan_carries"], _dt(cv))
                        if not (_is_var(cv) and _is_sub_f32(cv)):
                            continue
                        peqn = body_prod.get(cv)
                        if peqn is not None and peqn.primitive.name in \
                                ("add", "add_any"):
                            carry_in = inner.invars[nconst + i] \
                                if nconst + i < len(inner.invars) else None
                            if carry_in is None or any(
                                    _chain_reaches_var(v, body_prod,
                                                       carry_in,
                                                       hops=_CHAIN_HOPS)
                                    for v in peqn.invars if _is_var(v)):
                                flag("P001",
                                     f"scan carry {i} add-accumulates "
                                     f"in {_dt(cv)} — running sums "
                                     "below f32 lose low bits every "
                                     "iteration",
                                     "carry the accumulator in f32 and "
                                     "downcast after the scan (the "
                                     "flash/decode kernels' pattern)",
                                     f"carry-subf32:{_dt(cv)}:{i}")

            elif prim in JAXPR_COLLECTIVES:
                operand = next((v for v in eqn.invars if _is_var(v)),
                               None)
                key_dt = _dt(operand) if operand is not None else "?"
                _bump(profile["collectives"], f"{prim}:{key_dt}")
                if prim in _ACC_COLLECTIVES and operand is not None \
                        and _is_sub_f32(operand):
                    flag("P001",
                         f"`{prim}` reduces a {key_dt} operand across "
                         "ranks — the cross-replica sum is itself a "
                         "sub-f32 accumulation chain",
                         "psum in f32 (upcast the operand; downcast "
                         "after)", f"psum-subf32:{prim}:{key_dt}")
                if operand is not None and in_width and \
                        _float_width(operand) > in_width:
                    flag("P004",
                         f"`{prim}` moves a {key_dt} operand while the "
                         "entry's widest floating input is "
                         f"{in_width * 8}-bit — widened bytes on the "
                         "wire",
                         "downcast before the collective (or keep the "
                         "f32 master copy local, ZeRO-style)",
                         f"widening:{prim}:{key_dt}")

            elif prim == "convert_element_type":
                src = eqn.invars[0]
                key = f"{_dt(src)}->{_dt(out)}"
                _bump(profile["converts"], key)
                if _dt(src).startswith("int8") and _is_float(out):
                    profile["q8"]["dequantize"] += 1
                    scope_deq.append(eqn)
                elif _is_float(src) and _dt(out).startswith("int8"):
                    profile["q8"]["quantize"] += 1
                    scope_req.append(eqn)
                # P003: direct convert-of-convert landing back on the
                # inner input's dtype, the intermediate consumed by
                # nothing else — a pure round trip
                if _is_var(src) and not backward_scope:
                    peqn = producers.get(src)
                    if peqn is not None and \
                            peqn.primitive.name == "convert_element_type":
                        inner_src = peqn.invars[0]
                        only_here = (
                            all(c is eqn for c in consumers.get(src, ()))
                            and src not in set(scope.outvars))
                        if only_here and _dt(out) == _dt(inner_src) \
                                and _dt(out) != _dt(src):
                            profile["convert_round_trips"] += 1
                            flag("P003",
                                 f"convert {_dt(inner_src)}->{_dt(src)}"
                                 f"->{_dt(out)} round trip — the value "
                                 "never changed; both converts are HBM "
                                 "bandwidth",
                                 "delete the ping-pong (keep the value "
                                 "in its working dtype)",
                                 f"churn:{_dt(inner_src)}->{_dt(src)}")

        # -------- P002: quantize<->dequantize pairing, per q8 scope
        if scope_deq:
            scope_outs = set(scope.outvars)
            for eqn in scope_deq:
                out = eqn.outvars[0]
                if out in scope_outs:
                    continue      # escapes the scope; caller's problem
                if not _scale_multiplied(out, consumers):
                    flag("P002",
                         "int8 codes converted to float but never "
                         "scale-multiplied nearby — a raw-code read "
                         "(missing dequant) on the q8 cache path",
                         "multiply by the per-(row,page,head) scale "
                         "right after the convert "
                         "(ops/decode_attention dequant idiom)",
                         "q8-read-unscaled")
        if scope_deq and scope_req:
            # read-modify-write scope: the requantize must sit behind a
            # select_n (write-head zeroing) or stale garbage sets scales
            for eqn in scope_req:
                if not _reaches_prim(eqn.invars[0], producers,
                                     "select_n", _P002B_DEPTH):
                    flag("P002",
                         "requantize in a read-modify-write q8 scope "
                         "without masking past the write head — stale "
                         "values from a prior tenancy inflate the page "
                         "maxabs and crush fresh precision",
                         "jnp.where positions past the row's write "
                         "head to 0 before recomputing scales "
                         "(quantized_cache_update's zeroing step)",
                         "q8-requant-unmasked")

    # sort for JSON stability / manifest comparison
    for k in ("dots", "reductions", "scan_carries", "collectives",
              "converts"):
        profile[k] = dict(sorted(profile[k].items()))
    return profile, findings


def trace_profile(name: str):
    """-> (profile, findings) for one named entry."""
    return profile_closed(trace_closed(name), name)


# ----------------------------------------------------- rank simulation

def _build_for(name):
    if name in PRECISION_EXTRA:
        return lambda: _build_extra(name)
    from deeplearning4j_tpu.analysis import jaxpr_audit

    return lambda: jaxpr_audit._build(name)


def check_rank_independence(name: str, build=None) -> list[Finding]:
    """Re-derive the precision profile under simulated process_index
    0 vs 1 (collective_audit's env-contract simulation). A divergent
    profile is deadlock-class (P005), exactly like stage 3's C003: the
    replicas would compile different mixed-precision programs."""
    import jax

    from deeplearning4j_tpu.analysis.collective_audit import (
        SIMULATED_PROCESSES, simulated_process_index)

    build = build or _build_for(name)
    profiles = {}
    for pid in SIMULATED_PROCESSES:
        with simulated_process_index(pid):
            fn, args = build()
            closed = jax.make_jaxpr(fn)(*args)
            profiles[pid], _ = profile_closed(closed, name)
    p0, p1 = (profiles[p] for p in SIMULATED_PROCESSES)
    if p0 != p1:
        diff = sorted(k for k in set(p0) | set(p1)
                      if p0.get(k) != p1.get(k))
        return [Finding(
            "P005", name, 0, 0,
            "rank-divergent precision profile — process 0 and process 1 "
            f"disagree on {diff}: replicas compiling different "
            "mixed-precision programs desync exactly like a divergent "
            "collective sequence (DEADLOCK class)",
            "make dtype decisions rank-invariant; never branch dtypes "
            "on process_index at trace time",
            snippet="rank-divergent-precision", stage="precision")]
    return []


# -------------------------------------------------------------- manifest

def load_budget(path: str | None = None) -> dict[str, dict]:
    try:
        with open(path or BUDGET_PATH) as fh:
            return dict(json.load(fh)["entries"])
    except FileNotFoundError:
        return {}


def write_budget(profiles: dict[str, dict],
                 path: str | None = None) -> None:
    with open(path or BUDGET_PATH, "w") as fh:
        json.dump(
            {"comment": "frozen per-entry precision manifest (graftlint "
                        "stage 5): dtype-keyed counts of dots / additive "
                        "reductions / scan carries / collectives / "
                        "converts plus int8 quantize/dequantize tallies. "
                        "A drift here is a mixed-precision regression "
                        "unless deliberate: tools/graftlint.py "
                        "--update-precision",
             "entries": {k: profiles[k] for k in sorted(profiles)}},
            fh, indent=1, sort_keys=False)
        fh.write("\n")


def _diff_keys(frozen: dict, got: dict) -> list[str]:
    return sorted(k for k in set(frozen) | set(got)
                  if frozen.get(k) != got.get(k))


def audit(names=None, budget_path: str | None = None, *,
          divergence: bool = True):
    """Run the stage-5 audit -> (findings, {entry: profile})."""
    budget = load_budget(budget_path)
    findings, profiles = [], {}
    for name in names if names is not None else entry_names():
        profile, fs = trace_profile(name)
        profiles[name] = profile
        findings.extend(fs)
        frozen = budget.get(name)
        if frozen is None:
            findings.append(Finding(
                "PB01", name, 0, 0,
                "entry point has no frozen precision profile "
                f"(traced {sum(profile['dots'].values())} dot(s), "
                f"{sum(profile['converts'].values())} convert(s))",
                "run `python tools/graftlint.py --update-precision`",
                snippet="missing-precision-profile", stage="precision"))
        elif frozen != profile:
            findings.append(Finding(
                "PB01", name, 0, 0,
                "precision profile drift vs the frozen manifest in "
                f"{_diff_keys(frozen, profile)} — an accumulation "
                "dtype, convert, or quant count changed",
                "find what changed the dtype flow; only then refreeze "
                "(--update-precision)",
                snippet="precision-drift", stage="precision"))
        # rank simulation re-traces, so only the cheap extras carry it
        # (the LM steps' rank story is stage 3's C003 on the
        # distributed entries)
        if divergence and name in PRECISION_EXTRA:
            findings.extend(check_rank_independence(name))
    return findings, profiles


# --------------------------------------------------- external fixtures

def load_entry_module(path: str):
    """Import a fixture .py by path and return its
    GRAFTLINT_PRECISION_ENTRIES hook ({name: builder}), or {}."""
    import importlib.util

    modname = "_graftlint_prec_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, ENTRY_HOOK, {})


def audit_paths(paths) -> tuple[list[Finding], dict[str, dict]]:
    """Profile + P-rule-check every external entry the given .py files
    expose (no frozen-manifest requirement — demo/fixture entries)."""
    import jax

    findings, profiles = [], {}
    for path in paths:
        if not (path.endswith(".py") and os.path.isfile(path)):
            continue
        for name, build in load_entry_module(path).items():
            fn, args = build()
            closed = jax.make_jaxpr(fn)(*args)
            profile, fs = profile_closed(closed, name)
            profiles[name] = profile
            findings.extend(fs)
            findings.extend(check_rank_independence(name, build))
    return findings, profiles
