"""The G001-G009 + G016-G024 + G029 AST rules (G010-G015 + G018 live
in spmd_rules.py, G025-G028 in concurrency_rules.py; both register
into ALL_RULES/RULE_DOCS at the bottom of this module).

Every rule errs toward PRECISION over recall: a lint gate that cries
wolf gets suppressed wholesale, while a quiet one keeps running in CI
forever. Each rule documents what it deliberately does not catch.

All name matching goes through the per-file import table (`Imports`), so
`import numpy as onp` / `from jax import random as jr` spellings resolve
to canonical dotted paths before any rule looks at them.
"""

from __future__ import annotations

import ast
import re

from deeplearning4j_tpu.analysis.core import Finding

# Paths whose code runs per training step — the G002 host-sync scope.
HOT_PATH_FRAGMENTS = ("/ops/", "/parallel/", "/nn/layers/")

# Decorators that put a function body under a jax trace.
_JIT_NAMES = {"jax.jit", "jax.pjit", "jit", "pjit",
              "jax.experimental.pjit.pjit"}
_TRACED_DECOS = _JIT_NAMES | {
    "jax.custom_vjp", "jax.custom_jvp", "jax.checkpoint", "jax.remat",
    "jax.vmap", "jax.grad", "jax.value_and_grad"}

# Attribute reads that return STATIC python values even on tracers.
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                 "weak_type"}
# Builtins whose result on a traced arg is static (or that never trace).
_STATIC_CALLS = {"len", "isinstance", "type", "hasattr", "getattr", "id",
                 "repr", "str"}

_NP_CTORS = {"zeros", "ones", "empty", "full", "arange", "linspace",
             "eye", "identity"}

# jax.random.* that do NOT consume the key (safe to call repeatedly with
# the same key). Everything else — split included — consumes it.
_KEY_NONCONSUMING = {"fold_in", "key_data", "wrap_key_data", "key_impl",
                     "clone"}

# params treated as PRNG keys for the G004 reuse check, by convention
_KEY_PARAM_RE = re.compile(r"(?:^|_)(?:key|rng|prng)s?$|^(?:key|rng)")

_MUTABLE_DEFAULT_CALLS = {"list", "dict", "set", "bytearray",
                          "defaultdict", "OrderedDict"}

# jnp/jax calls that ALLOCATE a device buffer when run at module level.
_DEVICE_ALLOC = {"jax.numpy." + n for n in
                 _NP_CTORS | {"array", "asarray", "stack", "concatenate"}}
_DEVICE_ALLOC |= {"jax.random.PRNGKey", "jax.random.key",
                  "jax.device_put"}


class Imports:
    """Local alias -> canonical dotted module path, e.g. jnp ->
    jax.numpy, shard_map -> deeplearning4j_tpu.util.compat.shard_map."""

    def __init__(self, tree: ast.AST):
        self.map: dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.map[a.asname] = a.name
                    else:
                        root = a.name.split(".")[0]
                        self.map[root] = root
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    self.map[a.asname or a.name] = f"{node.module}.{a.name}"

    def canon(self, node: ast.AST) -> str | None:
        """Canonical dotted path of a Name/Attribute chain, or None."""
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        parts.append(self.map.get(node.id, node.id))
        return ".".join(reversed(parts))


def _walk_with_parents(tree: ast.AST):
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            child._gl_parent = parent  # type: ignore[attr-defined]
    return tree


def _parents(node: ast.AST):
    while True:
        node = getattr(node, "_gl_parent", None)
        if node is None:
            return
        yield node


def _decorator_canon(deco: ast.AST, imports: Imports):
    """(canonical name, call node | None) for plain / called / partial-
    wrapped decorators: @jax.jit, @jax.jit(...), @partial(jax.jit, ...)."""
    call = None
    if isinstance(deco, ast.Call):
        call = deco
        name = imports.canon(deco.func)
        if name in ("functools.partial", "partial") and deco.args:
            name = imports.canon(deco.args[0])
        return name, call
    return imports.canon(deco), call


def _static_params(fn: ast.FunctionDef, deco_call: ast.Call | None,
                   deco_name: str) -> set[str]:
    """Param names the decorator marks static (static_argnums/argnames,
    custom_vjp nondiff_argnums — passed as concrete python values)."""
    pos = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    static: set[str] = set()
    if deco_call is None:
        return static
    for kw in deco_call.keywords:
        if kw.arg in ("static_argnums", "nondiff_argnums",
                      "static_argnames"):
            vals = kw.value.elts if isinstance(
                kw.value, (ast.Tuple, ast.List)) else [kw.value]
            for v in vals:
                if isinstance(v, ast.Constant):
                    if isinstance(v.value, int) and 0 <= v.value < len(pos):
                        static.add(pos[v.value])
                    elif isinstance(v.value, str):
                        static.add(v.value)
    return static


def _traced_functions(tree: ast.AST, imports: Imports):
    """(fn, traced param names) for every function whose body jax traces."""
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for deco in node.decorator_list:
            name, call = _decorator_canon(deco, imports)
            if name in _TRACED_DECOS:
                params = {a.arg for a in node.args.posonlyargs
                          + node.args.args + node.args.kwonlyargs}
                params -= _static_params(node, call, name)
                yield node, params
                break


def _mentions_traced(expr: ast.AST, tracked: set[str],
                     imports: Imports) -> bool:
    """Does `expr` read a tracked (traced-value) name in a position that
    yields a tracer? `.shape`/`.ndim`/... reads and len()/isinstance()
    calls are static even on tracers and do not count."""
    def visit(node) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False
        if isinstance(node, ast.Call):
            fname = imports.canon(node.func)
            if fname in _STATIC_CALLS:
                return False
            return visit(node.func) or any(
                visit(a) for a in node.args) or any(
                visit(k.value) for k in node.keywords)
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            return node.id in tracked
        return any(visit(c) for c in ast.iter_child_nodes(node))
    return visit(expr)


def _only_identity_tests(test: ast.AST) -> bool:
    """`x is None` / `x is not None` and and/or/not combinations thereof
    — legal on tracers (identity, not value)."""
    if isinstance(test, ast.Compare):
        return all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)
    if isinstance(test, ast.BoolOp):
        return all(_only_identity_tests(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _only_identity_tests(test.operand)
    return False


def _grow_tracked(fn: ast.AST, tracked: set[str], imports: Imports):
    """Fixpoint: names assigned from expressions over tracked names are
    themselves tracked (y = x * 2). Bounded iterations; order-insensitive."""
    for _ in range(4):
        before = len(tracked)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _mentions_traced(
                    node.value, tracked, imports):
                for tgt in node.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            tracked.add(n.id)
            elif isinstance(node, ast.For) and _mentions_traced(
                    node.iter, tracked, imports):
                for n in ast.walk(node.target):
                    if isinstance(n, ast.Name):
                        tracked.add(n.id)
        if len(tracked) == before:
            break


# --------------------------------------------------------------- G001

def g001_traced_bool(tree, imports, path):
    """Python control flow / bool()/float()/int() on traced values inside
    jit-traced functions: ConcretizationTypeError at runtime, or worse, a
    silent retrace per distinct value. Not caught: traced values entering
    via closure instead of params."""
    out = []
    for fn, tracked in _traced_functions(tree, imports):
        tracked = set(tracked)
        _grow_tracked(fn, tracked, imports)
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                if _only_identity_tests(node.test):
                    continue
                if _mentions_traced(node.test, tracked, imports):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    out.append((node, f"python `{kind}` on a traced value",
                                "use jnp.where / lax.cond / lax.while_loop,"
                                " or mark the driving arg static"))
            elif isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Name) and node.func.id in (
                    "bool", "float", "int") and node.args and \
                    _mentions_traced(node.args[0], tracked, imports):
                out.append((node, f"`{node.func.id}()` forces a traced "
                            "value to a python scalar (device sync / "
                            "ConcretizationTypeError)",
                            "keep it as a jnp scalar, or hoist the "
                            "conversion out of the traced function"))
    return [("G001", n, m, f) for n, m, f in out]


# --------------------------------------------------------------- G002

def g002_host_sync(tree, imports, path):
    """Implicit device->host syncs in hot paths (ops/, parallel/,
    nn/layers/): .item(), jax.device_get, np.asarray/np.array on device
    values stall the dispatch pipeline mid-step. Host-side setup code in
    those dirs carries an inline disable with its justification."""
    if not any(frag in path for frag in HOT_PATH_FRAGMENTS):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = imports.canon(node.func)
        if name in ("numpy.asarray", "numpy.array"):
            out.append(("G002", node,
                        f"`{name.replace('numpy', 'np')}` in a hot path "
                        "pulls the value to host (sync) and re-uploads",
                        "stay in jnp (`jnp.asarray`), or move host "
                        "conversion out of the per-step path"))
        elif name == "jax.device_get":
            out.append(("G002", node, "`jax.device_get` in a hot path is "
                        "an explicit device sync",
                        "batch transfers outside the step loop"))
        elif isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args:
            out.append(("G002", node, "`.item()` in a hot path blocks on "
                        "the device value",
                        "keep the scalar on device; log via jax.debug or "
                        "after the step"))
    return out


# --------------------------------------------------------------- G003

def g003_float64_drift(tree, imports, path):
    """dtype-less np constructors inside functions that also do jnp math:
    np defaults to float64/int64, so the host value either silently
    downcasts at the jnp boundary or (x64 enabled) upcasts the whole
    expression. Not caught: promotion via python float literals."""
    out = []
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    seen: set[int] = set()
    for fn in fns:
        uses_jnp = any(
            (c := imports.canon(n)) and
            (c.startswith("jax.numpy.") or c.startswith("jax.lax."))
            for n in ast.walk(fn) if isinstance(n, (ast.Attribute, ast.Name)))
        if not uses_jnp:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            name = imports.canon(node.func)
            if name and name.startswith("numpy.") and \
                    name.split(".")[-1] in _NP_CTORS and \
                    not any(kw.arg == "dtype" for kw in node.keywords) and \
                    len(node.args) < _ctor_dtype_pos(name):
                seen.add(id(node))
                out.append(("G003", node,
                            f"dtype-less `{name.replace('numpy', 'np')}` "
                            "in jnp code defaults to float64/int64 "
                            "(silent downcast or x64 promotion)",
                            "pass an explicit dtype= (e.g. np.float32), "
                            "or build it with jnp"))
    return out


def _ctor_dtype_pos(name: str) -> int:
    # positional index where dtype may be passed without the keyword
    return {"numpy.full": 3, "numpy.arange": 99, "numpy.linspace": 99,
            "numpy.eye": 99}.get(name, 2)


# --------------------------------------------------------------- G004

def g004_rng_discipline(tree, imports, path):
    """(a) np.random / stdlib random inside traced functions: baked in at
    trace time, identical every step. (b) a PRNG key consumed by two
    jax.random calls without a split between them: correlated streams."""
    out = []
    for fn, _tracked in _traced_functions(tree, imports):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = imports.canon(node.func) or ""
                if name.startswith("numpy.random.") or \
                        name.startswith("random."):
                    out.append(("G004", node,
                                f"`{name}` inside a traced function is "
                                "frozen at trace time (same draw every "
                                "step)",
                                "thread a jax PRNG key through the "
                                "function and use jax.random"))
    # (b) key reuse, per function scope
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        # keys born here, plus params that are keys by naming convention
        keys: set[str] = {
            a.arg for a in fn.args.posonlyargs + fn.args.args
            + fn.args.kwonlyargs if _KEY_PARAM_RE.search(a.arg)}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                name = imports.canon(node.value.func)
                if name in ("jax.random.PRNGKey", "jax.random.key"):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            keys.add(tgt.id)
        if not keys:
            continue
        consuming: dict[str, list[ast.Call]] = {k: [] for k in keys}
        rebinds: dict[str, list[int]] = {k: [] for k in keys}
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = imports.canon(node.func) or ""
                if name.startswith("jax.random.") and \
                        name.split(".")[-1] not in _KEY_NONCONSUMING | {
                            "PRNGKey", "key"}:
                    for a in node.args:
                        if isinstance(a, ast.Name) and a.id in keys:
                            consuming[a.id].append(node)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                tgts = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for tgt in tgts:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name) and n.id in keys:
                            rebinds[n.id].append(node.lineno)
        for key, uses in consuming.items():
            uses.sort(key=lambda n: n.lineno)
            for prev, cur in zip(uses, uses[1:]):
                # rebind may share the consuming line: key, s = split(key)
                if any(prev.lineno <= rb <= cur.lineno
                       for rb in rebinds[key]):
                    continue
                if _exclusive_paths(prev, cur, fn):
                    continue
                out.append(("G004", cur,
                            f"PRNG key `{key}` consumed again without "
                            f"a split (previous use line {prev.lineno}): "
                            "correlated random streams",
                            f"`{key}, sub = jax.random.split({key})` "
                            "and consume `sub`"))
    return out


def _enclosing_suites(node: ast.AST, fn: ast.AST):
    """(owner, field, suite) for every statement-suite between `node`
    and `fn`, innermost first — the control context of the node."""
    suites = []
    cur = node
    for par in _parents(node):
        for field in ("body", "orelse", "finalbody"):
            suite = getattr(par, field, None)
            if isinstance(suite, list) and any(s is cur for s in suite):
                suites.append((par, field, suite))
        cur = par
        if par is fn:
            break
    return suites


def _exclusive_paths(prev: ast.AST, cur: ast.AST, fn: ast.AST) -> bool:
    """True when `prev` executing implies `cur` cannot: they sit in
    opposite arms of one `if`, or prev's branch ends in return/raise
    (the if/elif-return ladder of weights.init_weight)."""
    prev_suites = _enclosing_suites(prev, fn)
    cur_owner_ids = {id(owner) for owner, _f, _s in
                     _enclosing_suites(cur, fn)}
    cur_suite_ids = {id(s) for _o, _f, s in _enclosing_suites(cur, fn)}
    for owner, field, suite in prev_suites:
        if isinstance(owner, ast.If):
            if id(owner) in cur_owner_ids and id(suite) not in \
                    cur_suite_ids:
                return True  # opposite arms of the same if
            if id(suite) not in cur_suite_ids and suite and isinstance(
                    suite[-1], (ast.Return, ast.Raise, ast.Continue,
                                ast.Break)):
                return True  # prev's arm leaves; cur is unreachable then
    return False


# --------------------------------------------------------------- G005

def g005_retrace_hazards(tree, imports, path):
    """jit re-creation per call — `jax.jit(f)(x)` or jit() inside a
    loop — recompiles every invocation; unhashable static_argnums raise
    at call time. Not caught: jit fns keyed on changing python scalars."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = imports.canon(node.func)
        if isinstance(node.func, ast.Call):
            inner = imports.canon(node.func.func)
            if inner in _JIT_NAMES:
                out.append(("G005", node,
                            "`jax.jit(f)(...)` creates and discards a "
                            "fresh compiled function every call (full "
                            "retrace each time)",
                            "hoist `jit(f)` to module level or cache it"))
        if name in _JIT_NAMES:
            for kw in node.keywords:
                if kw.arg == "static_argnums" and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    out.append(("G005", node,
                                "non-hashable static_argnums literal",
                                "use an int or tuple of ints"))
            for anc in _parents(node):
                if isinstance(anc, (ast.For, ast.While)):
                    out.append(("G005", node,
                                "jit() inside a loop body compiles a "
                                "fresh function per iteration",
                                "create the jitted function once, "
                                "outside the loop"))
                    break
                if isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                    break
    return out


# --------------------------------------------------------------- G006

def g006_shard_map_arity(tree, imports, path):
    """shard_map in_specs/out_specs arity vs the wrapped function, when
    both are statically visible. Single-spec (pytree-prefix) forms and
    non-local callables are out of scope by design."""
    out = []
    local_defs = {n.name: n for n in ast.walk(tree)
                  if isinstance(n, ast.FunctionDef)}

    def check(call: ast.Call, fn_node, report_at):
        specs = {kw.arg: kw.value for kw in call.keywords
                 if kw.arg in ("in_specs", "out_specs")}
        in_specs = specs.get("in_specs")
        if isinstance(in_specs, (ast.Tuple, ast.List)) and \
                fn_node is not None:
            lo, hi = _arity_range(fn_node)
            if lo is not None and not lo <= len(in_specs.elts) <= hi:
                out.append(("G006", report_at,
                            f"in_specs has {len(in_specs.elts)} specs but "
                            f"`{getattr(fn_node, 'name', '<lambda>')}` "
                            f"takes {lo}"
                            + (f"-{hi}" if hi != lo else "")
                            + " positional args",
                            "one spec per positional arg (or a single "
                            "pytree-prefix spec)"))
        out_specs = specs.get("out_specs")
        if isinstance(out_specs, (ast.Tuple, ast.List)) and \
                isinstance(fn_node, ast.FunctionDef):
            lens = _return_tuple_lens(fn_node)
            if lens and all(n != len(out_specs.elts) for n in lens):
                out.append(("G006", report_at,
                            f"out_specs has {len(out_specs.elts)} specs "
                            f"but `{fn_node.name}` returns "
                            f"{sorted(lens)} values",
                            "match out_specs to the returned tuple"))

    def resolve_target(arg):
        """(fn_node, bound_positional) for direct name / lambda /
        functools.partial over a local def."""
        if isinstance(arg, ast.Lambda):
            return arg, 0
        if isinstance(arg, ast.Name):
            return local_defs.get(arg.id), 0
        if isinstance(arg, ast.Call):
            name = imports.canon(arg.func)
            if name in ("functools.partial", "partial") and arg.args:
                fn, extra = resolve_target(arg.args[0])
                return fn, extra + len(arg.args) - 1
        return None, 0

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = imports.canon(node.func) or ""
            if name == "shard_map" or name.endswith(".shard_map"):
                if node.args:
                    fn_node, bound = resolve_target(node.args[0])
                    if fn_node is not None and bound == 0:
                        check(node, fn_node, node)
                    elif fn_node is None:
                        check(node, None, node)
        elif isinstance(node, ast.FunctionDef):
            for deco in node.decorator_list:
                dname, call = _decorator_canon(deco, imports)
                if call is not None and dname and (
                        dname == "shard_map"
                        or dname.endswith(".shard_map")):
                    check(call, node, call)
    return out


def _arity_range(fn_node):
    args = fn_node.args
    if args.vararg is not None:
        return None, None
    pos = len(args.posonlyargs) + len(args.args)
    return pos - len(args.defaults), pos


def _return_tuple_lens(fn: ast.FunctionDef) -> set[int] | None:
    lens: set[int] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            # only returns belonging to THIS def, not nested ones
            owner = next((p for p in _parents(node) if isinstance(
                p, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))),
                None)
            if owner is not fn:
                continue
            if isinstance(node.value, ast.Tuple):
                lens.add(len(node.value.elts))
            else:
                return None  # opaque return — cannot judge
    return lens or None


# --------------------------------------------------------------- G007

_COMPAT_SHIMS = {
    "jax.shard_map": "deeplearning4j_tpu.util.compat.shard_map",
    "jax.experimental.shard_map.shard_map":
        "deeplearning4j_tpu.util.compat.shard_map",
    "jax.lax.pcast": "deeplearning4j_tpu.util.compat.pcast_varying",
}


def g007_compat_bypass(tree, imports, path):
    """Raw uses of version-moved jax symbols (shard_map /
    TPUCompilerParams / pcast) that must route through util/compat.py so
    the next jax bump stays a one-file change."""
    if path.endswith("util/compat.py"):
        return []
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            for a in node.names:
                full = f"{mod}.{a.name}"
                if full in ("jax.shard_map",
                            "jax.experimental.shard_map.shard_map") or \
                        mod == "jax.experimental.shard_map":
                    out.append(("G007", node,
                                f"raw `from {mod} import {a.name}` moved "
                                "between jax 0.4/0.5",
                                "from deeplearning4j_tpu.util.compat "
                                "import shard_map"))
                elif a.name in ("TPUCompilerParams", "CompilerParams") \
                        and "pallas" in mod:
                    out.append(("G007", node,
                                f"raw `{a.name}` import was renamed "
                                "across jax versions",
                                "use util.compat.tpu_compiler_params()"))
        elif isinstance(node, ast.Attribute):
            name = imports.canon(node)
            if name in _COMPAT_SHIMS:
                out.append(("G007", node,
                            f"raw `{name}` moved between jax 0.4/0.5",
                            f"use {_COMPAT_SHIMS[name]}"))
            elif node.attr in ("TPUCompilerParams",):
                out.append(("G007", node,
                            "`TPUCompilerParams` was renamed "
                            "CompilerParams in jax 0.5",
                            "use util.compat.tpu_compiler_params()"))
            elif node.attr == "CompilerParams" and name and \
                    "pallas" in name:
                out.append(("G007", node,
                            "`CompilerParams` does not exist on jax "
                            "0.4.x pallas",
                            "use util.compat.tpu_compiler_params()"))
    return out


# --------------------------------------------------------------- G009

# the single home of the rendezvous layer; everything else routes
# through it (same shape as G007's compat routing)
_RENDEZVOUS_HOME = "distributed/bootstrap.py"

# the env-var contract's one spelling lives in bootstrap's ENV_*
# constants; a literal copy elsewhere silently forks the contract
_RENDEZVOUS_ENV_VARS = {
    "DL4J_TPU_COORDINATOR", "DL4J_TPU_PROCESS_ID",
    "DL4J_TPU_NUM_PROCESSES", "DL4J_TPU_LOCAL_DEVICE_COUNT",
    "DL4J_TPU_FAULTS",
}


def g009_rendezvous_routing(tree, imports, path):
    """Raw `jax.distributed.initialize`/`shutdown` calls or hand-rolled
    rendezvous env plumbing outside distributed/bootstrap.py. The
    bootstrap owns retry/backoff on connect, CPU-fleet collectives
    selection, the env-var contract, and per-process telemetry — a raw
    call sidesteps all four and reintroduces the untested-thin-wrapper
    failure mode (VERDICT r5 Missing #1)."""
    # the contract's home and this rule's own spelling of it are exempt
    if path.endswith((_RENDEZVOUS_HOME, "analysis/ast_rules.py")):
        return []
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            name = imports.canon(node)
            if name in ("jax.distributed.initialize",
                        "jax.distributed.shutdown"):
                out.append(("G009", node,
                            f"raw `{name}` bypasses the rendezvous "
                            "bootstrap (retry/backoff, env contract, "
                            "CPU collectives, telemetry)",
                            "use deeplearning4j_tpu.distributed."
                            "bootstrap.initialize()/shutdown()"))
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            if (node.module or "") == "jax.distributed":
                out.append(("G009", node,
                            "raw `from jax.distributed import ...` "
                            "bypasses the rendezvous bootstrap",
                            "use deeplearning4j_tpu.distributed."
                            "bootstrap.initialize()/shutdown()"))
        elif isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and \
                node.value in _RENDEZVOUS_ENV_VARS:
            out.append(("G009", node,
                        f"rendezvous env var {node.value!r} spelled as a "
                        "literal — the contract's one spelling lives in "
                        "distributed/bootstrap.py",
                        "import the ENV_* constant from "
                        "deeplearning4j_tpu.distributed.bootstrap"))
    return out


# --------------------------------------------------------------- G008

def g008_import_time(tree, imports, path):
    """(a) mutable default args — shared across calls; (b) module-level
    jnp allocations — they initialize a backend and pin a buffer at
    IMPORT time, before the program can pick devices/platform."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            for d in node.args.defaults + [
                    d for d in node.args.kw_defaults if d is not None]:
                bad = isinstance(d, (ast.List, ast.Dict, ast.Set,
                                     ast.ListComp, ast.DictComp,
                                     ast.SetComp))
                if isinstance(d, ast.Call) and isinstance(
                        d.func, ast.Name) and \
                        d.func.id in _MUTABLE_DEFAULT_CALLS:
                    bad = True
                if bad:
                    out.append(("G008", d,
                                "mutable default argument is shared "
                                "across calls",
                                "default to None; create inside the "
                                "function"))
    # module-level device allocations: top-level stmts (incl. if/try
    # bodies and class-attr assignments) — anything inside a def runs
    # lazily and is out of scope here.
    def scan(node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, ast.Call):
            name = imports.canon(node.func)
            if name in _DEVICE_ALLOC:
                out.append(("G008", node,
                            f"module-level `{name}` allocates a device "
                            "buffer at import time (captures the default "
                            "backend before it is configured)",
                            "allocate lazily inside a function, or keep "
                            "the constant in numpy"))
        for child in ast.iter_child_nodes(node):
            scan(child)

    for stmt in getattr(tree, "body", []):
        scan(stmt)
    return out


# --------------------------------------------------------------- G016

# The one module allowed to hold tunable Pallas block-size knobs: the
# tuning layer (table + heuristics + override hook). Kernels resolve
# their grids through it; a literal elsewhere re-freezes a knob the
# kerneltune sweep can no longer reach.
_TUNING_LAYER = ("ops/autotune.py",)

_PALLAS_BLOCKSPEC = {"jax.experimental.pallas.BlockSpec",
                     "jax.experimental.pallas.tpu.BlockSpec"}
_PALLAS_CALL = {"jax.experimental.pallas.pallas_call",
                "jax.experimental.pallas.tpu.pallas_call"}

# 128 is the hardware lane/sublane tile (MXU 128x128, VPU 8x128) —
# structural, not tunable; anything larger in a block/grid position is a
# swept knob that belongs in the tuning layer.
_G016_STRUCTURAL_MAX = 128

# module-level constant names that denote block/tile knobs (kernel files
# only): BLOCK_Q_MAX, _ROW_BLOCK, CHUNK_TILES, ...
_G016_CONST_RE = re.compile(r"BLOCK|TILE")


def _g016_literal_over(node: ast.AST):
    """Int literals > 128 anywhere inside a (possibly nested) tuple/list
    expression."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, int) \
                and not isinstance(sub.value, bool) \
                and sub.value > _G016_STRUCTURAL_MAX:
            yield sub


def g016_hardcoded_block_literals(tree, imports, path):
    """Pallas block-size/grid literals hardcoded outside the tuning
    layer (ops/autotune.py): (a) int literals > 128 inside a
    pl.BlockSpec block shape or a pallas_call grid= — the grid must be a
    function of the autotune-resolved block params, not a re-frozen
    constant; (b) module-level UPPERCASE BLOCK/TILE constants in ops/
    kernel files bound to int (or int-tuple) literals > 128 — the swept
    defaults live in autotune.py. 128 itself is the hardware lane tile
    (structural). Not caught: literals laundered through arithmetic
    (512 * 1) or non-BLOCK-named constants — precision over recall."""
    norm = path.replace("\\", "/")
    if any(norm.endswith(t) for t in _TUNING_LAYER):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = imports.canon(node.func)
        if name in _PALLAS_BLOCKSPEC:
            shape = None
            if node.args:
                shape = node.args[0]
            for kw in node.keywords:
                if kw.arg == "block_shape":
                    shape = kw.value
            if shape is not None and isinstance(shape, (ast.Tuple,
                                                        ast.List)):
                for lit in _g016_literal_over(shape):
                    out.append(("G016", lit,
                                f"hardcoded block-size literal "
                                f"{lit.value} in a pl.BlockSpec outside "
                                "the tuning layer — a knob the "
                                "kerneltune sweep cannot reach",
                                "resolve the block through "
                                "ops/autotune.py (flash_blocks/ln_rows/"
                                "xent_blocks) and pass the variable"))
        elif name in _PALLAS_CALL:
            for kw in node.keywords:
                if kw.arg == "grid" and isinstance(kw.value, (ast.Tuple,
                                                              ast.List)):
                    for lit in _g016_literal_over(kw.value):
                        out.append(("G016", lit,
                                    f"hardcoded grid literal {lit.value} "
                                    "in a pallas_call outside the tuning "
                                    "layer",
                                    "derive the grid from the autotune-"
                                    "resolved block sizes"))
    if "/ops/" in norm:
        for stmt in getattr(tree, "body", []):
            targets = []
            if isinstance(stmt, ast.Assign):
                targets = [t for t in stmt.targets
                           if isinstance(t, ast.Name)]
                value = stmt.value
            elif isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name) and stmt.value is not None:
                targets = [stmt.target]
                value = stmt.value
            else:
                continue
            for tgt in targets:
                if tgt.id.isupper() and _G016_CONST_RE.search(tgt.id):
                    for lit in _g016_literal_over(value):
                        out.append(("G016", lit,
                                    f"block/tile constant `{tgt.id}` "
                                    f"hardcodes {lit.value} in a kernel "
                                    "file — the swept defaults live in "
                                    "the tuning layer",
                                    "move the default to ops/autotune.py "
                                    "and alias it here"))
    return out


# --------------------------------------------------------------- G017

# Serving hot-path discipline (serving/ only). The continuous-batching
# contract is: requests are padded into the bucket lattice BEFORE any
# jitted call (else every novel length is a retrace worth seconds of
# tail latency), and results come back to host ONCE per batch (else N
# per-request device syncs serialize the pipeline). Exemptions are
# named, not inferred: bucket-shape dispatch (argument/function names
# mentioning bucket/batch/padded/warmup) and the batch-boundary fetch
# (a sync OUTSIDE a per-request loop).
_G017_REQUESTISH = re.compile(r"(^|_)(request|req|prompt)s?($|_|\b)",
                              re.IGNORECASE)
_G017_BUCKETISH = re.compile(r"bucket|batch|padded|warm", re.IGNORECASE)
_G017_SYNC_ATTRS = {"item", "block_until_ready"}
_G017_SYNC_CALLS = {"jax.device_get"}


def _g017_name_strings(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _g017_mentions(node: ast.AST, pattern) -> bool:
    return any(pattern.search(s) for s in _g017_name_strings(node))


def _g017_enclosing_fn_name(node: ast.AST) -> str:
    cur = getattr(node, "parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur.name
        cur = getattr(cur, "parent", None)
    return ""


def g017_serving_hot_path(tree, imports, path):
    """Serving hot-path rule (serving/ files only), two halves:

    (a) UNBUCKETED JIT ENTRY: a jit-wrapped callable invoked with an
        argument that mentions a request-ish name (request/req/prompt)
        and nothing bucket-ish (bucket/batch/padded/warm) — raw request
        data fed straight into jit compiles one program per novel
        length. Bucket-shape dispatch is exempt by the name carve-out;
        so are warmup/bucket-named enclosing functions.
    (b) PER-REQUEST HOST SYNC: `.item()` / `.block_until_ready()` /
        `jax.device_get` inside a for-loop that iterates request-ish
        values — N device round-trips per batch. The batch-boundary
        fetch (one `np.asarray`/sync per BATCH, outside such loops)
        never flags."""
    norm = path.replace("\\", "/")
    if "/serving/" not in norm:
        return []
    out = []
    # names bound to jit results: `fwd = jax.jit(f)` / `self._jit = ...`
    jit_bound: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and imports.canon(node.value.func) in _JIT_NAMES:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    jit_bound.add(tgt.id)
                elif isinstance(tgt, ast.Attribute):
                    jit_bound.add(tgt.attr)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = node.func
        is_jit_entry = (
            (isinstance(callee, ast.Name) and callee.id in jit_bound)
            or (isinstance(callee, ast.Attribute)
                and callee.attr in jit_bound)
            or (isinstance(callee, ast.Call)
                and imports.canon(callee.func) in _JIT_NAMES))
        if not is_jit_entry:
            continue
        if _G017_BUCKETISH.search(_g017_enclosing_fn_name(node)):
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if _g017_mentions(arg, _G017_REQUESTISH) \
                    and not _g017_mentions(arg, _G017_BUCKETISH):
                out.append(("G017", node,
                            "unbucketed jit entry: raw request data fed "
                            "straight into a jitted callable — every "
                            "novel request shape is a retrace worth "
                            "seconds of tail latency",
                            "pad the request into a bucket batch first "
                            "(serving/batcher.py assemble) and pass the "
                            "bucketed arrays"))
                break
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor)):
            continue
        if not (_g017_mentions(loop.target, _G017_REQUESTISH)
                or _g017_mentions(loop.iter, _G017_REQUESTISH)):
            continue
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            name = imports.canon(node.func)
            is_sync = name in _G017_SYNC_CALLS or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _G017_SYNC_ATTRS)
            if is_sync:
                out.append(("G017", node,
                            "per-request host sync inside a request "
                            "loop: one device round-trip per request "
                            "serializes the serving pipeline",
                            "fetch ONCE per batch (np.asarray on the "
                            "whole padded output — the batch-boundary "
                            "fetch) and distribute host-side rows"))
    return out


# --------------------------------------------------------------- G019

# Decode-loop discipline (serving/ only) — the generation-side twin of
# G017's host-sync half. The decode loop emits ONE token per active
# slot per step; the contract is ONE batch-boundary fetch of the whole
# next-token vector per step (np.asarray on the [n_slots] array), then
# host-side distribution. A `.item()` / `jax.device_get` /
# `.block_until_ready()` inside a loop over token-ish values is a
# device round-trip PER EMITTED TOKEN — at decode rates that serializes
# the whole generation pipeline behind host latency.
_G019_TOKENISH = re.compile(r"(^|_)(token|tok)s?($|_|\b)|decode",
                            re.IGNORECASE)


def g019_decode_loop_sync(tree, imports, path):
    """Per-token host syncs inside decode loops (serving/ files only):
    a for-loop whose target or iterable mentions token-ish names
    (token/tok/decode) containing `.item()` / `jax.device_get` /
    `.block_until_ready()`. The batch-boundary fetch — one sync for the
    whole step's token vector, OUTSIDE such loops — never flags."""
    norm = path.replace("\\", "/")
    if "/serving/" not in norm:
        return []
    out = []
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor)):
            continue
        if not (_g017_mentions(loop.target, _G019_TOKENISH)
                or _g017_mentions(loop.iter, _G019_TOKENISH)):
            continue
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            name = imports.canon(node.func)
            is_sync = name in _G017_SYNC_CALLS or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _G017_SYNC_ATTRS)
            if is_sync:
                out.append(("G019", node,
                            "per-token host sync inside a decode loop: "
                            "one device round-trip per emitted token "
                            "serializes the generation pipeline behind "
                            "host latency",
                            "fetch the step's whole next-token vector "
                            "ONCE (np.asarray at the batch boundary) "
                            "and distribute host-side values"))
    return out


# --------------------------------------------------------------- G020

# Input-pipeline discipline: the fit step loops ride
# data/pipeline.iter_prefetched, which runs batch conversion
# (`_batch_dict` / `globalize_batch`) and device placement on a
# prefetch thread. A synchronous conversion INSIDE a step loop — the
# `while it.has_next():` shape every fit loop had before ISSUE 12 —
# serializes host input work in front of every step: at N fleet
# processes that's a per-step input tax the pipeline exists to hide.
_G020_CONVERTERS = frozenset({"_batch_dict", "_globalize_batch",
                              "globalize_batch", "globalize_full"})
_G020_DEVICE_PUTS = frozenset({"jax.device_put"})
# blessed: the pipeline's own synchronous fallback (depth 0 /
# async-unsupported iterators) and the host-prefetch adapter
_G020_BLESSED = ("deeplearning4j_tpu/data/",
                 "deeplearning4j_tpu/datasets/async_iterator.py")


def g020_sync_input_in_step_loop(tree, imports, path):
    """Synchronous batch conversion / device placement inside a fit
    step loop: a `while <x>.has_next():` loop containing a call to
    `_batch_dict` / `_globalize_batch` / `globalize_batch` /
    `globalize_full` or `jax.device_put`. Whole-epoch staging
    (`fit_scanned`'s list comprehension), per-window TBPTT conversion
    (a `for` over range), and batch-boundary fetches never flag — the
    rule keys on the step-loop shape itself."""
    norm = path.replace("\\", "/")
    if any(b in norm for b in _G020_BLESSED):
        return []
    out = []
    for loop in ast.walk(tree):
        if not isinstance(loop, ast.While):
            continue
        has_next = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr == "has_next"
            for n in ast.walk(loop.test))
        if not has_next:
            continue
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            is_converter = (isinstance(node.func, ast.Attribute)
                            and node.func.attr in _G020_CONVERTERS) or \
                imports.canon(node.func) in _G020_CONVERTERS
            is_put = imports.canon(node.func) in _G020_DEVICE_PUTS
            if is_converter or is_put:
                out.append(("G020", node,
                            "synchronous batch conversion/device put "
                            "inside a fit step loop: host input work "
                            "runs serially in front of every step "
                            "instead of overlapping compute",
                            "route the loop through data/pipeline."
                            "iter_prefetched so conversion and the "
                            "device put run on the prefetch thread "
                            "(the depth-k bounded queue of device-"
                            "resident batches)"))
    return out


# --------------------------------------------------------------- G021

# Weight-swap discipline: serving replicas read their params through the
# engine's double-buffered WeightStore (serving/fleet.py), read ONCE per
# batch so a live hot-swap flips between batches and every request
# serves against ONE coherent generation. A direct write to a live
# `.params` reference, or a `resume_from` restore into a serving net
# outside the blessed path, bypasses the standby-slot restore, the
# shape/placement validation, the atomic flip, AND the `weight_swap`
# telemetry record — the swap happens (or half-happens) invisibly, mid-
# batch, with no rollback.
_G021_BLESSED = ("deeplearning4j_tpu/serving/fleet.py",)


def g021_weight_swap_path(tree, imports, path):
    """Param publish/flip outside the blessed swap path (serving/ files
    only; serving/fleet.py exempt): (a) assignment to a `.params`
    attribute — a direct write to what a worker serves; (b) any
    `.resume_from(...)` call — restoring INTO a serving net must route
    through fleet.restore_for_serving / fleet.hot_swap. Reading params
    (`ws.params`, `net.params is None`) never flags."""
    norm = path.replace("\\", "/")
    if "/serving/" not in norm or any(b in norm for b in _G021_BLESSED):
        return []
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if isinstance(t, ast.Attribute) and t.attr == "params":
                    out.append((
                        "G021", node,
                        "direct write to a live param reference in "
                        "serving code: bypasses the WeightStore double "
                        "buffer — a replica mid-batch can observe a "
                        "half-swapped param set and there is no "
                        "validation, generation record, or rollback",
                        "publish through serving/fleet.py: "
                        "hot_swap(engine, ckpt) restores into a shadow "
                        "net, validates, and flips atomically"))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "resume_from":
            out.append((
                "G021", node,
                "resume_from on a net inside serving code: restores "
                "INTO the served params outside the blessed swap path "
                "(no double buffer, no shape/placement validation, no "
                "weight_swap telemetry, old weights unrecoverable on a "
                "bad checkpoint)",
                "route restores through serving/fleet."
                "restore_for_serving (startup) or fleet.hot_swap "
                "(live)"))
    return out


# --------------------------------------------------------------- G022

# Placement discipline at the USER-FACING layers: examples/, cli/, and
# the elastic runtime are where mesh layouts get hand-guessed — exactly
# the habit the automatic placement search (reshard/search.py) retires.
# A raw `jax.sharding.Mesh(...)` construction, or an axis-role dict
# literal ({"data": ..., "model": ...}) fed to a mesh builder /
# set_mesh, bypasses Placement validation (PlacementError feasibility)
# AND the search's ranking+telemetry — the layout ships unvalidated and
# unrecorded. The blessed spellings are `planner.Placement.of/
# from_json` (validated declarative data; set_mesh consumes it
# directly) and `search_placement`/`searched_global_mesh` (the ranked
# search). Library internals (parallel/, reshard/, distributed/
# global_mesh) stay out of scope: they IMPLEMENT the blessed paths.
_G022_SCOPE_FRAGMENTS = ("/examples/", "/cli/")
_G022_SCOPE_SUFFIXES = ("distributed/elastic.py",)
_G022_ROLE_NAMES = frozenset({"data", "model", "pipe", "seq", "expert"})
_G022_MESH_CALL_TAILS = frozenset({"Mesh", "make_mesh", "make_global_mesh",
                                   "set_mesh"})
_G022_BLESSED_TAILS = frozenset({"search_placement",
                                 "searched_global_mesh"})


def _g022_call_tail(func: ast.AST) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _g022_is_blessed(node: ast.Call) -> bool:
    """Placement.of / Placement.from_json / search entry points."""
    func = node.func
    tail = _g022_call_tail(func)
    if tail in _G022_BLESSED_TAILS:
        return True
    if isinstance(func, ast.Attribute) and tail in ("of", "from_json"):
        base = func.value
        base_name = (base.attr if isinstance(base, ast.Attribute)
                     else getattr(base, "id", ""))
        return base_name == "Placement"
    return False


def _g022_role_dict(arg: ast.AST) -> bool:
    """A dict literal whose string keys are ALL placement roles (and at
    least one) — the hand-written axis/role map shape. Comprehensions,
    parsed variables, and non-role dicts never flag."""
    if not isinstance(arg, ast.Dict) or not arg.keys:
        return False
    keys = []
    for k in arg.keys:
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            return False
        keys.append(k.value)
    return all(k in _G022_ROLE_NAMES for k in keys)


def g022_handrolled_placement(tree, imports, path):
    """Hand-constructed placements at the user-facing layers (examples/,
    cli/, distributed/elastic.py): (a) a raw `jax.sharding.Mesh(...)`
    constructor call; (b) an axis-role dict literal passed to
    make_mesh / make_global_mesh / set_mesh / Mesh. Route through
    `planner.Placement.of` (validated declarative data — set_mesh
    consumes the Placement directly) or `search_placement`/
    `searched_global_mesh` (the ranked search), whose own calls are
    exempt."""
    # leading slash so relative paths ("examples/foo.py") match too
    norm = "/" + path.replace("\\", "/").lstrip("/")
    if not (any(f in norm for f in _G022_SCOPE_FRAGMENTS)
            or any(norm.endswith(s) for s in _G022_SCOPE_SUFFIXES)):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or _g022_is_blessed(node):
            continue
        name = imports.canon(node.func) or ""
        tail = _g022_call_tail(node.func)
        if name == "jax.sharding.Mesh" or name.endswith("sharding.Mesh"):
            out.append(("G022", node,
                        "raw `jax.sharding.Mesh(...)` construction in a "
                        "user-facing layer: the layout skips Placement "
                        "validation (PlacementError feasibility) and the "
                        "placement search's ranking + telemetry",
                        "declare the layout as planner.Placement.of(...) "
                        "and feed it to set_mesh, or let "
                        "search_placement pick it"))
            continue
        if tail not in _G022_MESH_CALL_TAILS:
            continue
        for arg in list(node.args) + [k.value for k in node.keywords]:
            if _g022_role_dict(arg):
                out.append(("G022", node,
                            f"hand-written axis-role dict literal fed to "
                            f"`{tail}` in a user-facing layer — an "
                            "unvalidated, unranked mesh layout (the "
                            "habit the automatic placement search "
                            "retires)",
                            "build the layout with planner.Placement.of "
                            "(set_mesh consumes it directly) or take "
                            "the search_placement winner"))
                break
    return out


# --------------------------------------------------------------- G023

# Telemetry schema discipline: the fleet-timeline tooling
# (telemetry/trace.py merge/stats/anomaly/Perfetto, tools/tracetool.py)
# classifies every record it merges by its event kind and span name.
# An event("...")/span("...") literal invented at a call site is a
# record the registered schema (recorder.py EVENT_KINDS/SPAN_NAMES +
# the docstring table) doesn't know — it parses as noise, joins no
# tree, and silently falls out of stats and anomaly detection. The
# blessed home of new kinds/names is the registry itself: telemetry/
# is exempt (it IS the schema), and dynamic names (f-strings like the
# bench sweep's `mode:<name>` spans) are uncheckable statically and
# stay silent. The same holds for the regions of a compiled program: a
# `named_scope("...")` literal or a layer impl's `region = "..."` that
# REGION_NAMES lacks is a region no reader of the device trace knows.
_G023_EXEMPT = ("deeplearning4j_tpu/telemetry/",)
_G023_SETS: dict = {}


def _g023_registered():
    """(EVENT_KINDS, SPAN_NAMES, REGION_NAMES) from the registry,
    cached; resolves under the stage-1 no-jax stubs (telemetry/ is
    stdlib-pure). An unresolvable registry disables the rule rather than
    crashing the lint."""
    if "sets" not in _G023_SETS:
        try:
            from deeplearning4j_tpu.telemetry.recorder import (EVENT_KINDS,
                                                               REGION_NAMES,
                                                               SPAN_NAMES)
            _G023_SETS["sets"] = (EVENT_KINDS, SPAN_NAMES, REGION_NAMES)
        except Exception:  # pragma: no cover - broken stub layouts
            _G023_SETS["sets"] = None
    return _G023_SETS["sets"]


def _g023_str_arg(node: ast.AST):
    return node.value if (isinstance(node, ast.Constant)
                          and isinstance(node.value, str)) else None


def _g023_regions(tree, region_names) -> list:
    """Region literals: the first argument of a `named_scope(...)` call
    and a class-level `region = "..."`."""
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args and (
                getattr(node.func, "attr", None) == "named_scope"
                or getattr(node.func, "id", None) == "named_scope"):
            found.append((node, _g023_str_arg(node.args[0])))
        elif isinstance(node, ast.ClassDef):
            found.extend(
                (st, _g023_str_arg(st.value)) for st in node.body
                if isinstance(st, ast.Assign)
                and any(getattr(t, "id", None) == "region"
                        for t in st.targets))
    return [("G023", node,
             f"region {lit!r} is not in the registered schema "
             "(telemetry/recorder.py REGION_NAMES): no reader of a "
             "program's device time by region knows it",
             "register the region in REGIONS first, or reuse an "
             "existing one")
            for node, lit in found
            if lit is not None and lit not in region_names]


def g023_unregistered_telemetry_names(tree, imports, path):
    """An `<obj>.event("<kind>")` whose kind literal is not a
    registered EVENT_KIND, or an `<obj>.span("<name>")` /
    `event("span", name="<name>")` whose name literal is not a
    registered SPAN_NAME, or a `named_scope("<region>")` / a class's
    `region = "<region>"` whose literal is not a registered REGION_NAME,
    outside telemetry/. Non-literal (variable / f-string) names and
    non-string first arguments (`re.Match.span(0)`) never flag."""
    norm = path.replace("\\", "/")
    if any(b in norm for b in _G023_EXEMPT):
        return []
    sets = _g023_registered()
    if sets is None:
        return []
    event_kinds, span_names, region_names = sets
    out = _g023_regions(tree, region_names)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr not in ("span", "event") \
                or not node.args:
            continue
        lit = _g023_str_arg(node.args[0])
        if lit is None:
            continue
        if node.func.attr == "span":
            if lit not in span_names:
                out.append(("G023", node,
                            f"span name {lit!r} is not in the registered "
                            "schema (telemetry/recorder.py SPAN_NAMES): "
                            "the fleet-timeline tooling cannot classify "
                            "it — it joins no stats row, no tree, no "
                            "anomaly rule",
                            "register the name in SPAN_NAMES (and the "
                            "recorder docstring table) first, or reuse "
                            "an existing span name"))
            continue
        if lit not in event_kinds:
            out.append(("G023", node,
                        f"event kind {lit!r} is not in the registered "
                        "schema (telemetry/recorder.py EVENT_KINDS): "
                        "merged timelines parse it as noise",
                        "register the kind in EVENT_KINDS (and the "
                        "recorder docstring table) first, or use a "
                        "typed Recorder method"))
        elif lit == "span":
            for kw in node.keywords:
                if kw.arg != "name":
                    continue
                name_lit = _g023_str_arg(kw.value)
                if name_lit is not None and name_lit not in span_names:
                    out.append(("G023", node,
                                f"span name {name_lit!r} (via "
                                "event(\"span\", name=...)) is not in "
                                "the registered schema "
                                "(telemetry/recorder.py SPAN_NAMES)",
                                "register the name in SPAN_NAMES (and "
                                "the recorder docstring table) first"))
    return out


# --------------------------------------------------------------- G024

# Sampling discipline (serving/ only) — the sampling-side twin of
# G019's host-sync half. Token selection belongs ON DEVICE in the one
# fused kernel (ops/fused_sampling.fused_sample: temperature, top-k,
# top-p and the gumbel argmax in a single pass, f32 accumulation).
# Host-side sampling inside a decode loop — an `np.random.*` /
# `random.*` draw, or an `argsort` / `cumsum` over fetched logits to
# rebuild top-k/top-p by hand — ships the [slots, vocab] logit matrix
# to the host EVERY STEP and reorders the vocab in numpy: at decode
# rates that is the pipeline's largest avoidable transfer, and the
# hand-rolled filter drifts from the kernel's tie-breaking.
_G024_HOST_RNG_PREFIXES = ("numpy.random.", "random.")
_G024_SORTISH_ATTRS = frozenset({"argsort", "cumsum"})
_G024_SORTISH_CALLS = frozenset({"numpy.argsort", "numpy.cumsum"})
_G024_LOGITSISH = re.compile(r"logit|prob|score", re.IGNORECASE)


def g024_host_sampling(tree, imports, path):
    """Host-side sampling in decode loops (serving/ files only): inside
    a for-loop whose target or iterable mentions token-ish names
    (token/tok/decode), flag `np.random.*` / `random.*` draws and
    `argsort`/`cumsum` calls over logits-ish values (logit/prob/score).
    The blessed path is ops/fused_sampling.fused_sample — one fused
    on-device kernel per step, with host code handling only the
    returned token ids."""
    norm = path.replace("\\", "/")
    if "/serving/" not in norm:
        return []
    out = []
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor)):
            continue
        if not (_g017_mentions(loop.target, _G019_TOKENISH)
                or _g017_mentions(loop.iter, _G019_TOKENISH)):
            continue
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            name = imports.canon(node.func) or ""
            if name.startswith(_G024_HOST_RNG_PREFIXES):
                out.append(("G024", node,
                            "host RNG draw inside a decode loop: token "
                            "selection off-device means a per-step "
                            "logit fetch and numpy-side sampling that "
                            "drifts from the kernel's tie-breaking",
                            "sample on device via ops/fused_sampling."
                            "fused_sample (temperature/top-k/top-p in "
                            "one kernel; gumbel noise from a split PRNG "
                            "key) and distribute the returned ids"))
                continue
            sortish = name in _G024_SORTISH_CALLS or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _G024_SORTISH_ATTRS)
            if not sortish:
                continue
            over_logits = any(
                _g017_mentions(arg, _G024_LOGITSISH)
                for arg in list(node.args)
                + [kw.value for kw in node.keywords]) or (
                isinstance(node.func, ast.Attribute)
                and _g017_mentions(node.func.value, _G024_LOGITSISH))
            if over_logits:
                out.append(("G024", node,
                            "host-side top-k/top-p reconstruction "
                            "(argsort/cumsum over logits) inside a "
                            "decode loop: the [slots, vocab] matrix "
                            "crosses to the host every step",
                            "filter on device via ops/fused_sampling."
                            "fused_sample — its top-k/top-p masking "
                            "runs in the same kernel as the sample"))
    return out


# --------------------------------------------------------------- G029

# Memory-introspection discipline — the observability twin of G002's
# host-sync rule. `dev.memory_stats()` queries the backend allocator,
# `jax.live_arrays()` walks EVERY live buffer in the process, and
# `compiled.memory_analysis()` re-summarizes an executable: host work
# measured in milliseconds, and inside a jit-traced function they
# additionally burn in as compile-time constants (the trace sees one
# snapshot forever). The blessed producers put the walk where the hot
# path can't feel it: telemetry/memstat.py samples at batch boundaries
# / on its own thread, telemetry/costbook.py harvests at warmup-time
# compile. Everyone else consumes their cached `memory`/`cost` events.
_G029_BLESSED = ("deeplearning4j_tpu/telemetry/memstat.py",
                 "deeplearning4j_tpu/telemetry/costbook.py")
_G029_INTROSPECT = frozenset({"memory_stats", "live_arrays",
                              "memory_analysis"})
_G029_CANON = frozenset({"jax.live_arrays"})


def g029_memory_introspection_hot_path(tree, imports, path):
    """A `memory_stats()` / `live_arrays()` / `memory_analysis()` call
    inside a jit-traced function or a per-token / per-request loop.
    Batch-boundary or warmup-time introspection (plain functions, no
    hot loop) stays silent — that IS the sampler contract — and the
    two blessed producer modules are exempt."""
    norm = path.replace("\\", "/")
    if norm.endswith(_G029_BLESSED):
        return []
    out = []
    seen: set[int] = set()

    def scan(scope, where):
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            attr = (node.func.attr
                    if isinstance(node.func, ast.Attribute) else None)
            name = imports.canon(node.func) or ""
            if attr in _G029_INTROSPECT or name in _G029_CANON:
                seen.add(id(node))
                out.append((
                    "G029", node,
                    f"device-memory introspection ({attr or name}) "
                    f"inside {where}: a full live-buffer walk / "
                    "allocator query on the hot path — and under jit "
                    "it traces as a frozen compile-time constant",
                    "sample at batch boundaries via telemetry/"
                    "memstat.py (MemorySampler.on_step/maybe_sample) "
                    "or harvest at warmup via telemetry/costbook.py, "
                    "then read the cached event/ledger"))

    for fn, _params in _traced_functions(tree, imports):
        scan(fn, "a jit-traced function")
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor)):
            continue
        if (_g017_mentions(loop.target, _G019_TOKENISH)
                or _g017_mentions(loop.iter, _G019_TOKENISH)
                or _g017_mentions(loop.target, _G017_REQUESTISH)
                or _g017_mentions(loop.iter, _G017_REQUESTISH)):
            scan(loop, "a per-token/per-request loop")
    return out


# --------------------------------------------------------------- G030

# Sparse-embedding discipline — the data-movement twin of G016's
# block-literal rule. An embedding step touches a handful of rows out
# of a vocab-sized table; the two ways to lose that sparsity are (a) a
# dense `jnp.take` gather over the full table outside the engine (at
# ep>1 this materializes every shard's rows on every rank instead of
# the masked-psum partial gather) and (b) densifying the sparse
# gradient — `jnp.zeros_like(table).at[idx].add(grads)` allocates and
# all-reduces a full table-shaped buffer where the overlap layer's
# sparse bucket kind (parallel/overlap.plan_sparse_bucket) moves only
# (indices, values) pairs. The blessed sites own those patterns: the
# embedding engine internally (its scatter is per-shard, post-psum),
# the legacy dense reference (nlp/lookup.py — the ep=1 parity anchor),
# and the device pipeline's fused epoch step.
_G030_BLESSED = ("deeplearning4j_tpu/embedding/",
                 "deeplearning4j_tpu/nlp/lookup.py",
                 "deeplearning4j_tpu/nlp/device_pipeline.py")
# identifiers that read as a full embedding table; deliberately exact
# (cum_table / tuning_table / a weight "W" must not match)
_G030_TABLEISH = re.compile(
    r"^(syn0|syn1|syn1neg|embed(ding)?s?(_table)?|emb_table|"
    r"lookup_table|vocab_table)$")
_G030_TABLE_NAMES = frozenset({"syn0", "syn1", "syn1neg"})


def _g030_ident(node: ast.AST) -> str | None:
    """The identifier text of a table-ish operand: bare name, attribute
    leaf (`self.syn0`), or a constant subscript key (`params["table"]`)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Subscript):
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            return sl.value
    return None


def _g030_is_zeros_like(node: ast.AST, imports) -> bool:
    return (isinstance(node, ast.Call)
            and imports.canon(node.func) in ("jax.numpy.zeros_like",
                                             "numpy.zeros_like"))


def g030_dense_embedding_path(tree, imports, path):
    """A full-table gather (`jnp.take(table, ...)`, `syn0[idx]`) or a
    densified sparse gradient (`jnp.zeros_like(table).at[idx].add(g)`)
    outside the embedding engine's blessed internals — the dense
    pattern the sparse (indices, values) contract exists to replace."""
    norm = path.replace("\\", "/")
    if any(b in norm if b.endswith("/") else norm.endswith(b)
           for b in _G030_BLESSED):
        return []
    out = []
    for node in ast.walk(tree):
        # (a) dense gather: jnp.take over a table-ish operand, or a
        # direct subscript load of the canonical table names
        if isinstance(node, ast.Call) \
                and imports.canon(node.func) == "jax.numpy.take" \
                and node.args:
            ident = _g030_ident(node.args[0])
            if ident and _G030_TABLEISH.match(ident):
                out.append((
                    "G030", node,
                    f"dense jnp.take over the full embedding table "
                    f"({ident!r}) outside the engine: at ep>1 this "
                    "gathers every shard's rows on every rank",
                    "route lookups through embedding/engine.py "
                    "(ShardedEmbeddingEngine.embed / the step's masked "
                    "partial gather + psum)"))
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in _G030_TABLE_NAMES:
            out.append((
                "G030", node,
                f"direct subscript gather over embedding table "
                f"{node.value.id!r} outside the blessed dense "
                "reference (nlp/lookup.py)",
                "use embedding/engine.py's sharded gather (or the "
                "EngineLookupView accessors, which slice the padded "
                "device table once)"))
        # (b) densified sparse gradient:
        # jnp.zeros_like(T).at[idx].add(values)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "add" \
                and isinstance(node.func.value, ast.Subscript) \
                and isinstance(node.func.value.value, ast.Attribute) \
                and node.func.value.value.attr == "at" \
                and _g030_is_zeros_like(node.func.value.value.value,
                                        imports):
            out.append((
                "G030", node,
                "sparse gradient densified into a table-shaped buffer "
                "(zeros_like(table).at[idx].add(values)): allocates "
                "and reduces the full vocab where only the touched "
                "rows carry signal",
                "keep gradients as (indices, values) pairs and move "
                "them with parallel/overlap.sparse_bucket_reduce (the "
                "sparse bucket kind); scatter per-shard inside "
                "embedding/engine.py"))
    return out


# stage-3 AST rules (G010-G014) live in spmd_rules.py and register here;
# the import sits below every helper they borrow lazily, so importing
# either module first resolves cleanly.
from deeplearning4j_tpu.analysis.spmd_rules import (  # noqa: E402
    SPMD_RULE_DOCS,
    SPMD_RULES,
)
# stage-4 AST rules (G025-G028, host-concurrency) live in
# concurrency_rules.py and register the same way
from deeplearning4j_tpu.analysis.concurrency_rules import (  # noqa: E402
    CONC_RULE_DOCS,
    CONC_RULE_IDS,
    CONC_RULES,
)
# stage-5 AST rules (G031-G034, precision discipline) live in
# precision_rules.py and register the same way
from deeplearning4j_tpu.analysis.precision_rules import (  # noqa: E402
    PRECISION_RULE_DOCS,
    PRECISION_RULE_IDS,
    PRECISION_RULES,
)

ALL_RULES = [g001_traced_bool, g002_host_sync, g003_float64_drift,
             g004_rng_discipline, g005_retrace_hazards,
             g006_shard_map_arity, g007_compat_bypass, g008_import_time,
             g009_rendezvous_routing,
             g016_hardcoded_block_literals,
             g017_serving_hot_path, g019_decode_loop_sync,
             g020_sync_input_in_step_loop,
             g021_weight_swap_path,
             g022_handrolled_placement,
             g023_unregistered_telemetry_names,
             g024_host_sampling,
             g029_memory_introspection_hot_path,
             g030_dense_embedding_path] + SPMD_RULES + CONC_RULES \
    + PRECISION_RULES

RULE_DOCS = {
    "G001": "python control flow / bool()/float()/int() on traced values",
    "G002": "implicit host sync (.item/np.asarray/device_get) in hot paths",
    "G003": "dtype-less np constructors mixed into jnp code (float64 drift)",
    "G004": "np.random/random in traced code; PRNG key reuse without split",
    "G005": "per-call jit creation / non-hashable static_argnums (retraces)",
    "G006": "shard_map in_specs/out_specs arity vs wrapped function",
    "G007": "version-moved jax symbols bypassing util/compat.py",
    "G008": "mutable default args; module-level jnp allocations",
    "G009": "raw jax.distributed / rendezvous env plumbing bypassing "
            "distributed/bootstrap.py",
    "G016": "Pallas block-size/grid literals hardcoded outside the "
            "tuning layer (ops/autotune.py)",
    "G017": "serving hot-path discipline: unbucketed jit entries and "
            "per-request host syncs in serving/ (bucket dispatch and "
            "the batch-boundary fetch are exempt)",
    "G019": "decode-loop discipline: per-token host syncs "
            "(.item/device_get/block_until_ready) inside token-ish "
            "loops in serving/ — the generation pipeline's per-step "
            "batch-boundary fetch is the blessed pattern",
    "G020": "synchronous globalize_batch/_batch_dict/device-put inside "
            "fit step loops (while has_next) bypassing the data/ input "
            "pipeline — the pipeline's own sync fallback and the "
            "AsyncDataSetIterator adapter are the blessed sites",
    "G021": "param publish/flip outside the blessed serving/fleet.py "
            "swap path: direct `.params` assignment or `resume_from` "
            "in serving/ bypasses the double-buffered WeightStore "
            "(validation, atomic flip, weight_swap telemetry)",
    "G022": "hand-constructed Mesh(...) / axis-role dict literals in "
            "the user-facing layers (examples/, cli/, "
            "distributed/elastic.py) outside the blessed "
            "planner.Placement / search_placement paths — unvalidated, "
            "unranked mesh layouts",
    "G023": "telemetry event kinds / span names invented at the call "
            "site: an event(\"...\")/span(\"...\") string literal "
            "outside telemetry/ that is not in the registered schema "
            "(recorder.py EVENT_KINDS/SPAN_NAMES) — the fleet-timeline "
            "tooling cannot classify such records",
    "G024": "sampling discipline: host-side token sampling "
            "(np.random/random draws, argsort/cumsum over logits) "
            "inside decode loops in serving/ — token selection belongs "
            "in the fused on-device kernel "
            "(ops/fused_sampling.fused_sample)",
    "G029": "memory-introspection discipline: memory_stats()/"
            "live_arrays()/memory_analysis() inside jit-traced "
            "functions or per-token/per-request loops — a live-buffer "
            "walk on the hot path (frozen as a constant under jit); "
            "the blessed producers are telemetry/memstat.py (batch-"
            "boundary sampler) and telemetry/costbook.py (warmup "
            "harvest)",
    "G030": "sparse-embedding discipline: dense jnp.take / subscript "
            "gathers over full-vocab embedding tables, and sparse "
            "gradients densified via zeros_like(table).at[].add(...), "
            "outside the blessed engine internals (embedding/, "
            "nlp/lookup.py, nlp/device_pipeline.py) — gradients travel "
            "as (indices, values) pairs through the overlap layer's "
            "sparse bucket kind",
    **SPMD_RULE_DOCS,
    **CONC_RULE_DOCS,
    **PRECISION_RULE_DOCS,
}


def run_rules(tree: ast.AST, source: str, path: str) -> list[Finding]:
    """All rules over one parsed file -> raw findings (no suppression)."""
    _walk_with_parents(tree)
    imports = Imports(tree)
    lines = source.splitlines()
    findings = []
    for rule in ALL_RULES:
        for rule_id, node, message, fixit in rule(tree, imports, path):
            line = getattr(node, "lineno", 0)
            col = getattr(node, "col_offset", 0)
            snippet = lines[line - 1].strip() if 0 < line <= len(lines) \
                else ""
            stage = ("concurrency" if rule_id in CONC_RULE_IDS
                     else "precision" if rule_id in PRECISION_RULE_IDS
                     else "ast")
            findings.append(Finding(rule_id, path, line, col, message,
                                    fixit, snippet, stage=stage))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
