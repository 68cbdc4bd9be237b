"""Tier-1 gate for graftlint (ISSUE 2 + the ISSUE 5 SPMD rules + the
ISSUE 17 concurrency stage + the ISSUE 18 memory-introspection rule +
the ISSUE 19 sparse-embedding rule): every AST rule G001-G030 proven on a
positive AND a negative fixture, the suppression + baseline machinery,
the stage-2 jaxpr audit over every public entry point, and the package
itself held lint-clean (zero non-baselined findings). The stage-3
collective audit has its own gate in tests/test_spmd_lint.py; the
stage-4 lock-order audit and guard-map inference have theirs in
tests/test_concurrency_lint.py.

PR 1 burned its budget reactively fixing exactly these bug classes
(silent RNG divergence, jax API drift, modes that crashed only at real
dims); this file is what makes them build-breaking instead."""

import json
import os
import subprocess
import sys

import pytest

from deeplearning4j_tpu.analysis import (RULE_DOCS, lint_report,
                                         lint_source, load_baseline,
                                         split_baselined)
from deeplearning4j_tpu.analysis.core import Finding

pytestmark = pytest.mark.lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "deeplearning4j_tpu")
BASELINE = os.path.join(ROOT, "tools", "graftlint_baseline.json")
CLI = os.path.join(ROOT, "tools", "graftlint.py")

# fixtures land in a location that is BOTH a G002 hot path and inside
# the G011 SPMD scope (parallel/ is in HOT_PATH_FRAGMENTS and _G011_SCOPE)
FIXTURE_PATH = "deeplearning4j_tpu/parallel/_graftlint_fixture.py"

_PRELUDE = """\
import functools
import os
import random
import time
import numpy as np
import jax
import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from deeplearning4j_tpu.util.compat import shard_map
"""


def rules_in(src, path=FIXTURE_PATH):
    return {f.rule for f in lint_source(_PRELUDE + src, path)}


# ----------------------------------------------- per-rule fixtures
# (rule, positive source, negative source) — the negative exercises the
# precision carve-outs, not just an empty file.

FIXTURES = [
    ("G001", """\
@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
""", """\
@jax.jit
def f(x, flag):
    if x is None:
        return flag
    if x.shape[0] > 2:
        return jnp.where(x > 0, x, -x)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def g(x, causal):
    if causal:
        return x
    return -x
"""),
    ("G001", """\
@jax.jit
def f(x):
    s = x.sum()
    return float(s)
""", """\
def host(x):
    return float(x.sum())
"""),
    ("G002", """\
def step(x):
    y = np.asarray(x)
    return y.item()
""", """\
def step(x):
    y = jnp.asarray(x)
    return y
"""),
    ("G003", """\
def f(x):
    w = np.arange(5)
    return jnp.dot(x, w)
""", """\
def f(x):
    w = np.arange(5, dtype=np.float32)
    return jnp.dot(x, w)


def host_only():
    return np.arange(5)
"""),
    ("G004", """\
@jax.jit
def f(x):
    noise = np.random.randn(4)
    return x + noise
""", """\
@jax.jit
def f(x, key):
    return x + jax.random.normal(key, x.shape)
"""),
    ("G004", """\
def sample():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))
    return a + b
""", """\
def sample():
    key = jax.random.PRNGKey(0)
    key, sub = jax.random.split(key)
    a = jax.random.normal(sub, (2,))
    key, sub2 = jax.random.split(key)
    b = jax.random.uniform(sub2, (2,))
    k1 = jax.random.fold_in(key, 1)
    k2 = jax.random.fold_in(key, 2)
    return a + b, k1, k2
"""),
    ("G004", """\
def consume_twice(key):
    a = jax.random.split(key)
    b = jax.random.split(key)
    return a, b
""", """\
def init_ladder(rng, scheme, shape):
    if scheme == "normal":
        return jax.random.normal(rng, shape)
    if scheme == "uniform":
        return jax.random.uniform(rng, shape)
    raise ValueError(scheme)


def arms(rng, flag):
    if flag:
        return jax.random.normal(rng, (2,))
    else:
        return jax.random.uniform(rng, (2,))
"""),
    ("G005", """\
def g(x):
    return x


def f(x):
    return jax.jit(g)(x)
""", """\
def g(x):
    return x


fast_g = jax.jit(g)


def f(x):
    return fast_g(x)
"""),
    ("G005", """\
def g(x):
    return x


def f(xs):
    out = []
    for x in xs:
        h = jax.jit(g)
        out.append(h(x))
    return out
""", """\
def g(x):
    return x


def f(xs):
    h = jax.jit(g, static_argnums=(0,))
    return [h(x) for x in xs]
"""),
    ("G006", """\
def local(a, b):
    return a + b


def run(mesh, P):
    return shard_map(local, mesh=mesh,
                     in_specs=(P, P, P), out_specs=P)
""", """\
def local(a, b):
    return a + b


def run(mesh, P):
    one = shard_map(local, mesh=mesh, in_specs=(P, P), out_specs=P)
    pre = shard_map(local, mesh=mesh, in_specs=P, out_specs=P)
    return one, pre
"""),
    ("G006", """\
def local(a):
    return a, a + 1


def run(mesh, P):
    return shard_map(local, mesh=mesh, in_specs=(P,),
                     out_specs=(P, P, P))
""", """\
def local(a):
    return a, a + 1


def run(mesh, P):
    return shard_map(local, mesh=mesh, in_specs=(P,),
                     out_specs=(P, P))
"""),
    ("G007", """\
from jax.experimental.shard_map import shard_map as raw_shard_map
from jax.experimental.pallas import tpu as pltpu


def params():
    return pltpu.TPUCompilerParams(dimension_semantics=("parallel",))
""", """\
from deeplearning4j_tpu.util.compat import (pcast_varying, shard_map,
                                            tpu_compiler_params)


def params():
    return tpu_compiler_params(dimension_semantics=("parallel",))
"""),
    ("G008", """\
K = jnp.zeros((4,))


def f(x, acc=[]):
    acc.append(x)
    return K + x
""", """\
K = np.zeros((4,), dtype=np.float32)


def f(x, acc=None):
    if acc is None:
        acc = []
    acc.append(x)
    return jnp.zeros((4,)) + x
"""),
    ("G009", """\
def up(addr, n, i):
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=n, process_id=i)
""", """\
def up(addr, n, i):
    from deeplearning4j_tpu.distributed import bootstrap

    bootstrap.initialize(coordinator_address=addr, num_processes=n,
                         process_id=i)
"""),
    ("G009", """\
import os


def wire(env):
    env["DL4J_TPU_PROCESS_ID"] = "0"
    return os.environ.get("DL4J_TPU_COORDINATOR")
""", """\
import os

from deeplearning4j_tpu.distributed.bootstrap import (ENV_COORDINATOR,
                                                      ENV_PROCESS_ID)


def wire(env):
    env[ENV_PROCESS_ID] = "0"
    return os.environ.get(ENV_COORDINATOR)
"""),
    ("G010", """\
def up(x):
    if jax.process_index() == 0:
        return jax.lax.psum(x, "data")
    return x
""", """\
def up(x, process_id, axis_name):
    if process_id == 0:
        print("rank 0: host-side logging/checkpoint IO is fine")
    return jax.lax.psum(x, axis_name)
"""),
    ("G010", """\
from deeplearning4j_tpu.distributed.bootstrap import ENV_PROCESS_ID
from deeplearning4j_tpu.parallel.mesh import make_mesh


def up(f, x):
    if os.environ[ENV_PROCESS_ID] == "0":
        mesh = make_mesh({"data": 8})
    return f(x)
""", """\
from deeplearning4j_tpu.parallel.mesh import make_mesh


def up(f, x, process_index):
    mesh = make_mesh({"data": 8})
    if process_index == 0:
        path = "checkpoint.zip"
    return f(x)
"""),
    ("G011", """\
def f(x):
    t = time.time()
    return jnp.full((2,), t)
""", """\
def f(x, rec):
    rec.event(time.time())
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.random(3))
"""),
    ("G012", """\
def f(x):
    return jax.lax.pmean(x, "data")
""", """\
def g(x, axis_name):
    return jax.lax.psum(x, axis_name)


def run(mesh, x):
    local = lambda a: jax.lax.pmean(a, "data")
    return shard_map(local, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P())(x)


def wrapped(a):
    return jax.lax.psum(a, "seq")


def outer(mesh, x):
    return shard_map(wrapped, mesh=mesh, in_specs=(P("seq"),),
                     out_specs=P())(x)
"""),
    ("G013", """\
def sync(x, loss):
    if jax.process_index() == 0:
        return loss.item()
    return x
""", """\
def sync(x, loss, process_id):
    if process_id == 0:
        path = "ck.zip"
    jax.block_until_ready(x)
    return x
"""),
    ("G014", """\
def sync(x, axis_name):
    try:
        return jax.lax.psum(x, axis_name)
    except Exception:
        return x
""", """\
def sync(x, axis_name):
    try:
        return jax.lax.psum(x, axis_name)
    except ConnectionError:
        raise RuntimeError("fleet lost")


def sync_cleanup(x, axis_name):
    try:
        return jax.lax.psum(x, axis_name)
    except Exception:
        raise


def teardown():
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def retry_outside_distributed():
    while True:
        try:
            connect()
            break
        except OSError:
            time.sleep(0.1)
"""),
    ("G015", """\
def reduce_step(grads, axis_name):
    return jax.lax.pmean(grads, axis_name)
""", """\
def reduce_params(params, axis_name):
    return jax.lax.pmean(params, axis_name)


def reduce_loss(loss, acts, axis_name):
    return jax.lax.psum(loss, axis_name), jax.lax.pmean(acts, axis_name)
"""),
    ("G017", """\
fwd = jax.jit(lambda p, s, x: x)


def handle(request, params, state):
    y = fwd(params, state, request.features)
    outs = []
    for req in request.siblings:
        outs.append(req.result.item())
    return y, outs
""", """\
fwd = jax.jit(lambda p, s, x, m: x)


def run_batch(batch, params, state):
    y = fwd(params, state, batch.features, batch.mask)
    rows = np.asarray(y)
    for req, row in zip(batch.requests, rows):
        req.set_result(row)
    return rows


def warmup_bucket(params, state, zeros, mask):
    return fwd(params, state, zeros, mask)
"""),
    ("G016", """\
from jax.experimental import pallas as pl


def build(kern, x):
    return pl.pallas_call(
        kern,
        grid=(8, 512),
        in_specs=[pl.BlockSpec((512, 128), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec(block_shape=(256, 128),
                               index_map=lambda i, j: (i, 0)),
    )(x)
""", """\
from jax.experimental import pallas as pl
from deeplearning4j_tpu.ops import autotune


def build(kern, x, T, D):
    bq, bk = autotune.flash_blocks(T, D, causal=True, dropout=False,
                                   masked=False)
    return pl.pallas_call(
        kern,
        grid=(T // bq, 8),
        in_specs=[pl.BlockSpec((bq, 128), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, 3), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
    )(x)
"""),
    ("G019", """\
def stream_decoded(emitted_tokens, sink):
    for tok in emitted_tokens:
        sink.write(tok.item())
""", """\
def decode_step_fetch(step_out, slots):
    toks = np.asarray(step_out)  # ONE batch-boundary fetch per step
    for slot, value in zip(slots, toks.tolist()):
        slot.emit(value)
"""),
    ("G020", """\
def fit(net, it, step):
    while it.has_next():
        ds = it.next()
        batch = net._batch_dict(ds)
        placed = jax.device_put(batch)
        step(placed)
""", """\
from deeplearning4j_tpu.data.pipeline import iter_prefetched


def fit(net, it, step):
    for ds, batch in iter_prefetched(it, net._batch_dict):
        step(batch)


def stage_epoch(net, data):
    # whole-epoch staging (fit_scanned), not a step loop
    return [net._batch_dict(ds) for ds in data]


def fit_tbptt(net, ds, step, L):
    for t0 in range(0, ds.features.shape[1], L):
        step(net._batch_dict(ds.slice_time(t0, L)))
"""),
    ("G018", """\
from deeplearning4j_tpu.util.orbax_checkpoint import host_materialize


def snapshot(net):
    tree = host_materialize(net.params)
    flat = jax.device_get(net.opt_state)
    moments = jax.tree.map(np.asarray, net.opt_state)
    return tree, flat, moments
""", """\
def read_one(net, params):
    w = np.asarray(params["W"])        # single leaf, not the tree
    s = np.asarray(net.score_value)    # a derived scalar
    placed = jax.tree.map(jax.device_put, net.params, net._param_sh)
    return w, s, placed
"""),
    ("G021", """\
def adopt_new_weights(worker, new_params, ckpt_dir):
    worker.net.params = new_params     # direct live-param write
    worker.net.resume_from(ckpt_dir)   # restore outside the swap path
""", """\
def serve_one(self, batch):
    ws = self.weights.current          # the ONE read per batch
    return self._jit(ws.params, ws.state, batch.features)


def swap(engine, ckpt_dir):
    from deeplearning4j_tpu.serving import fleet
    return fleet.hot_swap(engine, ckpt_dir)  # the blessed path


def init_if_needed(net):
    if net.params is None:             # reading params never flags
        net.init()
"""),
    ("G022", """\
def run(net, devices):
    mesh = jax.sharding.Mesh(devices, ("data",))     # raw ctor
    net.set_mesh(mesh, axes={"data": "data"})        # role-dict literal


def train(net):
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh({"data": 2, "model": 4})        # role-dict literal
""", """\
from deeplearning4j_tpu.reshard.planner import Placement
from deeplearning4j_tpu.reshard.search import FleetShape, search_placement


def run(net, fleet_spec):
    result = search_placement(net, FleetShape.parse(fleet_spec))
    net.set_mesh(result.winner)            # the searched winner


def declare(net):
    # the validated declarative spelling: Placement.of IS the blessed
    # home of the role-dict literal
    placement = Placement.of({"data": 2, "expert": 4},
                             {"data": "data", "expert": "expert"})
    net.set_mesh(placement)


def parsed(net, make_mesh, axes):
    # parsed/derived dicts (CLI --mesh) and comprehensions never flag
    mesh = make_mesh(axes)
    net.set_mesh(mesh, axes={r: r for r in axes})
    opts = {"data": "d.csv"}               # a non-mesh dict is silent
    return opts
"""),
    ("G023", """\
def run(rec):
    with rec.span("my_invented_phase"):       # unregistered span name
        pass
    rec.event("telemetry_blob", x=1)          # unregistered event kind
    rec.event("span", name="custom_region",   # unregistered via name=
              ok=True, seconds=0.0)
    with jax.named_scope("my_layer"):           # unregistered region
        pass


class MyImpl:
    region = "mixer"                          # unregistered region
""", """\
def run(rec, m, mode):
    with rec.span("compile", what="fit_scanned"):   # registered name
        pass
    rec.event("fault", kind="reform")               # registered kind
    rec.event("span", name="bucket_reduce",         # registered name=
              ok=True, seconds=0.0)
    rec.event("anomaly", kind="straggler")          # the detector kind
    a, b = m.span(0)              # non-string first arg (re.Match.span)
    name = "dynamic"
    rec.span(name)                # variable names are uncheckable
    with rec.span(f"mode:{mode}"):  # f-strings parse as opaque spans
        pass
    with jax.named_scope("attention"):             # registered region
        with jax.named_scope(region):              # variable: uncheckable
            pass


class MyImpl:
    region = "ffn"                                 # registered region
"""),
    ("G024", """\
def sample_tokens(slots, logits_batch):
    for slot, decode_row in zip(slots, logits_batch):
        if np.random.random() < 0.5:              # host RNG per token
            order = np.argsort(decode_row_logits)  # host top-k rebuild
            mass = np.cumsum(probs[order])         # host top-p rebuild
""", """\
from deeplearning4j_tpu.ops.fused_sampling import fused_sample


def sample_step(slots, logits, noise):
    ids = fused_sample(logits, noise, temperature=0.8,
                       top_k=32, top_p=0.9)        # the blessed kernel
    for slot, tok in zip(slots, np.asarray(ids).tolist()):
        slot.emit(tok)


def order_slots(slots):
    # argsort over non-logits values in a token loop stays silent
    for tok_batch in slots:
        ranks = np.argsort(tok_batch.arrival_times)


def seed_proposer(seed):
    # host RNG OUTSIDE decode loops (setup, jitter) is not sampling
    return np.random.default_rng(seed)
"""),
    # ------------------------------------------- stage 4 (ISSUE 17)
    ("G025", """\
import threading


class RacyWorker:
    def __init__(self):
        self.served = 0
        self._thread = None

    def start(self):
        def loop():
            for _ in range(1000):
                self.served += 1

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self):
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    def describe(self):
        return {"served": self.served}
""", """\
import threading


class GuardedWorker:
    def __init__(self):
        self._mu = threading.Lock()
        self.served = 0
        self._thread = None

    def start(self):
        def loop():
            for _ in range(1000):
                with self._mu:
                    self.served += 1

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self):
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    def describe(self):
        with self._mu:
            return {"served": self.served}
"""),
    ("G026", """\
import queue
import threading


class BlockingDispatcher:
    def __init__(self):
        self._lock = threading.Lock()
        self.q = queue.Queue(maxsize=4)

    def dispatch(self, item):
        with self._lock:
            self.q.put(item)      # blocks every lock contender

    def backoff(self):
        with self._lock:
            time.sleep(0.05)
""", """\
import queue
import threading


class PoliteDispatcher:
    def __init__(self):
        self._cv = threading.Condition()
        self._buf = []
        self.q = queue.Queue(maxsize=4)

    def try_drain(self):
        with self._cv:
            return self.q.get(block=False)   # non-blocking: exempt

    def wait_item(self):
        with self._cv:
            while not self._buf:
                self._cv.wait(0.1)           # waits on the HELD cond
            return self._buf.pop()

    def dispatch(self, item):
        with self._cv:
            target = self.q                  # snapshot under the lock
        target.put(item)                     # block outside it
"""),
    ("G027", """\
import threading


class SloppyWaiter:
    def __init__(self):
        self._cv = threading.Condition()
        self.ready = False

    def await_once(self):
        with self._cv:
            self._cv.wait(0.5)    # no while-predicate re-check

    def poke(self):
        self._cv.notify_all()     # owning lock not held

    def spin(self):
        while not self.ready:
            time.sleep(0.01)      # sleep-poll loop
""", """\
import threading


class PatientWaiter:
    def __init__(self):
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self.ready = False

    def await_ready(self):
        with self._cv:
            while not self.ready:
                self._cv.wait(0.5)

    def set_ready(self):
        with self._cv:
            self.ready = True
            self._cv.notify_all()

    def idle(self):
        while not self._stop.is_set():
            self._stop.wait(0.05)   # Event stop-flag, not a sleep poll
"""),
    ("G028", """\
import threading


class FireAndForget:
    def launch(self):
        t = threading.Thread(target=self._loop)
        t.start()                 # non-daemon, never joined

    def _loop(self):
        pass


class BareDaemon:
    def launch(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        pass
""", """\
import threading


class SupervisedWorker:
    def __init__(self):
        self._thread = None

    def launch(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        pass

    def stop(self):
        if self._thread is not None:
            self._thread.join(timeout=1.0)
"""),
    # ------------------------------------------- ISSUE 18 (memory)
    ("G029", """\
import jax


@jax.jit
def forward(params, batch):
    hbm = jax.devices()[0].memory_stats()     # frozen at trace time
    return params


def decode_all(slots):
    for tok in slots:
        live = sum(a.nbytes for a in jax.live_arrays())  # per-token walk


def serve(requests, compiled):
    for req in requests:
        peak = compiled.memory_analysis()     # per-request re-summary
""", """\
import jax


def snapshot():
    # batch-boundary sampling OUTSIDE traced/hot contexts is the
    # sampler contract, not a violation
    return sum(a.nbytes for a in jax.live_arrays())


def harvest(compiled):
    # warmup-time harvest in a plain function
    return compiled.memory_analysis()


def decode_all(slots, cached_memory_event):
    for tok in slots:
        read = cached_memory_event["live_array_bytes"]  # cached, no walk
"""),
    # ---------------------------------------- ISSUE 19 (embeddings)
    ("G030", """\
import jax.numpy as jnp


def lookup_rows(syn0, idx):
    return jnp.take(syn0, idx, axis=0)        # dense full-table gather


def lookup_direct(syn1neg, idx):
    return syn1neg[idx]                       # same, spelled as subscript


def densify_grad(embedding_table, idx, values):
    # table-shaped zeros + scatter: the densified sparse gradient
    return jnp.zeros_like(embedding_table).at[idx].add(values)
""", """\
import jax.numpy as jnp


def lookup_weight(params, idx):
    return jnp.take(params["W"], idx, axis=0)  # a weight, not a table


def gather_cum(cum_table, draws):
    return cum_table[draws]                    # sampling table, exempt


def accumulate(W, i, g):
    return W.at[i].add(g)                      # in-place, not zeros_like


def engine_step(table, idx, values):
    from deeplearning4j_tpu.parallel.overlap import sparse_bucket_reduce
    return sparse_bucket_reduce(idx, values, "data")
"""),
    # ---------------------------------------- ISSUE 20 (precision)
    ("G031", """\
def scores(q, k):
    s = jnp.einsum("qd,kd->qk", q, k)      # accumulator dtype implicit
    return s + q @ k.T                     # `@` cannot declare one
""", """\
def scores(q, k):
    return jnp.einsum("qd,kd->qk", q, k,
                      preferred_element_type=jnp.float32)
"""),
    ("G032", """\
def f(x):
    y = x.astype(jnp.float64)
    z = jnp.zeros((2,), dtype="float64")
    w = np.float64(3.0)
    return y, z, w
""", """\
def f(x):
    y = x.astype(jnp.float32)
    z = jnp.zeros((2,), dtype="float32")
    return y, z


_DTYPES = {"float64": jnp.float64, "float32": jnp.float32}
"""),
    ("G033", """\
def quantize(vals, maxabs):
    scale = maxabs / 127.0
    return jnp.clip(jnp.round(vals / scale), -127, 127), scale
""", """\
from deeplearning4j_tpu.ops.decode_attention import quantize_pages


def quantize(vals):
    return quantize_pages(vals)


def round_up(n):
    return (n + 127) // 128 * 128          # lane-tile round-up, exempt


BLOCK = 128
"""),
    ("G034", """\
def downcast(net):
    half = net.params.astype(jnp.bfloat16)
    opt = jax.tree.map(lambda x: x.astype(jnp.bfloat16), net.opt_state)
    return half, opt
""", """\
def place(params):
    w = params["W"].astype(jnp.bfloat16)   # single leaf, not the tree
    moved = jax.tree.map(jnp.asarray, params)  # no cast in the mapped fn
    return w, moved
"""),
]


# rules whose scope excludes the default fixture path lint their
# fixtures at a path inside their scope (G017: serving/ hot paths)
RULE_FIXTURE_PATHS = {
    "G017": "deeplearning4j_tpu/serving/_graftlint_fixture.py",
    "G019": "deeplearning4j_tpu/serving/_graftlint_fixture.py",
    "G021": "deeplearning4j_tpu/serving/_graftlint_fixture.py",
    "G024": "deeplearning4j_tpu/serving/_graftlint_fixture.py",
    "G022": "deeplearning4j_tpu/cli/_graftlint_fixture.py",
    # stage-4 scoped rules: G026 (serving//data//telemetry/) and G027
    # (serving//data/) lint their fixtures on a serving/ path
    "G026": "deeplearning4j_tpu/serving/_graftlint_fixture.py",
    "G027": "deeplearning4j_tpu/serving/_graftlint_fixture.py",
    # G031 (accumulator discipline) is scoped to the kernel dirs
    "G031": "deeplearning4j_tpu/ops/_graftlint_fixture.py",
}


@pytest.mark.parametrize(
    "rule,pos,neg", FIXTURES,
    ids=[f"{r}-{i}" for i, (r, _, _) in enumerate(FIXTURES)])
def test_rule_fires_on_positive_not_negative(rule, pos, neg):
    path = RULE_FIXTURE_PATHS.get(rule, FIXTURE_PATH)
    assert rule in rules_in(pos, path), f"{rule} missed its positive fixture"
    assert rule not in rules_in(neg, path), f"{rule} false-positive"


def test_every_rule_has_fixture_coverage():
    assert {r for r, _, _ in FIXTURES} == set(RULE_DOCS) == {
        f"G{i:03d}" for i in range(1, 35)}


def test_g015_blessed_sites_are_exempt():
    """The bucket planner and the train-step assembly are the two
    blessed gradient-collective sites; the same source flags anywhere
    else in the package."""
    src = ("def reduce_step(grads, axis_name):\n"
           "    return jax.lax.psum(grads, axis_name)\n")
    assert "G015" not in rules_in(
        src, "deeplearning4j_tpu/parallel/overlap.py")
    assert "G015" not in rules_in(
        src, "deeplearning4j_tpu/nn/training.py")
    assert "G015" in rules_in(
        src, "deeplearning4j_tpu/parallel/sequence_parallel.py")
    assert "G015" in rules_in(src)  # the default fixture path


def test_g017_scope_and_carveouts():
    """G017 is serving/-only (the same source is silent elsewhere), and
    both named carve-outs hold: bucket-ish argument names and
    warmup/bucket-named enclosing functions don't flag the jit-entry
    half; a batch-boundary sync outside a request loop doesn't flag the
    host-sync half."""
    rule_id, pos, neg = next(f for f in FIXTURES if f[0] == "G017")
    serving = RULE_FIXTURE_PATHS["G017"]
    assert "G017" in rules_in(pos, serving)
    assert "G017" not in rules_in(pos)  # parallel/ default path: out of scope
    assert "G017" not in rules_in(pos, "deeplearning4j_tpu/nn/x.py")
    # batch-boundary fetch: one sync per batch, outside a request loop
    boundary = ("fwd = jax.jit(lambda p, s, x: x)\n"
                "def run(batch, p, s):\n"
                "    y = fwd(p, s, batch.features)\n"
                "    return np.asarray(y).item()\n")
    assert "G017" not in rules_in(boundary, serving)


def test_g019_scope_and_batch_boundary_carveout():
    """G019 is serving/-only, and the decode loop's blessed pattern —
    ONE np.asarray of the step's whole next-token vector, host-side
    distribution after — never flags; the per-token `.item()` does."""
    _, pos, neg = next(f for f in FIXTURES if f[0] == "G019")
    serving = RULE_FIXTURE_PATHS["G019"]
    assert "G019" in rules_in(pos, serving)
    assert "G019" not in rules_in(pos)  # parallel/ default path: out of scope
    assert "G019" not in rules_in(pos, "deeplearning4j_tpu/nn/decode.py")
    # a sync on a non-token loop stays G019-silent (G017 owns requests)
    other = ("def collect(results):\n"
             "    for r in results:\n"
             "        r.block_until_ready()\n")
    assert "G019" not in rules_in(other, serving)


def test_g024_scope_and_carveouts():
    """G024 is serving/-only: the same host-sampling source is silent
    in ops/ (where the kernel's own reference path legitimately sorts
    logits) and on the default path; argsort over non-logits values and
    host RNG outside decode loops never flag."""
    _, pos, neg = next(f for f in FIXTURES if f[0] == "G024")
    serving = RULE_FIXTURE_PATHS["G024"]
    assert "G024" in rules_in(pos, serving)
    assert "G024" not in rules_in(pos)  # parallel/ default path
    assert "G024" not in rules_in(
        pos, "deeplearning4j_tpu/ops/fused_sampling.py")
    # an RNG draw in a non-token loop stays G024-silent
    other = ("def jitter(requests):\n"
             "    for r in requests:\n"
             "        r.delay = np.random.random()\n")
    assert "G024" not in rules_in(other, serving)


def test_g020_blessed_paths_and_loop_shape():
    """The pipeline's own synchronous fallback (data/) and the
    AsyncDataSetIterator adapter are the blessed conversion sites; the
    same step-loop source flags anywhere else, and a non-has_next while
    loop never engages the rule."""
    _, pos, _ = next(f for f in FIXTURES if f[0] == "G020")
    assert "G020" not in rules_in(
        pos, "deeplearning4j_tpu/data/pipeline.py")
    assert "G020" not in rules_in(
        pos, "deeplearning4j_tpu/datasets/async_iterator.py")
    assert "G020" in rules_in(pos)  # the default parallel/ fixture path
    assert "G020" in rules_in(pos, "deeplearning4j_tpu/nn/multilayer.py")
    other = ("def drain(q, net):\n"
             "    while q:\n"
             "        net._batch_dict(q.pop())\n")
    assert "G020" not in rules_in(other)


def test_g018_blessed_paths_are_exempt():
    """The resharding engine and the two checkpoint formats ARE the
    places full-tree host materialization is allowed; the same source
    flags anywhere else in the package."""
    src = ("def snap(net):\n"
           "    return jax.device_get(net.params)\n")
    assert "G018" not in rules_in(
        src, "deeplearning4j_tpu/reshard/executor.py")
    assert "G018" not in rules_in(
        src, "deeplearning4j_tpu/util/orbax_checkpoint.py")
    assert "G018" not in rules_in(
        src, "deeplearning4j_tpu/util/model_serializer.py")
    assert "G018" in rules_in(src)  # the default parallel/ fixture path
    assert "G018" in rules_in(src, "deeplearning4j_tpu/serving/engine.py")


def test_g021_scope_and_blessed_swap_path():
    """G021 is serving/-only (a training loop assigning net.params is
    legitimate elsewhere), serving/fleet.py is THE blessed publish/flip
    site, and both halves fire independently: the `.params` assignment
    without resume_from, and resume_from without an assignment."""
    _, pos, _ = next(f for f in FIXTURES if f[0] == "G021")
    serving = RULE_FIXTURE_PATHS["G021"]
    assert "G021" in rules_in(pos, serving)
    assert "G021" in rules_in(pos, "deeplearning4j_tpu/serving/engine.py")
    assert "G021" not in rules_in(pos)  # parallel/ default: out of scope
    assert "G021" not in rules_in(
        pos, "deeplearning4j_tpu/nn/multilayer.py")
    assert "G021" not in rules_in(
        pos, "deeplearning4j_tpu/serving/fleet.py")  # the blessed path
    assign_only = "def f(w, p):\n    w.net.params = p\n"
    resume_only = "def f(net, d):\n    return net.resume_from(d)\n"
    assert "G021" in rules_in(assign_only, serving)
    assert "G021" in rules_in(resume_only, serving)


def test_g022_scope_and_blessed_paths():
    """G022 covers the user-facing layers only — examples/, cli/, and
    distributed/elastic.py (library internals IMPLEMENT the blessed
    paths and stay silent) — and both halves fire independently: the
    raw Mesh ctor without a role dict, and a role-dict literal without
    a raw ctor. Placement.of keeps its role-dict literals."""
    _, pos, neg = next(f for f in FIXTURES if f[0] == "G022")
    cli = RULE_FIXTURE_PATHS["G022"]
    assert "G022" in rules_in(pos, cli)
    assert "G022" in rules_in(pos, "examples/data_parallel_training.py")
    assert "G022" in rules_in(
        pos, "deeplearning4j_tpu/distributed/elastic.py")
    # out of scope: the library layers that implement the blessed paths
    assert "G022" not in rules_in(pos)  # parallel/ default fixture path
    assert "G022" not in rules_in(
        pos, "deeplearning4j_tpu/parallel/mesh.py")
    assert "G022" not in rules_in(
        pos, "deeplearning4j_tpu/distributed/global_mesh.py")
    raw_only = ("def f(devices):\n"
                "    return jax.sharding.Mesh(devices, ('data',))\n")
    dict_only = ("def f(net, mesh):\n"
                 "    net.set_mesh(mesh, axes={'data': 'data'})\n")
    blessed = ("from deeplearning4j_tpu.reshard.planner import Placement\n"
               "def f(net):\n"
               "    net.set_mesh(Placement.of({'data': 8},\n"
               "                              {'data': 'data'}))\n")
    assert "G022" in rules_in(raw_only, cli)
    assert "G022" in rules_in(dict_only, cli)
    assert "G022" not in rules_in(blessed, cli)


def test_g022_user_facing_layers_sweep_clean():
    """The rule's whole scope — examples/ (outside the package sweep)
    plus cli/ and distributed/elastic.py — holds zero G022 findings:
    every mesh the user-facing layers build now routes through
    Placement / the search."""
    targets = [os.path.join(ROOT, "examples"),
               os.path.join(PKG, "cli"),
               os.path.join(PKG, "distributed", "elastic.py")]
    new, _old = lint_report(targets, load_baseline(BASELINE), root=ROOT)
    hits = [f for f in new if f.rule == "G022"]
    assert not hits, "G022 findings in user-facing layers:\n" + "\n".join(
        f.format() for f in hits)


def test_g023_scope_and_registry():
    """G023 holds everywhere EXCEPT telemetry/ (the registry is the
    blessed home of new kinds/names), checks the `event("span",
    name=...)` spelling, and the whole package + bench.py + tools sweep
    clean — every literal the code emits is registered."""
    _, pos, neg = next(f for f in FIXTURES if f[0] == "G023")
    hits = [f for f in lint_source(_PRELUDE + pos, FIXTURE_PATH)
            if f.rule == "G023"]
    # span literal + event kind + name= kwarg + scope + impl region
    assert len(hits) == 5
    # the registry itself is exempt: the same source is silent there
    assert "G023" not in rules_in(
        pos, "deeplearning4j_tpu/telemetry/recorder.py")
    assert "G023" not in rules_in(
        pos, "deeplearning4j_tpu/telemetry/trace.py")
    # in scope across the package AND outside it (bench.py, tools/)
    assert "G023" in rules_in(pos, "deeplearning4j_tpu/serving/engine.py")
    assert "G023" in rules_in(pos, "bench.py")
    # the registered sets ARE the recorder's: a name added to the
    # registry immediately stops flagging
    from deeplearning4j_tpu.telemetry.recorder import (EVENT_KINDS,
                                                       SPAN_NAMES)
    assert "compile" in SPAN_NAMES and "anomaly" in EVENT_KINDS
    assert "my_invented_phase" not in SPAN_NAMES


def test_g029_scope_and_blessed_producers():
    """G029 is contextual: introspection flags inside jit-traced fns
    and token/request loops anywhere, EXCEPT the two blessed producer
    modules (memstat.py batch-boundary sampler, costbook.py warmup
    harvest); the same walks outside those contexts — the sampler
    contract itself — never flag."""
    _, pos, neg = next(f for f in FIXTURES if f[0] == "G029")
    hits = [f for f in lint_source(pos, FIXTURE_PATH)
            if f.rule == "G029"]
    assert len(hits) == 3  # traced fn + token loop + request loop
    assert "G029" not in rules_in(
        pos, "deeplearning4j_tpu/telemetry/memstat.py")
    assert "G029" not in rules_in(
        pos, "deeplearning4j_tpu/telemetry/costbook.py")
    # non-blessed telemetry files are NOT exempt (unlike G023's scope)
    assert "G029" in rules_in(
        pos, "deeplearning4j_tpu/telemetry/trace.py")
    # a walk at a batch boundary (plain function, no hot loop) is the
    # design, not a finding
    boundary = ("import jax\n\n"
                "def sample_now():\n"
                "    return [a.nbytes for a in jax.live_arrays()]\n")
    assert "G029" not in rules_in(boundary)
    # a loop over non-token/non-request names stays silent even with
    # introspection inside (precision over recall)
    cold = ("import jax\n\n"
            "def audit(checkpoints):\n"
            "    for ckpt in checkpoints:\n"
            "        print(sum(a.nbytes for a in jax.live_arrays()))\n")
    assert "G029" not in rules_in(cold)


def test_g029_package_sweeps_clean():
    """No hot-path memory introspection anywhere in the package, the
    bench, or the tools — the only producers are the blessed modules."""
    targets = [PKG, os.path.join(ROOT, "bench.py"),
               os.path.join(ROOT, "tools")]
    new, _old = lint_report(targets, load_baseline(BASELINE), root=ROOT)
    hits = [f for f in new if f.rule == "G029"]
    assert not hits, "hot-path memory introspection:\n" + "\n".join(
        f.format() for f in hits)


def test_g023_whole_surface_sweeps_clean():
    """Every telemetry literal the repo emits — package, bench.py,
    examples/, and the tools — is in the registered schema."""
    targets = [PKG, os.path.join(ROOT, "bench.py"),
               os.path.join(ROOT, "examples"),
               os.path.join(ROOT, "tools")]
    new, _old = lint_report(targets, load_baseline(BASELINE), root=ROOT)
    hits = [f for f in new if f.rule == "G023"]
    assert not hits, "unregistered telemetry names:\n" + "\n".join(
        f.format() for f in hits)


def test_g016_tuning_layer_and_scope():
    """The tuning layer itself is exempt (it IS where block literals
    live); the module-constant half applies to ops/ kernel files only,
    and 128 (the hardware lane tile) never flags."""
    spec = ("from jax.experimental import pallas as pl\n"
            "def f():\n"
            "    return pl.BlockSpec((512, 128), lambda i: (i, 0))\n")
    assert "G016" in rules_in(spec, "deeplearning4j_tpu/ops/x.py")
    assert "G016" in rules_in(spec)  # BlockSpec half is package-wide
    assert "G016" not in rules_in(spec,
                                  "deeplearning4j_tpu/ops/autotune.py")
    const = "BLOCK_Q_MAX = 512\nCHUNK_TILES = (8192, 4096)\n"
    assert "G016" in rules_in(const, "deeplearning4j_tpu/ops/x.py")
    assert "G016" not in rules_in(const,
                                  "deeplearning4j_tpu/ops/autotune.py")
    # constants half is scoped to kernel files; non-ops code with a
    # TILE-named constant (e.g. a plotting grid) stays clean
    assert "G016" not in rules_in(const,
                                  "deeplearning4j_tpu/plot/x.py")
    lane = ("from jax.experimental import pallas as pl\n"
            "BLOCK = 128\n"
            "def f(bn):\n"
            "    return pl.BlockSpec((bn, 128), lambda i: (i, 0))\n")
    assert "G016" not in rules_in(lane, "deeplearning4j_tpu/ops/x.py")


def test_g031_scope_and_embedding_dir():
    """G031 covers the kernel dirs only (ops/ + embedding/): a
    contraction elsewhere legitimately inherits the backend default."""
    _, pos, _ = next(f for f in FIXTURES if f[0] == "G031")
    assert "G031" in rules_in(pos, RULE_FIXTURE_PATHS["G031"])
    assert "G031" in rules_in(
        pos, "deeplearning4j_tpu/embedding/_graftlint_fixture.py")
    assert "G031" not in rules_in(pos)  # parallel/ default: out of scope
    assert "G031" not in rules_in(pos, "deeplearning4j_tpu/nn/x.py")


def test_g032_blessed_dirs_and_registry_carveout():
    """gradientcheck/'s finite differences deliberately run f64 (tests
    enable x64) and stay silent; the np.float64-constructor half is
    device-dirs only (host analytics keep their f64); a name->dtype
    registry dict is declarative, not drift."""
    _, pos, neg = next(f for f in FIXTURES if f[0] == "G032")
    assert "G032" in rules_in(pos)  # parallel/ is a device dir
    assert "G032" not in rules_in(
        pos, "deeplearning4j_tpu/gradientcheck/finite_diff.py")
    np_ctor = "def f():\n    return np.float64(3.0)\n"
    assert "G032" in rules_in(np_ctor, "deeplearning4j_tpu/ops/x.py")
    assert "G032" not in rules_in(
        np_ctor, "deeplearning4j_tpu/clustering/kmeans.py")
    registry = '_DTYPES = {"float64": jnp.float64}\n'
    assert "G032" not in rules_in(registry)


def test_g033_blessed_quantize_helpers_are_exempt():
    """ops/decode_attention.py IS where maxabs/127 lives — the rule
    exists so there is exactly ONE spelling of the scale math."""
    _, pos, _ = next(f for f in FIXTURES if f[0] == "G033")
    assert "G033" in rules_in(pos)
    assert "G033" in rules_in(pos, "deeplearning4j_tpu/serving/engine.py")
    assert "G033" not in rules_in(
        pos, "deeplearning4j_tpu/ops/decode_attention.py")
    # integer 128 is the lane tile (G016's constant), never quant math
    lane = "def f(x):\n    return x * 128\n"
    assert "G033" not in rules_in(lane)


def test_g034_blessed_dtype_policy_paths_are_exempt():
    """reshard/ and the two checkpoint formats OWN the dtype policy;
    the same wholesale tree cast flags anywhere else."""
    _, pos, _ = next(f for f in FIXTURES if f[0] == "G034")
    assert "G034" in rules_in(pos)
    assert "G034" in rules_in(pos, "deeplearning4j_tpu/nn/multilayer.py")
    assert "G034" not in rules_in(
        pos, "deeplearning4j_tpu/reshard/executor.py")
    assert "G034" not in rules_in(
        pos, "deeplearning4j_tpu/util/orbax_checkpoint.py")
    assert "G034" not in rules_in(
        pos, "deeplearning4j_tpu/util/model_serializer.py")


def test_g014_retry_loop_scoped_to_distributed():
    """The uncapped-retry half of G014 applies to distributed/ only
    (the elastic rejoin path); a bounded Backoff loop stays clean."""
    uncapped = ("def retry():\n"
                "    while True:\n"
                "        try:\n"
                "            connect()\n"
                "            break\n"
                "        except OSError:\n"
                "            time.sleep(0.1)\n")
    capped = ("def retry(backoff):\n"
              "    while True:\n"
              "        try:\n"
              "            connect()\n"
              "            break\n"
              "        except OSError:\n"
              "            if not backoff.pause():\n"
              "                raise\n")
    dist = "deeplearning4j_tpu/distributed/x.py"
    assert "G014" in rules_in(uncapped, dist)
    assert "G014" not in rules_in(capped, dist)
    assert "G014" not in rules_in(uncapped,
                                  "deeplearning4j_tpu/parallel/x.py")


def test_g002_scoped_to_hot_paths():
    src = "def step(x):\n    return np.asarray(x)\n"
    assert "G002" in rules_in(src, "deeplearning4j_tpu/ops/x.py")
    assert "G002" in rules_in(src, "deeplearning4j_tpu/nn/layers/x.py")
    assert "G002" not in rules_in(src, "deeplearning4j_tpu/datasets/x.py")


def test_g011_scoped_to_spmd_dirs():
    src = "def f():\n    t = time.time()\n    return jnp.full((2,), t)\n"
    assert "G011" in rules_in(src, "deeplearning4j_tpu/distributed/x.py")
    assert "G011" in rules_in(src, "deeplearning4j_tpu/nn/layers/x.py")
    assert "G011" not in rules_in(src, "deeplearning4j_tpu/ops/x.py")


def test_g007_exempts_compat_itself():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert "G007" in rules_in(src, "deeplearning4j_tpu/parallel/x.py")
    assert "G007" not in rules_in(src, "deeplearning4j_tpu/util/compat.py")


def test_g009_exempts_bootstrap_itself():
    src = ("def up():\n"
           "    jax.distributed.initialize()\n"
           'ENV = "DL4J_TPU_NUM_PROCESSES"\n')
    assert "G009" in rules_in(src, "deeplearning4j_tpu/parallel/x.py")
    assert "G009" not in rules_in(
        src, "deeplearning4j_tpu/distributed/bootstrap.py")


def test_inline_suppression_and_fixit():
    src = """\
def g(x):
    return x


def f(x):
    return jax.jit(g)(x)
"""
    findings = lint_source(_PRELUDE + src, FIXTURE_PATH)
    assert [f.rule for f in findings] == ["G005"]
    assert findings[0].fixit  # every rule ships a fix-it message
    suppressed = src.replace("jax.jit(g)(x)",
                             "jax.jit(g)(x)  # graftlint: disable=G005")
    assert not lint_source(_PRELUDE + suppressed, FIXTURE_PATH)


def test_baseline_roundtrip(tmp_path):
    f1 = Finding("G005", "a.py", 3, 0, "m", "f", "jax.jit(g)(x)")
    f2 = Finding("G002", "b.py", 9, 0, "m", "f", "np.asarray(x)")
    from deeplearning4j_tpu.analysis import write_baseline
    path = tmp_path / "base.json"
    write_baseline(str(path), [f1])
    base = load_baseline(str(path))
    new, old = split_baselined([f1, f2], base)
    assert old == [f1] and new == [f2]
    # the key survives line-number drift
    assert Finding("G005", "a.py", 77, 4, "m", "f",
                   "jax.jit(g)(x)").key in base


def test_syntax_error_is_a_finding():
    assert rules_in("def f(:\n") == {"G000"}


# ----------------------------------------------- the package gate

def test_package_is_lint_clean():
    baseline = load_baseline(BASELINE)
    assert len(baseline) <= 5, "baseline must shrink, never grow"
    new, _old = lint_report([PKG], baseline, root=ROOT)
    assert not new, "new graftlint findings:\n" + "\n".join(
        f.format() for f in new)


# ----------------------------------------------- stage 2: jaxpr audit

from deeplearning4j_tpu.analysis import jaxpr_audit  # noqa: E402


@pytest.mark.parametrize("entry", jaxpr_audit.entry_names())
def test_jaxpr_audit_entry(entry):
    findings, counts = jaxpr_audit.audit([entry])
    assert not findings, "\n".join(f.format() for f in findings)
    assert counts[entry] > 0


def test_budget_catches_bloat(tmp_path):
    bad = tmp_path / "budget.json"
    bad.write_text(json.dumps({"ops": {"fused_layer_norm": 1}}))
    findings, _ = jaxpr_audit.audit(["fused_layer_norm"],
                                    budget_path=str(bad))
    assert [f.rule for f in findings] == ["J002"]


def test_every_finding_carries_its_stage_label(tmp_path):
    """--json consumers (benchdiff-style tooling) filter on the `stage`
    field, so AST findings AND budget trips must both carry it."""
    src = "def g(x):\n    return x\n\n\ndef f(x):\n    return jax.jit(g)(x)\n"
    findings = lint_source(_PRELUDE + src, FIXTURE_PATH)
    assert findings and all(f.stage == "ast" for f in findings)
    assert findings[0].to_json()["stage"] == "ast"
    bad = tmp_path / "budget.json"
    bad.write_text(json.dumps({"ops": {"fused_layer_norm": 1}}))
    jfindings, _ = jaxpr_audit.audit(["fused_layer_norm"],
                                     budget_path=str(bad))
    assert [f.stage for f in jfindings] == ["jaxpr"]
    # the stage is display metadata, not identity: baseline keys ignore it
    assert Finding("G005", "a.py", 3, 0, "m", "f", "s").key == \
        Finding("G005", "a.py", 3, 0, "m", "f", "s", stage="ast").key


def test_missing_budget_is_a_finding(tmp_path):
    empty = tmp_path / "budget.json"
    empty.write_text(json.dumps({"ops": {}}))
    findings, _ = jaxpr_audit.audit(["fused_layer_norm"],
                                    budget_path=str(empty))
    assert [f.rule for f in findings] == ["J004"]


def test_forbidden_primitive_detection():
    import jax

    def leaky(x):
        return jax.device_put(x)

    closed = jax.make_jaxpr(leaky)(jax.ShapeDtypeStruct((2,), "float32"))
    prims = {e.primitive.name for e in jaxpr_audit._iter_eqns(closed.jaxpr)}
    assert prims & jaxpr_audit.FORBIDDEN_PRIMITIVES


# ----------------------------------------------- CLI

def _run_cli(*argv):
    return subprocess.run([sys.executable, CLI, *argv], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)


def test_cli_check_clean_tree_exits_zero():
    proc = _run_cli("--check", "deeplearning4j_tpu")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_check_fails_on_findings_and_emits_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n\ndef f(x):\n    return jax.jit(x)(1)\n")
    proc = _run_cli("--check", str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "G005" in proc.stdout
    proc = _run_cli("--check", "--json", str(bad))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["findings"][0]["rule"] == "G005"
    assert payload["findings"][0]["fixit"]
    assert payload["findings"][0]["stage"] == "ast"


def _poisoned_jax_env(tmp_path):
    shim = tmp_path / "shim"
    shim.mkdir()
    (shim / "jax.py").write_text(
        "raise ImportError('graftlint host-only stage imported jax')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{shim}{os.pathsep}{ROOT}"
    return env


def test_ast_stage_completes_without_importing_jax(tmp_path):
    """The pre-commit fast path: --stage ast (G001-G014 included) must
    never import jax. A poisoned `jax` module on PYTHONPATH turns any
    violation into a hard failure."""
    proc = subprocess.run(
        [sys.executable, CLI, "--check", "deeplearning4j_tpu"],
        cwd=ROOT, env=_poisoned_jax_env(tmp_path),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ----------------------------------------------- stage 4 (ISSUE 17)

def test_cli_concurrency_stage_gate():
    """The tier-1 concurrency gate: the package sweeps clean under
    --stage concurrency (G025-G028 + the lock-order audit against the
    frozen edge set) with a non-empty lock graph."""
    proc = _run_cli("--check", "--stage", "concurrency", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["lock_order_edges"], "frozen lock graph is empty"


def test_cli_concurrency_findings_carry_stage_label(tmp_path):
    bad = tmp_path / "racy.py"
    bad.write_text(
        "import threading\n\n\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self.n = 0\n"
        "        self._t = None\n\n"
        "    def start(self):\n"
        "        def loop():\n"
        "            self.n += 1\n\n"
        "        self._t = threading.Thread(target=loop, daemon=True)\n"
        "        self._t.start()\n\n"
        "    def stop(self):\n"
        "        self._t.join()\n\n"
        "    def describe(self):\n"
        "        return self.n\n")
    proc = _run_cli("--check", "--stage", "concurrency", "--json",
                    str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    rules = {f["rule"] for f in payload["findings"]}
    assert "G025" in rules
    assert all(f["stage"] == "concurrency"
               for f in payload["findings"])


def test_concurrency_stage_completes_without_importing_jax(tmp_path):
    """Stage 4 is host-only analysis (AST rules + lock graph): it must
    run with jax poisoned, exactly like stage 1."""
    proc = subprocess.run(
        [sys.executable, CLI, "--check", "--stage", "concurrency",
         "deeplearning4j_tpu"],
        cwd=ROOT, env=_poisoned_jax_env(tmp_path),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rules_prints_per_stage_inventory(tmp_path):
    """--rules is the one-stop rule inventory: every id every stage can
    emit, grouped by stage — and it runs jax-free (doc lookups only)."""
    proc = subprocess.run(
        [sys.executable, CLI, "--rules"],
        cwd=ROOT, env=_poisoned_jax_env(tmp_path),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for stage in ("ast", "jaxpr", "spmd", "concurrency", "precision"):
        assert f"stage {stage}:" in proc.stdout
    for rid in ("G001", "G024", "G025", "G028", "G031", "G034",
                "J001", "J004", "C001", "C003", "D001", "D003",
                "P001", "P005", "PB01"):
        assert rid in proc.stdout, f"--rules missing {rid}"
