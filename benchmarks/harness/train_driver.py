"""Traffic kind `train`: a whole training job through the program's own
entry, `ComputationGraph.fit(iterator)`, fed by its prefetch pipeline.

Set-up builds one net, gives it the benchmark's seeded weights, drives
its first three optimizer steps through that same call and feed (each
batch new rows), reads what `correct` compares, and hands the same net
to the window. The window feeds new batches until the time is up; the
clock stops when the last step's parameters are ready. Then the net is
freed and the plain reference follows the same three steps. The net, its
seeded weights, the reference and the names of the leaves compared are
the configuration's family's (`spec.family_of`).
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from harness import compare, device, spec, weights

CHECK_STEPS = 3


class StepFeed:
    """The seeded token stream as a DataSetIterator: [batch, seq] ids
    uniform over the vocabulary with next-token labels, every batch new
    rows. It yields `limit` more batches, or batches until `deadline`."""

    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int):
        self._rng = np.random.default_rng([int(seed), 7])
        self.shape = (batch, seq_len + 1)
        self.vocab = vocab
        self.limit = 0
        self.deadline = None
        self.kept = []          # the first batches, for the reference
        self.keep = 0
        self.made = 0
        self._preprocessor = None

    def has_next(self) -> bool:
        if self.deadline is not None:
            return time.perf_counter() < self.deadline
        return self.limit > 0

    def next(self, num=None):
        from deeplearning4j_tpu.datasets.api import DataSet

        ids = self._rng.integers(0, self.vocab, self.shape, dtype=np.int32)
        tokens, labels = ids[:, :-1], ids[:, 1:]
        if len(self.kept) < self.keep:
            self.kept.append((tokens.copy(), labels.copy()))
        self.limit -= 1
        self.made += 1
        return DataSet(np.ascontiguousarray(tokens),
                       np.ascontiguousarray(labels))

    def reset(self) -> None:
        pass                    # a stream: fit's per-epoch reset rewinds nothing

    def batch(self) -> int:
        return self.shape[0]

    def async_supported(self) -> bool:
        return True

    def __iter__(self):
        return self

    def __next__(self):
        if not self.has_next():
            raise StopIteration
        return self.next()


class Pace:
    """An IterationListener that keeps at most `depth` steps in flight and
    stamps each step's completion: without it the host would enqueue
    steps far past the window's end."""

    def __init__(self, depth: int = 2):
        self.depth = depth
        self.inflight = deque()
        self.done = []          # perf_counter at which step k was complete

    def iteration_done(self, model, iteration: int) -> None:
        self.inflight.append(getattr(model, "_score_raw", None))
        while len(self.inflight) > self.depth:
            self._wait(self.inflight.popleft())

    def _wait(self, raw) -> None:
        if hasattr(raw, "block_until_ready"):
            raw.block_until_ready()
        self.done.append(time.perf_counter())

    def drain(self) -> None:
        while self.inflight:
            self._wait(self.inflight.popleft())


def proj_key(seed: int):
    """The key of the fixed +-1 vectors both sides project the first
    gradient on."""
    import jax

    return jax.random.fold_in(weights.seed_key(seed), 0x70726f6a)


def program_readings(family, net, feed: StepFeed, seed: int, dims: dict,
                     hp: dict):
    """Drive the net's first CHECK_STEPS steps through fit(feed) and read
    each loss, the first gradient's norms out of Adam's state after step
    1, and the norms of the parameters' change after the last."""
    import jax

    b1 = hp["adam_b1"]
    def first_grad(opt, params, k):
        g = jax.tree.map(lambda m: m / (1.0 - b1),
                         family.first_moment_tree(opt, params))
        return (family.program_sq_norms(g, dims),
                family.program_projections(g, dims, k))

    grad_sq = jax.jit(first_grad)
    change_sq = jax.jit(lambda params, k: family.program_sq_norms(
        jax.tree.map(lambda a, b: a.astype("float32") - b, params,
                     family.seeded_program_tree(k, dims, params)), dims))
    feed.keep = CHECK_STEPS
    losses, g1 = [], None
    for k in range(CHECK_STEPS):
        feed.limit = 1
        net.fit(feed)
        losses.append(float(net.score_value))
        if k == 0:
            g1, gp = jax.tree.map(np.asarray, grad_sq(
                net.opt_state, net.params, proj_key(seed)))
    ch = jax.tree.map(np.asarray, change_sq(net.params, weights.seed_key(seed)))
    return {"losses": losses, "grad_sq": g1, "grad_proj": gp, "change_sq": ch}


def run(ctx) -> dict:
    """ctx: harness.run.Context. Returns the pieces of the result line."""
    import jax

    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    family = spec.family_of(config)
    dims = family.dims_of(config)
    hp = config["training"]
    B, T = int(traffic["batch"]), int(traffic["seq_len"])

    net = family.training_net(config, seed, dims)
    pace = Pace(depth=int(traffic.get("steps_in_flight", 2)))
    net.set_listeners(pace)
    feed = StepFeed(seed, B, T, dims["V"])
    prog = program_readings(family, net, feed, seed, dims, hp)
    for _ in range(int(traffic.get("warm_steps", 2))):
        feed.limit = 1
        net.fit(feed)
    pace.drain()
    jax.block_until_ready(net.params)
    pace.done.clear()

    trace = ctx.start_trace()
    t0 = time.perf_counter()
    ctx.window_opens(t0, trace)
    steps_before = net.iteration_count
    feed.deadline = t0 + ctx.seconds
    net.fit(feed)
    pace.drain()
    jax.block_until_ready(net.params)
    t1 = time.perf_counter()
    feed.deadline = None
    steps = net.iteration_count - steps_before
    last_loss = float(net.score_value)

    peak = device.memory_peak_bytes()
    traced = ctx.finish_trace(trace)
    # free the program's state before the reference takes the chip
    net.params = net.opt_state = net.state = None
    net._train_step = None
    del net
    ref = family.reference_readings(seed, dims, hp, feed.kept, proj_key(seed))
    checks = compare.train_checks(prog, ref, ctx.limits)
    checks["steps_finite"] = [0.0 if np.isfinite(last_loss) else 1.0, 0]

    window = t1 - t0
    return {
        "attempted": steps, "failed": 0, "checks": checks,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": steps * B * T / window},
        "facts": {"window": (t0, t1), "steps": steps, "batch": B,
                  "seq_len": T, "dims": dims, "step_done": list(pace.done),
                  "flops_per_token": family.train_flops_per_token(dims, T),
                  "traced": traced},
    }
