"""Benchmark harness — prints ONE JSON line per BASELINE.json metric.

Covers all five BASELINE.json configs (BASELINE.md):
  1. lenet       — LeNet-5/MNIST images/sec/chip through the fit-path step
  2. vgg16       — VGG-16/CIFAR-10 images/sec/chip (DAG API)
  3. word2vec    — skip-gram negative sampling words/sec (text8-like corpus)
  4. resnet_dp   — ResNet-20 allreduce-DP vs parameter-averaging speedup
                   (virtual 8-device CPU mesh; ICI analogue of BASELINE #4)
  5. transformer — 6-layer Transformer-LM step time -> tokens/sec + MFU
                   (north star: >=30% MFU)

`python bench.py` runs every mode, each in its own subprocess so jax
backend/platform choices stay isolated (resnet_dp forces the virtual CPU
mesh; the rest use the default backend — the real TPU chip under the
driver). `python bench.py <mode>` runs one mode inline.

The reference publishes no numbers (BASELINE.md), so each `vs_baseline` is
the ratio against the nominal anchor constants below; anchors are re-based
to the first real-TPU measurements as rounds land them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# Anchors: lenet/vgg16/word2vec were measured on the real v5e chip
# (round 2, 2026-07) and act as regression guards; resnet_dp's natural
# baseline is parity (1.0) and transformer's is the >=30% MFU north star.
TARGETS = {
    "lenet": 1700000.0,      # images/sec/chip (r2 measured: 1.78M, scanned
                             # steady-state; per-step Python dispatch caps a
                             # naive loop far lower)
    "vgg16": 80000.0,        # images/sec/chip — ~0.7x the r5 healthy-
                             # window rate (116k after the one-pass BN
                             # stats + tiled maxpool backward; 40.7-116k
                             # across r5 windows was chip-state spread).
                             # Throttled windows scale the gate via the
                             # conv probe (gate_scale) instead of false-
                             # flagging.
    "word2vec": 800000.0,    # words/sec — ~0.9x the r5 oversample-2
                             # shared-negatives rate (831k measured at a
                             # 175 TF/s window; the oversample costs
                             # ~12% of the r4 os=1 rate and buys the
                             # 0.98x-host quality ratio). The old 600k
                             # floor let the r3 driver window's 699k
                             # pass silently (VERDICT r3 #3); throttled
                             # windows now scale the gate via the matmul
                             # probe instead of false-flagging.
    "resnet_dp": 1.0,        # allreduce/param-avg speedup (>=1 expected)
    "moe": 1250000.0,        # routed-MoE tokens/sec (r5 measured: 1.52M
                             # best / 1.46M typical interleaved at the
                             # matched 2-head flagship config = 0.765x
                             # the same-window dense line. r5 gains:
                             # MXU-friendly float routing metadata
                             # (tri-matmul prefix counts; no s32
                             # cumsum/pred bands) and the lane-rotated
                             # flat-optimizer layout (the [256,8] router
                             # leaves made XLA relayout the whole 19M-
                             # param flat vector, 2.8 ms/step))
    "transformer": 0.30,     # MFU fraction (north star >=30%; r5 session
                             # measured 0.530 clean / 0.530 masked /
                             # 0.481 masked+dropout at seq 512, 0.457 at
                             # the 4-head/D=64 config, ~0.59+ at seq
                             # 4096 — the anchor stays at the north star
                             # so the gate flags a fall below it, with
                             # gate_scale absorbing chip throttle)
}

def _peak_flops(device):
    """MFU denominator from the one published-peaks table
    (telemetry/costbook.DEVICE_PEAKS): None off-TPU, raises on a TPU
    kind the table does not know."""
    from deeplearning4j_tpu.telemetry.costbook import peak_flops

    return peak_flops(device)


REGRESSION_FLOOR = 0.9  # anchored metric below 0.9x its anchor fails loudly


def _recorder():
    """Process-global telemetry recorder (telemetry/recorder.py). A
    NullRecorder no-op unless DL4J_TPU_TELEMETRY names a log file —
    _run_all sets it so every mode subprocess appends to one shared
    JSONL log alongside the stdout metric lines."""
    from deeplearning4j_tpu.telemetry import get_default

    return get_default()

# Best chip-probe ceilings observed across rounds (r2-r5): the shared-
# tenancy chip swings 2x on minute timescales (r5 measured the SAME VGG
# binary at 40.7k and 116k img/s nine minutes apart), so an anchored
# metric's regression gate is scaled by (current probe / healthy probe)
# for the probe that matches the mode's resource — conv throughput for
# the conv nets (a matmul probe under-predicts conv degradation: r4's
# driver window read matmul 0.77x healthy while VGG ran 0.45x), matmul
# for the matmul-dominated modes. A below-scaled-anchor value means
# "regression even granting this chip state" and retries have already
# been spent (see _defended_measure).
HEALTHY_MATMUL_TFLOPS = 191.0
HEALTHY_CONV_TFLOPS = 190.0

# word2vec device path must keep >= this fraction of the host (reference-
# semantics) path's embedding quality on the shared sub-corpus. r5 closed
# the r4 gap (0.87): the residual came from (a) shared-negative VARIANCE
# — fixed by drawing oversample*K shared negatives weighted K/M, which
# keeps the per-pair SGNS objective expectation exactly — and (b) update
# GRANULARITY (8192-token batched updates vs the host's per-window) —
# the default pipeline config now updates every 1024 tokens. Measured
# ratio at the defaults: 0.977 (deterministic seed); the unshared and
# fine-granularity variants reach >= 1.0x host.
W2V_QUALITY_RATIO = 0.95

# routed MoE must hold >= this fraction of the SAME-WINDOW dense line
# (top-2/8 at capacity 1.25; r5 measured 0.737-0.765)
MOE_RATIO_FLOOR = 0.65


def _emit(mode: str, value: float, unit: str, **extra) -> None:
    line = {
        "metric": mode if "metric" not in extra else extra.pop("metric"),
        "value": round(float(value), 4),
        "unit": unit,
        "vs_baseline": round(float(value) / TARGETS[mode], 4),
    }
    line.update(extra)
    # the regression gate VERDICT r2 asked for, chip-state-scaled in r5:
    # `gate_scale` (from _defended_measure) shrinks the floor by the
    # measured probe/healthy ratio so the flag means "below anchor even
    # granting the current chip state" — a throttled-window capture no
    # longer poses as a code regression (VERDICT r4 #1). Printed ONCE
    # (the json line carries the flag; no duplicate stderr echo at the
    # parent level — r4's artifact tail lost a metric to the echoes).
    if line["vs_baseline"] < REGRESSION_FLOOR * line.get("gate_scale", 1.0):
        line["regression"] = True
        sys.stderr.write(
            f"REGRESSION: {line['metric']} = {line['value']} is "
            f"{line['vs_baseline']:.2f}x its anchor "
            f"({TARGETS[mode]})\n")
    print(json.dumps(line), flush=True)
    _recorder().metric(line)


def _emit_info(line: dict) -> None:
    """Print an informational (un-anchored) metric line AND record it as
    a telemetry `metric` event — every bench mode leaves both a stdout
    detail line and a truncation-proof telemetry record."""
    print(json.dumps(line), flush=True)
    _recorder().metric(line)


def _sync(carry) -> float:
    """Force execution of the whole chained computation by pulling one
    scalar of the final state to host."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree.leaves(carry)[0]
    return float(jnp.ravel(leaf.astype(jnp.float32))[0])


def _time_net_steps(net, ds, steps: int) -> float:
    """Seconds per training step through the STOCK fit path.

    `net.fit_scanned` stages the batch on device and runs each epoch as
    one jitted scan dispatch — the fit()-family API users call, not a
    bench-only harness. The slope between epochs=steps and 3*steps cancels
    the fixed dispatch/readback round-trip latency (the explicit scalar
    readback in _sync is the wait-for-the-device).
    """
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    def timed(n) -> float:
        t0 = time.perf_counter()
        net.fit_scanned(ListDataSetIterator([ds]), epochs=n)
        _sync(net.params)
        return time.perf_counter() - t0

    timed(steps)       # compile
    timed(3 * steps)   # compile
    # host-clock jitter; min-of-3 is the robust estimator
    for attempt in range(3):
        t1 = min(timed(steps) for _ in range(3))
        t3 = min(timed(3 * steps) for _ in range(3))
        if t3 - t1 > 0.05 * t3:  # slope must dominate jitter
            return (t3 - t1) / (2 * steps)
    # degenerate slope even after retries (heavy contention): report the
    # latency-inclusive upper bound rather than a fabricated number
    return t3 / (3 * steps)


_PROBE_CACHE = {}


def _measure_matmul_tflops():
    """Achievable dense bf16 matmul FLOP/s right now (slope over fori_loop
    lengths; cancels fixed latency). Returns None off-TPU. The jitted
    probe fns are cached — _defended_measure probes up to 6x per mode and
    re-jitting would burn chip time inside the window being probed."""
    import functools

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return None
    n = 8192
    a = jnp.asarray(np.random.default_rng(0).random((n, n)), jnp.bfloat16)

    def many(a, K):
        def body(i, c):
            return (a @ c) * jnp.bfloat16(1e-3)
        return jax.lax.fori_loop(0, K, body, a)

    if "matmul" not in _PROBE_CACHE:
        _PROBE_CACHE["matmul"] = {
            K: jax.jit(functools.partial(many, K=K)) for K in (10, 40)}
    fns = _PROBE_CACHE["matmul"]

    def timed(K):
        f = fns[K]
        _sync(f(a))  # compile+sync (cached)
        t0 = time.perf_counter()
        _sync(f(a))
        return time.perf_counter() - t0

    t1 = min(timed(10) for _ in range(2))
    t2 = min(timed(40) for _ in range(2))
    per = (t2 - t1) / 30
    if per <= 0:
        return None  # jitter swamped the slope — omit rather than corrupt
    return 2 * n**3 / per


def _measure_conv_tflops():
    """Achievable 3x3-conv bf16 FLOP/s right now (the VGG/LeNet resource:
    conv throughput degrades ~2x under tenancy windows where the matmul
    probe only drops 25% — r5 measured both). Returns None off-TPU."""
    import functools

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return None
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((256, 32, 32, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.random((3, 3, 128, 128)) * 0.01, jnp.bfloat16)

    def many(x, K):
        def body(i, c):
            y = jax.lax.conv_general_dilated(
                c, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return y * jnp.bfloat16(0.01)
        return jax.lax.fori_loop(0, K, body, x)

    # ~0.5 ms/iter: the slope needs hundreds of iters to dominate the
    # host-clock jitter (a 30-iter slope returned 406 TF/s — 2x the chip's
    # physical peak — and defeated the gate scaling it feeds)
    if "conv" not in _PROBE_CACHE:
        _PROBE_CACHE["conv"] = {
            K: jax.jit(functools.partial(many, K=K)) for K in (60, 240)}
    fns = _PROBE_CACHE["conv"]
    for f in fns.values():
        _sync(f(x))

    def timed(K):
        t0 = time.perf_counter()
        _sync(fns[K](x))
        return time.perf_counter() - t0

    t1 = min(timed(60) for _ in range(3))
    t2 = min(timed(240) for _ in range(3))
    per = (t2 - t1) / 180
    if per <= 0:
        return None
    return 2 * 256 * 32 * 32 * 128 * 3 * 3 * 128 / per


def _defended_measure(mode, measure, probe, healthy, n_attempts=3,
                      probe_key="chip_matmul_tflops"):
    """Measure with the bench defending itself (VERDICT r4 #1).

    Probes the mode's matched resource BEFORE and AFTER the timed window;
    when the result lands below the anchor gate AND the window read
    throttled, waits and re-measures (compiled state reused, so retries
    are cheap). Emits every attempt, the strongest probe reading, and a
    `gate_scale` = probe/healthy so _emit's flag separates "chip was
    slow" from "code got slower". Returns (best_value, extra_fields).
    """
    floor = REGRESSION_FLOOR * TARGETS[mode]
    attempts = []
    for i in range(n_attempts):
        pre = probe()
        v = measure()
        post = probe()
        rec = {"value": round(v, 1)}
        # a probe can itself catch a bad window — clip to the physical
        # ceiling and average pre/post so a window that degrades MID-
        # attempt (r5 saw 165 -> 41 TF/s inside one attempt) reads as
        # the state the measurement actually experienced
        reads = [min(p, healthy) for p in (pre, post) if p]
        chip = sum(reads) / len(reads) if reads else None
        if pre:
            rec["pre_tflops"] = round(pre / 1e12, 1)
        if post:
            rec["post_tflops"] = round(post / 1e12, 1)
        if chip:
            rec["chip"] = chip
        attempts.append(rec)
        # stop on a passing value; otherwise retry (chip-state probes can
        # read healthy while HOST-side contention drags the measurement —
        # r5 saw w2v at 0.81x with a 188 TF/s probe during a concurrent
        # test-suite run — so a below-floor value is always worth the
        # retries; the final flag is still gate_scale-adjusted)
        if v >= floor or not chip:
            break
        if i < n_attempts - 1:
            time.sleep(20)  # let transient tenancy contention drain
    best = max(attempts, key=lambda a: a["value"])
    chip_best = best.pop("chip", None)
    extra = {}
    if chip_best:
        extra[probe_key] = round(chip_best / 1e12, 1)
        extra["gate_scale"] = round(min(1.0, chip_best / healthy), 3)
    for a in attempts:
        a.pop("chip", None)
    if len(attempts) > 1:
        extra["attempts"] = attempts
    return best["value"], extra


# --------------------------------------------------------------------- modes

def bench_lenet() -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.lenet import lenet5

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    batch = 512 if on_tpu else 128
    net = lenet5(dtype="bfloat16" if on_tpu else "float32")
    net.init()
    rng = np.random.default_rng(0)
    x = rng.random((batch, 28, 28, 1), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    from deeplearning4j_tpu.datasets.api import DataSet

    ds = DataSet(x, y)
    # LeNet steps are ~40us on the chip: thousands of scanned steps
    # are needed for the slope to dominate host-clock jitter
    if on_tpu:
        value, extra = _defended_measure(
            "lenet", lambda: batch / _time_net_steps(net, ds, steps=2000),
            _measure_conv_tflops, HEALTHY_CONV_TFLOPS * 1e12,
            probe_key="chip_conv_tflops")
    else:
        value, extra = batch / _time_net_steps(net, ds, steps=4), {}
    _emit("lenet", value, "images/sec/chip",
          metric=f"lenet_mnist_images_per_sec_{backend}", **extra)


def bench_vgg16() -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.vgg import vgg16

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    batch = 256 if on_tpu else 16
    steps = 40 if on_tpu else 2
    net = vgg16(dtype="bfloat16" if on_tpu else "float32")
    net.init()
    rng = np.random.default_rng(0)
    x = rng.random((batch, 32, 32, 3), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    from deeplearning4j_tpu.datasets.api import DataSet

    ds = DataSet(x, y)
    # the r4 driver captured 48.4k on a throttled window vs 107k+ healthy
    # (same binary, VERDICT r4 #1) — the defended measurement probes CONV
    # throughput (the matched resource) before/after, retries throttled
    # windows, and scales the gate by chip state
    if on_tpu:
        value, extra = _defended_measure(
            "vgg16", lambda: batch / _time_net_steps(net, ds, steps=steps),
            _measure_conv_tflops, HEALTHY_CONV_TFLOPS * 1e12,
            probe_key="chip_conv_tflops")
    else:
        value, extra = batch / _time_net_steps(net, ds, steps=steps), {}
    _emit("vgg16", value, "images/sec/chip",
          metric=f"vgg16_cifar_images_per_sec_{backend}", **extra)


def _topic_corpus(rng, vocab, n_words, sent_len, n_topics=20):
    """Zipf-frequency corpus with PLANTED topic structure: word i belongs
    to topic i % n_topics; each sentence draws from one topic's word
    slice. Frequencies stay zipf-like (interleaved assignment), so the
    throughput character matches a plain zipf corpus, but embedding
    quality is measurable as within-vs-across-topic cosine separation."""
    words = [f"w{i}" for i in range(vocab)]
    per = vocab // n_topics
    zipf = 1.0 / np.arange(1, per + 1)
    p = zipf / zipf.sum()
    n_sents = n_words // sent_len
    topics = rng.integers(0, n_topics, n_sents)
    # word id = rank * n_topics + topic (interleaved)
    ranks = rng.choice(per, size=(n_sents, sent_len), p=p)
    ids = ranks * n_topics + topics[:, None]
    return [[words[j] for j in row] for row in ids]


def _topic_separation(w2v, n_topics=20, top_ranks=10):
    """quality = mean within-topic cosine - mean across-topic cosine over
    the most frequent words of each topic. Random vectors score ~0; a
    model that learned the planted structure scores well above it."""
    vecs = {}
    for t in range(n_topics):
        rows = []
        for r in range(top_ranks):
            v = w2v.word_vector(f"w{r * n_topics + t}")
            if v is not None:
                v = np.asarray(v, np.float64)
                n = np.linalg.norm(v)
                if n > 0:
                    rows.append(v / n)
        vecs[t] = np.stack(rows)
    within, across = [], []
    for t in range(n_topics):
        sim = vecs[t] @ vecs[t].T
        iu = np.triu_indices(len(vecs[t]), 1)
        within.append(sim[iu].mean())
        u = (t + 1) % n_topics
        across.append((vecs[t] @ vecs[u].T).mean())
    return float(np.mean(within) - np.mean(across))


def _quality_w2v(sents, **kw):
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    b = (Word2Vec.builder().layer_size(128).window_size(5)
         .min_word_frequency(1).negative_sample(5).epochs(1).seed(1))
    for k, v in kw.items():
        getattr(b, k)(v)
    w2v = b.build()
    w2v.build_vocab(sents)
    w2v.fit(sents)
    return w2v


def bench_word2vec() -> None:
    """Skip-gram NS words/sec on a synthetic topic-structured zipf corpus
    (text8 stand-in — zero-egress environment). Besides words/sec, emits
    an embedding QUALITY metric (VERDICT r2 #5): within-vs-across-topic
    cosine separation, compared against the unshared-negatives variant and
    the host (reference-semantics) path on the same sub-corpus/seed — so
    trust-region clipping + shared negatives cannot silently trade quality
    for speed.

    Config pairing (r5): the sub-corpus gate probes the PIPELINE DEFAULTS
    (512x2 chunks = 1024-token updates) — the coarse timed config
    (2048x4 = 8192) cannot be probed on a 200k-word sub-corpus because
    its update COUNT collapses (~24 updates trains nothing: measured
    0.24 separation, a corpus-size artifact, not a quality signal). The
    timed config's own quality on the full corpus is the `quality`
    field, which must also clear the host sub-corpus separation — a
    slide in the coarse path flags there."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    rng = np.random.default_rng(0)
    vocab, n_words, sent_len = 10000, 1_000_000, 25
    sents = _topic_corpus(rng, vocab, n_words, sent_len)

    w2v = (Word2Vec.builder().layer_size(128).window_size(5)
           .min_word_frequency(1).negative_sample(5)
           .use_device_pipeline(True).epochs(1).seed(1).build())
    # swept on v5e: 2048x4 runs ~2.3x faster than 1024x8 at the SAME
    # 8192-token update granularity (bigger vmapped chunks, fewer scan
    # steps — no change to the SGD semantics)
    w2v.pipeline_chunk, w2v.pipeline_group = 2048, 4
    w2v.build_vocab(sents)  # one-time host-side work, not training throughput
    w2v.fit(sents)          # warmup fit: compiles the epoch scan
    np.asarray(w2v.word_vector("w0"))  # DRAIN the warmup's device epoch —
    # without this the timed fit queues behind it and absorbs its runtime

    qual = {}

    def measure():
        t0 = time.perf_counter()
        w2v.fit(sents)      # timed fit: repack + full on-device epoch
        np.asarray(w2v.word_vector("w0"))  # force pending work to finish
        rate = n_words / (time.perf_counter() - t0)
        if "q" not in qual:
            # snapshot quality after the FIRST timed fit (2 epochs
            # total) so retry count never changes how trained the model
            # is when the cross-round quality reference is taken
            qual["q"] = _topic_separation(w2v)
        return rate

    import jax

    if jax.default_backend() == "tpu":
        value, extra0 = _defended_measure(
            "word2vec", measure, _measure_matmul_tflops,
            HEALTHY_MATMUL_TFLOPS * 1e12)
    else:
        value, extra0 = measure(), {}

    quality = qual["q"]
    # apples-to-apples quality comparison on a common sub-corpus: the
    # timed config vs unshared negatives vs the host path
    sub = sents[:8000]  # 200k words — host path tractable
    q_dev = _topic_separation(_quality_w2v(sub, use_device_pipeline=True))
    q_unshared = _topic_separation(
        _quality_w2v(sub, use_device_pipeline=True, share_negatives=False))
    q_host = _topic_separation(
        _quality_w2v(sub, use_device_pipeline=False))
    extra = dict(extra0)
    extra.update({
        "quality": round(quality, 4),
        "quality_subcorpus": round(q_dev, 4),
        "quality_subcorpus_unshared_negatives": round(q_unshared, 4),
        "quality_subcorpus_host_path": round(q_host, 4),
        # r3 #3 quality GATE: the fast shared-negatives device path must
        # stay within tolerance of reference (host-path) semantics on the
        # same seed/sub-corpus — a silent quality slide now flags
        "quality_gate_min_ratio": W2V_QUALITY_RATIO,
        "quality_ratio_vs_host": round(q_dev / max(q_host, 1e-9), 4),
    })
    if q_dev < W2V_QUALITY_RATIO * q_host:
        extra["regression"] = True
        sys.stderr.write(
            f"REGRESSION: word2vec device-path quality {q_dev:.4f} fell "
            f"below {W2V_QUALITY_RATIO}x the host path ({q_host:.4f})\n")
    _emit("word2vec", value, "words/sec",
          metric="word2vec_sgns_words_per_sec", **extra)


def _ab_ratio_stats(pairs):
    """Per-repeat A/B ratio statistics for the DP-speedup bench
    (VERDICT r5 #2: a single best-of ratio swung 0.96-1.21 between
    rounds with nothing to diagnose it). `pairs` is [(a_rate, b_rate)]
    from interleaved repeats; the reported value is the MEDIAN of the
    per-repeat ratios (host-contention spikes hit one repeat, not the
    middle of the distribution) and the spread is [min, max]."""
    ratios = sorted(a / b for a, b in pairs)
    n = len(ratios)
    median = (ratios[n // 2] if n % 2
              else 0.5 * (ratios[n // 2 - 1] + ratios[n // 2]))
    return {
        "ratio_median": round(median, 4),
        "ratio_spread": [round(ratios[0], 4), round(ratios[-1], 4)],
        "ratios": [round(r, 4) for r in ratios],
        "repeats": n,
    }


# bucket sizes the resnet_dp overlap arm sweeps: on the chatty virtual-
# CPU mesh finer buckets amortize per-collective dispatch AND expose the
# per-bucket dataflow XLA overlaps with backward/update compute; the
# largest candidate (1 GiB -> one bucket) doubles as the "fused single
# allreduce, manually issued" control
OVERLAP_BUCKET_SWEEP = (64 * 1024, 256 * 1024, 1 << 30)


def _probe_bucket_collectives(plan, mesh, rec, cap=8):
    """Micro-time each bucket's psum alone and emit a `bucket_reduce`
    telemetry span per bucket (index/bytes/leaves/seconds) — the
    per-bucket collective cost is invisible inside the fused step, and
    this is the record that explains a sweep winner."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.util.compat import shard_map

    def bucket_psum(v):
        return jax.lax.psum(v, "data")

    fn = jax.jit(shard_map(bucket_psum, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False,
                           axis_names={"data"}))
    for b in plan.buckets[:cap]:
        vec = jnp.zeros((b.n_elements,), jnp.float32)
        jax.block_until_ready(fn(vec))  # compile (one trace per size)
        with rec.span("bucket_reduce", mode="resnet_dp", bucket=b.index,
                      bytes=b.n_bytes, n_leaves=len(b.paths)):
            jax.block_until_ready(fn(vec))
    if len(plan.buckets) > cap:
        rec.event("span", name="bucket_reduce_capped", ok=True, seconds=0.0,
                  probed=cap, n_buckets=len(plan.buckets))


def bench_resnet_dp() -> None:
    """DP gradient reduction vs parameter-averaging steps/sec on an
    8-device mesh (BASELINE #4: the Spark param-averaging flagship vs
    the ICI redesign). THREE arms, interleaved per repeat so every side
    of every ratio sees the same host-contention window:

    - `overlap`   — bucketed async allreduce (parallel/overlap.py): the
      grads pytree partitioned into size-targeted buckets by reverse
      layer order, one psum per bucket interleaved with backward/update
      compute (ISSUE 7 tentpole; bucket size picked by the sweep below);
    - `allreduce` — the monolithic GSPMD formulation (the pre-r7
      headline arm, kept as the overlap-vs-monolithic control);
    - `paramavg`  — the reference's averaging semantics (SparkNet-style
      coarse sync, averaging_frequency=1 for like-for-like comms).

    The HEADLINE ratio is the repo's best DP path (overlap) vs paramavg
    — median of per-repeat ratios with spread; the monolithic-vs-
    paramavg and overlap-vs-monolithic medians ride the same line so
    the flip is attributable. The bucket-size sweep and the per-bucket
    collective spans land in telemetry."""
    from deeplearning4j_tpu.util.virtual_devices import ensure_cpu_devices

    n_dev = 8
    ensure_cpu_devices(n_dev)

    from deeplearning4j_tpu.models.resnet import resnet20
    from deeplearning4j_tpu.parallel.data_parallel import (
        DataParallelTrainer,
        ParameterAveragingTrainer,
    )
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    batch = 64
    n_batches = 8
    repeats = 5
    averaging_frequency = 1
    rng = np.random.default_rng(0)
    x = rng.random((batch, 32, 32, 3), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    ds = DataSet(x, y)

    def one_round(trainer):
        t0 = time.perf_counter()
        trainer.fit(ListDataSetIterator([ds] * n_batches))
        return n_batches / (time.perf_counter() - t0)

    mesh = make_mesh({"data": n_dev})
    rec = _recorder()

    # ---- bucket-size sweep: pick the overlap arm's bucket size on THIS
    # host's collective latency (one timed round per candidate)
    sweep = {}
    for bb in OVERLAP_BUCKET_SWEEP:
        net_c = resnet20()
        net_c.init()
        tr = DataParallelTrainer(net_c, mesh, overlap=bb)
        plan = net_c._overlap_plan
        with rec.span("compile", mode="resnet_dp", arm="overlap",
                      bucket_bytes=bb, n_buckets=len(plan.buckets)):
            tr.fit(ListDataSetIterator([ds] * 2))
        with rec.span("overlap_sweep", mode="resnet_dp",
                      bucket_bytes=bb, n_buckets=len(plan.buckets)) as sp:
            rate = one_round(tr)
            sp["steps_per_sec"] = round(rate, 3)
        sweep[bb] = (rate, tr, plan)
    best_bb = max(sweep, key=lambda k: sweep[k][0])
    trainer_ov, plan = sweep[best_bb][1], sweep[best_bb][2]
    _probe_bucket_collectives(plan, mesh, rec)

    net_ar = resnet20()
    net_ar.init()
    trainer_ar = DataParallelTrainer(net_ar, mesh)
    net_pa = resnet20()
    net_pa.init()
    trainer_pa = ParameterAveragingTrainer(
        net_pa, mesh, averaging_frequency=averaging_frequency)
    with rec.span("compile", mode="resnet_dp"):
        trainer_ar.fit(ListDataSetIterator([ds] * 2))  # warmup/compile
        trainer_pa.fit(ListDataSetIterator([ds] * 2))

    pairs = []          # (overlap, paramavg) — the headline
    pairs_mono = []     # (monolithic allreduce, paramavg)
    pairs_ovm = []      # (overlap, monolithic allreduce)
    for rep in range(repeats):
        with rec.span("ab_repeat", mode="resnet_dp", repeat=rep) as sp:
            c = one_round(trainer_ov)
            a = one_round(trainer_ar)
            b = one_round(trainer_pa)
            sp["overlap_steps_per_sec"] = round(c, 3)
            sp["allreduce_steps_per_sec"] = round(a, 3)
            sp["paramavg_steps_per_sec"] = round(b, 3)
        pairs.append((c, b))
        pairs_mono.append((a, b))
        pairs_ovm.append((c, a))

    stats = _ab_ratio_stats(pairs)
    stats_mono = _ab_ratio_stats(pairs_mono)
    stats_ovm = _ab_ratio_stats(pairs_ovm)
    _emit("resnet_dp", stats["ratio_median"], "x",
          metric="resnet20_dp_allreduce_vs_paramavg_speedup",
          dp_arm="overlap_bucketed",
          bucket_bytes=best_bb,
          n_buckets=len(plan.buckets),
          bucket_sweep_steps_per_sec={
              str(bb): round(sweep[bb][0], 3) for bb in sweep},
          overlap_steps_per_sec=round(
              sorted(c for c, _ in pairs)[repeats // 2], 3),
          allreduce_monolithic_steps_per_sec=round(
              sorted(a for a, _ in pairs_mono)[repeats // 2], 3),
          paramavg_steps_per_sec=round(
              sorted(b for _, b in pairs)[repeats // 2], 3),
          # the pre-r7 headline, kept diagnosable: the monolithic GSPMD
          # arm's ratio and the overlap arm's gain over it
          monolithic_allreduce_vs_paramavg=stats_mono["ratio_median"],
          monolithic_ratio_spread=stats_mono["ratio_spread"],
          overlap_vs_monolithic=stats_ovm["ratio_median"],
          overlap_vs_monolithic_spread=stats_ovm["ratio_spread"],
          # sync-cadence fields: the regime explains the ratio (a
          # paramavg that averaged every k>1 steps would do LESS
          # communication and should win on a chatty virtual-CPU mesh)
          allreduce_sync_every_steps=1,
          paramavg_averaging_frequency=averaging_frequency,
          # self-describing artifact: this ratio is measured on the virtual
          # CPU mesh (one real chip available), NOT an ICI measurement
          mesh=f"virtual-cpu-{n_dev}", **stats)


VOCAB_LM = 10000

# Dims of every Transformer-LM bench mode, keyed by MODES name — the ONE
# source read by both the bench bodies and the off-TPU compile smoke
# (tests/test_bench_modes.py). VERDICT r5 #1: `transformer_large` died
# only under driver capture because nothing off-TPU ever traced the
# d1024 model-build path; now every mode's REAL dims are dry-run (shape-
# level fwd+bwd) by tier-1, so a crashing mode fails pytest, not the
# round artifact.
LM_MODE_DIMS = {
    "transformer": dict(d_model=256, n_heads=2, d_ff=1024, seq=512,
                        batch=32, steps=40),
    "transformer_d64": dict(d_model=256, n_heads=4, d_ff=1024, seq=512,
                            batch=32, steps=40),
    "transformer_large": dict(d_model=1024, n_heads=8, d_ff=4096, seq=512,
                              batch=32, steps=5),
    "masked": dict(d_model=256, n_heads=2, d_ff=1024, seq=512, batch=32,
                   steps=40, masked=True),
    "dropout": dict(d_model=256, n_heads=2, d_ff=1024, seq=512, batch=32,
                    steps=40, masked=True, attention_dropout=0.1),
    "longcontext": dict(d_model=256, n_heads=2, d_ff=1024, seq=4096,
                        batch=4, steps=20),
    "longcontext_chunked": dict(d_model=256, n_heads=2, d_ff=1024,
                                seq=32768, batch=8, steps=2),
    "longcontext_chunked_dropout": dict(d_model=256, n_heads=2, d_ff=1024,
                                        seq=32768, batch=8, steps=2,
                                        masked=True, attention_dropout=0.1),
}


def lm_mode_net_ds(mode, *, force_tpu_dims=False):
    """(net, ds, cfg) for an LM bench mode: the stock transformer_lm at
    the mode's REAL (TPU) dims plus its token batch. Off-TPU the dims
    shrink to the CPU smoke config unless `force_tpu_dims` — the compile
    smoke passes True and only jax.eval_shape's the step, so the real
    dims cost nothing there."""
    import jax

    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.models.transformer import transformer_lm

    cfg = dict(LM_MODE_DIMS[mode])
    on_tpu = jax.default_backend() == "tpu"
    full_dims = on_tpu or force_tpu_dims
    if not full_dims:
        cfg.update(d_model=128, n_heads=2, d_ff=512, seq=128, batch=2,
                   steps=2)
    rng = np.random.default_rng(0)
    seq, batch = cfg["seq"], cfg["batch"]
    toks = np.asarray(rng.integers(0, VOCAB_LM, (batch, seq)), np.int32)
    kw = {}
    if cfg.get("masked"):
        # realistic NLP batch: lengths spread over [seq/2, seq]
        lengths = rng.integers(seq // 2, seq + 1, batch)
        mask = (np.arange(seq)[None, :] < lengths[:, None]).astype(
            np.float32)
        kw["features_mask"] = mask
        cfg["mean_valid_frac"] = round(float(mask.mean()), 3)
    ds = DataSet(toks, np.roll(toks, -1, axis=1), **kw)
    net = transformer_lm(
        vocab_size=VOCAB_LM, d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_layers=cfg.get("n_layers", 6),
        d_ff=cfg["d_ff"], max_length=seq,
        attention_dropout=cfg.get("attention_dropout"),
        dtype="bfloat16" if full_dims else "float32")
    net.init()
    return net, ds, cfg


def _mfu_fields(tokens_per_sec, cfg, peak):
    """MFU numbers for an LM line: `mfu` on the dense-accounted FLOPs
    convention and `mfu_executed` counting only what the causal kernels
    run (VERDICT r5 #4 — the seq-32k dense-accounted figure credits ~2x
    the executed attention work; both are emitted so the headline is
    comparable across conventions)."""
    from deeplearning4j_tpu.models.transformer import (
        transformer_flops_per_token,
        transformer_flops_per_token_executed,
    )

    flops_tok = transformer_flops_per_token(
        VOCAB_LM, cfg["d_model"], cfg.get("n_layers", 6), cfg["d_ff"],
        cfg["seq"])
    flops_exec = transformer_flops_per_token_executed(
        VOCAB_LM, cfg["d_model"], cfg.get("n_layers", 6), cfg["d_ff"],
        cfg["seq"])
    out = {"tokens_per_sec": round(tokens_per_sec, 1),
           "model_flops_per_token": flops_tok,
           "model_flops_per_token_executed": flops_exec}
    if peak:
        out["mfu"] = round(flops_tok * tokens_per_sec / peak, 4)
        out["mfu_executed"] = round(flops_exec * tokens_per_sec / peak, 4)
    return out


def _lm_harness(seq_tpu, batch_tpu, steps_tpu, seq_cpu=128, batch_cpu=2,
                steps_cpu=2):
    """Shared Transformer-LM bench scaffolding: backend-dependent dims and
    a token batch with next-token (sparse int) labels — the mcxent gather
    path (O(N) vs O(N*V) HBM traffic)."""
    import jax

    from deeplearning4j_tpu.datasets.api import DataSet

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    seq = seq_tpu if on_tpu else seq_cpu
    batch = batch_tpu if on_tpu else batch_cpu
    steps = steps_tpu if on_tpu else steps_cpu
    rng = np.random.default_rng(0)
    toks = np.asarray(rng.integers(0, VOCAB_LM, (batch, seq)), np.int32)
    ds = DataSet(toks, np.roll(toks, -1, axis=1))
    return backend, on_tpu, seq, batch, steps, ds


def bench_transformer() -> None:
    import jax

    backend = jax.default_backend()
    # 2 heads -> head_dim 128 (registry): fills the MXU contraction (r3:
    # D=64 ran flash at half rate) and unlocks the packed no-relayout
    # kernels
    net, ds, cfg = lm_mode_net_ds("transformer")
    sec = _time_net_steps(net, ds, steps=cfg["steps"])

    tokens_per_sec = cfg["batch"] * cfg["seq"] / sec
    peak = _peak_flops(jax.devices()[0])
    fields = _mfu_fields(tokens_per_sec, cfg, peak)
    if peak:
        extra = dict(fields)
        extra["peak_flops"] = peak
        extra.update(_chip_context(
            fields["model_flops_per_token"] * tokens_per_sec))
        _emit("transformer",
              fields["model_flops_per_token"] * tokens_per_sec / peak,
              "MFU fraction", metric=f"transformer_lm_mfu_{backend}",
              **extra)
    else:
        # no peak-FLOPs table entry (CPU smoke runs): report raw throughput
        _emit_info({
            "metric": f"transformer_lm_tokens_per_sec_{backend}",
            "value": round(tokens_per_sec, 1), "unit": "tokens/sec",
            "vs_baseline": None,  # no MFU anchor without a peak-FLOPs entry
            "model_flops_per_token": fields["model_flops_per_token"]})


def _chip_context(model_flops_per_sec):
    """Chip-state context fields for an MFU line: shared-tenancy
    throttling moves the achievable matmul ceiling by tens of percent
    between runs; mfu_vs_achievable factors the current ceiling out.
    Empty off-TPU (probe returns None)."""
    achieved = _measure_matmul_tflops()
    if not achieved:
        return {}
    return {"chip_matmul_tflops": round(achieved / 1e12, 1),
            "mfu_vs_achievable": round(model_flops_per_sec / achieved, 4)}


def _informational_lm_mode(mode, tag_fn, with_chip_context=False):
    """Shared body of the un-anchored LM variants (d64/large): build the
    stock transformer at the registry dims, time the fit path, and emit
    an informational line (vs_baseline None — compare to the anchored
    D=128 flagship mode). `tag_fn(d_model, heads)` names the metric from
    the ACTUAL dims so a CPU-fallback run can never file its number
    under the TPU config's name."""
    import jax

    backend = jax.default_backend()
    net, ds, cfg = lm_mode_net_ds(mode)
    d_model, heads = cfg["d_model"], cfg["n_heads"]
    sec = _time_net_steps(net, ds, steps=cfg["steps"])
    tokens_per_sec = cfg["batch"] * cfg["seq"] / sec
    peak = _peak_flops(jax.devices()[0])
    fields = _mfu_fields(tokens_per_sec, cfg, peak)
    extra = {"tokens_per_sec": round(tokens_per_sec, 1),
             "d_model": d_model, "n_heads": heads,
             "head_dim": d_model // heads}
    if peak:
        extra["mfu_executed"] = fields["mfu_executed"]
    if peak and with_chip_context:
        extra.update(_chip_context(
            fields["model_flops_per_token"] * tokens_per_sec))
    _emit_info({
        "metric": f"{tag_fn(d_model, heads)}_{backend}",
        "value": fields["mfu"] if peak else round(tokens_per_sec, 1),
        "unit": "MFU fraction" if peak else "tokens/sec",
        "vs_baseline": None,  # informational: no anchor
        **extra})


def bench_transformer_d64() -> None:
    """4-head / head_dim-64 LM step (informational, VERDICT r4 #5): the
    config users actually run — r3/r4 flash ran it at half rate through
    the flat layout's head relayouts; the r5 head-pair packed kernels
    put it on the no-relayout path. Compare `value` to the D=128
    transformer mode's MFU."""
    _informational_lm_mode(
        "transformer_d64", lambda d, h: f"transformer_lm_h{h}d{d // h}_mfu")


def bench_transformer_large() -> None:
    """d_model-1024 LM step (informational): the flagship d=256 config is
    HBM-bandwidth-limited past ~0.53 MFU (README step anatomy) — this
    mode measures the same stock fit path at a size users actually train
    (d 1024, 8 heads, d_ff 4096, ~90M params) where the matmuls amortise
    the streams. r5 session: 0.68 MFU at a 143-175 TF/s throttled window
    (~0.78-0.80 of the chip's achievable ceiling at capture time)."""
    import jax

    if jax.default_backend() != "tpu":
        # the CPU fallback dims would duplicate the d64 mode's smoke run
        # under a second metric name — off-TPU this mode has no content
        # (its d1024 model-build path IS still covered off-TPU: the
        # compile smoke in tests/test_bench_modes.py traces it at the
        # real dims)
        _emit_info({"metric": "transformer_lm_d1024_mfu",
                    "skipped": "TPU-only mode"})
        return
    _informational_lm_mode(
        "transformer_large", lambda d, h: f"transformer_lm_d{d}_mfu",
        with_chip_context=True)


def bench_transformer_masked() -> None:
    """Variable-length (padded+masked) LM training step: exercises the
    masked flash-attention path (VERDICT r2 #3 — masking is the
    reference's core long-sequence mechanism, setLayerMaskArrays). The
    MFU is accounted on the full padded [B, T] grid so the number is
    directly comparable to the unmasked transformer mode."""
    import jax

    backend = jax.default_backend()
    net, ds, cfg = lm_mode_net_ds("masked")
    sec = _time_net_steps(net, ds, steps=cfg["steps"])
    tokens_per_sec = cfg["batch"] * cfg["seq"] / sec
    peak = _peak_flops(jax.devices()[0])
    fields = _mfu_fields(tokens_per_sec, cfg, peak)
    line = {
        "metric": f"transformer_lm_masked_mfu_{backend}",
        "value": fields["mfu"] if peak else round(tokens_per_sec, 1),
        "unit": "MFU fraction" if peak else "tokens/sec",
        "vs_baseline": None,  # informational: compare to the unmasked mode
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mean_valid_frac": cfg["mean_valid_frac"],
    }
    if peak:
        line["mfu_executed"] = fields["mfu_executed"]
    _emit_info(line)


def bench_longcontext() -> None:
    """Long-sequence training step (seq 4096): exercises the fused Pallas
    flash-attention kernel (dense attention's [T,T] scores at this length
    are 32MB/head/layer each way) and remat — the long-context first-class
    requirement measured on hardware."""
    import jax

    backend = jax.default_backend()
    net, ds, cfg = lm_mode_net_ds("longcontext")
    sec = _time_net_steps(net, ds, steps=cfg["steps"])
    tokens_per_sec = cfg["batch"] * cfg["seq"] / sec
    peak = _peak_flops(jax.devices()[0])
    fields = _mfu_fields(tokens_per_sec, cfg, peak)
    line = {
        "metric": f"transformer_lm_seq{cfg['seq']}_tokens_per_sec_{backend}",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,  # informational: no anchor yet
    }
    line.update(fields)
    _emit_info(line)


def bench_longcontext_chunked() -> None:
    """seq-32768 training step (informational): T beyond the monolithic
    flash kernels' VMEM envelope runs chunked_flash_attention — the
    ring-attention hop primitive + lse merge serialized on one chip
    (ops/flash_attention.py). r5 session: 0.84 MFU at seq 32k / batch 8,
    0.91 at seq 64k / batch 4 — attention FLOPs dominate at these
    lengths and ride the MXU, so long-context is the repo's HIGHEST-MFU
    regime, not a degraded one. TPU-only (the CPU interpret path at 32k
    would run for hours)."""
    _chunked_lm_mode("longcontext_chunked", "transformer_lm_seq32768_mfu")


def _chunked_lm_mode(mode, skip_metric, extra_fields=None):
    """Shared body of the seq-32768 chunked modes (clean + dropout):
    TPU-only value run (the CPU interpret path at 32k would run for
    hours; tier-1 covers the build/trace path via the compile smoke).

    The HEADLINE is the EXECUTED-FLOPs MFU (VERDICT r5 #4): the chunked
    causal loop provably skips above-diagonal tile pairs, so
    `model_flops_per_token` counts the ~T(T+1)/2 causal pairs the
    kernels run, not the dense T^2 — the dense-accounted figure stays on
    the line as `mfu_dense_accounted` for cross-convention comparison."""
    import jax

    if jax.default_backend() != "tpu":
        _emit_info({"metric": skip_metric, "skipped": "TPU-only mode"})
        return
    backend = "tpu"
    net, ds, cfg = lm_mode_net_ds(mode)
    sec = _time_net_steps(net, ds, steps=cfg["steps"])
    tokens_per_sec = cfg["batch"] * cfg["seq"] / sec
    peak = _peak_flops(jax.devices()[0])
    fields = _mfu_fields(tokens_per_sec, cfg, peak)
    line = {
        "metric": f"{skip_metric}_{backend}",
        "value": (fields["mfu_executed"] if peak
                  else round(tokens_per_sec, 1)),
        "unit": "MFU fraction" if peak else "tokens/sec",
        "vs_baseline": None,  # informational: no anchor
        "attention": "chunked_flash",
        "flops_accounting": "causal_executed",
    }
    line.update(fields)
    # the honest count IS the headline count for the chunked causal path
    line["model_flops_per_token"] = fields["model_flops_per_token_executed"]
    if peak:
        line["mfu"] = fields["mfu_executed"]
        line["mfu_dense_accounted"] = fields["mfu"]
    line.update(extra_fields or {})
    _emit_info(line)


def bench_longcontext_chunked_dropout() -> None:
    """seq-32768 masked + attention-dropout training step (r6
    tentpole proof): the chunk-invariant in-kernel keep mask lets
    dropout ride the chunked flash path — the config that raised
    `chunked_unsupported_reason` in r5 now reports throughput. Compare
    to the clean seq-32768 mode: the target is near its MFU, not the
    0.48 the monolithic dropout mode bottomed at."""
    cfg = LM_MODE_DIMS["longcontext_chunked_dropout"]
    _chunked_lm_mode(
        "longcontext_chunked_dropout", "transformer_lm_seq32768_dropout_mfu",
        extra_fields={"attention_dropout": cfg["attention_dropout"]})


def bench_moe() -> None:
    """Mixture-of-Experts LM step throughput: the top-k gated expert FFN
    blocks from nn/layers/moe.py in the same 6-layer harness as the dense
    transformer bench. Emits the MoE MFU (useful-FLOPs accounting) and a
    SAME-WINDOW dense baseline + ratio (VERDICT r4 #3) — cross-subprocess
    ratios mixed different chip states, hiding the dispatch overhead
    inside tenancy noise."""
    from deeplearning4j_tpu.models.transformer import (
        transformer_lm,
        transformer_moe_flops_per_token,
        transformer_moe_lm,
    )

    backend, on_tpu, seq, batch, steps, ds = _lm_harness(512, 32, 40)
    # n_heads=2 matches the dense flagship (head_dim 128: packed
    # attention kernels + full MXU contraction) so the tokens/sec ratio
    # against the dense line compares the FF-vs-experts swap, not two
    # different attention configs
    net = transformer_moe_lm(vocab_size=VOCAB_LM, d_model=256, n_heads=2,
                             n_layers=6, n_experts=8, top_k=2,
                             d_expert_hidden=512, max_length=seq,
                             dtype="bfloat16" if on_tpu else "float32")
    net.init()
    if on_tpu:
        dense_net = transformer_lm(vocab_size=VOCAB_LM, d_model=256,
                                   n_heads=2, n_layers=6, d_ff=1024,
                                   max_length=seq, dtype="bfloat16")
        dense_net.init()
        pairs = []

        def measure():
            # dense twin timed back-to-back INSIDE each attempt, so the
            # ratio always compares the same chip window even when the
            # defended loop retries across windows
            v = batch * seq / _time_net_steps(net, ds, steps=steps)
            d = batch * seq / _time_net_steps(dense_net, ds, steps=steps)
            pairs.append((v, d))
            return v

        value, extra = _defended_measure(
            "moe", measure, _measure_matmul_tflops,
            HEALTHY_MATMUL_TFLOPS * 1e12)
        dense_tps = max(pairs, key=lambda p: p[0])[1]
        flops_tok = transformer_moe_flops_per_token(
            VOCAB_LM, 256, 6, 8, 2, 512, seq)
        import jax

        peak = _peak_flops(jax.devices()[0])
        if peak:
            extra["mfu"] = round(flops_tok * value / peak, 4)
        extra["dense_same_window_tokens_per_sec"] = round(dense_tps, 1)
        ratio = value / dense_tps
        extra["vs_dense_ratio"] = round(ratio, 4)
        # ratio gate (VERDICT r4 #3): a top-2/8 capacity-1.25 MoE should
        # hold >= 0.65x dense; the ratio is chip-state-immune (same
        # window), so no gate_scale — r5 measured 0.765
        extra["ratio_floor"] = MOE_RATIO_FLOOR
        if ratio < MOE_RATIO_FLOOR:
            extra["regression"] = True
            sys.stderr.write(f"REGRESSION: moe vs_dense_ratio "
                             f"{ratio:.3f} < {MOE_RATIO_FLOOR}\n")
        _emit("moe", value, "tokens/sec",
              metric=f"transformer_moe_lm_tokens_per_sec_{backend}",
              n_experts=8, top_k=2, routing="routed",
              capacity_factor=1.25, **extra)
    else:
        tokens_per_sec = batch * seq / _time_net_steps(net, ds, steps=steps)
        _emit_info({
            "metric": f"transformer_moe_lm_tokens_per_sec_{backend}",
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/sec",
            "vs_baseline": None,  # CPU smoke: no anchor
            "n_experts": 8, "top_k": 2})


def bench_transformer_dropout() -> None:
    """Masked + attention-dropout LM step (informational, VERDICT r3 #6):
    dropout is the reference's default regularizer — with the in-kernel
    counter-hash masks this config keeps the fused flash path instead of
    silently falling to dense O(T^2)."""
    import jax

    backend = jax.default_backend()
    net, ds, cfg = lm_mode_net_ds("dropout")
    sec = _time_net_steps(net, ds, steps=cfg["steps"])
    tokens_per_sec = cfg["batch"] * cfg["seq"] / sec
    peak = _peak_flops(jax.devices()[0])
    fields = _mfu_fields(tokens_per_sec, cfg, peak)
    line = {
        "metric": f"transformer_lm_masked_dropout_mfu_{backend}",
        "value": fields["mfu"] if peak else round(tokens_per_sec, 1),
        "unit": "MFU fraction" if peak else "tokens/sec",
        "vs_baseline": None,  # informational: compare to the clean mode
        "tokens_per_sec": round(tokens_per_sec, 1),
        "attention_dropout": cfg["attention_dropout"]}
    if peak:
        line["mfu_executed"] = fields["mfu_executed"]
    _emit_info(line)


def bench_ringhop() -> None:
    """Per-hop kernel rate inside ring attention (informational, VERDICT
    r3 #4): one ring hop = local Q against a visiting K/V block. Times
    the Pallas flash hop (flash_attention_lse) against the f32 einsum
    blockwise-softmax hop it replaced, single chip, fwd+bwd."""
    import functools

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.flash_attention import flash_attention_lse

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    BH, Tl, D = (64, 2048, 128) if on_tpu else (4, 256, 32)
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q, k, v = (jnp.asarray(rng.standard_normal((BH, Tl, D)), dt)
               for _ in range(3))
    scale = 1.0 / float(np.sqrt(D))

    def einsum_hop(q, k, v):
        s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        o = jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32))
        return o / jnp.maximum(p.sum(-1), 1e-30)[..., None]

    def flash_hop(q, k, v):
        o, _ = flash_attention_lse(q, k, v, scale, False)
        return o

    def grad_loop(hop, K):
        g = jax.grad(lambda q: jnp.sum(hop(q, k, v).astype(jnp.float32)
                                       ** 2))

        def body(i, c):
            return g(c) * dt(1e-3) + q
        return jax.lax.fori_loop(0, K, body, q)

    flops = 2 * 2 * BH * Tl * Tl * D * 3  # qk + pv, fwd + ~2x bwd

    def rate(hop):
        fns = {K: jax.jit(functools.partial(grad_loop, hop, K))
               for K in (4, 12)}
        for f in fns.values():
            _sync(f())  # compile

        def timed(f) -> float:
            t0 = time.perf_counter()
            _sync(f())
            return time.perf_counter() - t0

        t1 = min(timed(fns[4]) for _ in range(3))
        t3 = min(timed(fns[12]) for _ in range(3))
        per = (t3 - t1) / 8
        return flops / per if per > 0 else float("nan")

    f_rate, e_rate = rate(flash_hop), rate(einsum_hop)
    _emit_info({
        "metric": f"ring_hop_flash_tflops_{backend}",
        "value": round(f_rate / 1e12, 2), "unit": "TFLOP/s",
        "vs_baseline": None,
        "einsum_hop_tflops": round(e_rate / 1e12, 2),
        "speedup_vs_einsum_hop": round(f_rate / e_rate, 2),
        "shape": [BH, Tl, D]})


def bench_serving_replay() -> None:
    """Continuous-batching serving bench (serving/replay.py): replay the
    seeded mixed-length bursty trace against a freshly warmed engine +
    HTTP front door, reconstruct p50/p99/QPS from the telemetry
    `request` events alone, and leave the SERVE artifact next to the
    BENCH one. Runs identically off-TPU (the tiny-LM forward compiles
    anywhere); the sweep's skipped-env classification still applies if
    the environment eats it. Latency lines carry lower_is_better for
    benchdiff; the round gate is benchdiff vs the previous SERVE
    artifact, not an anchor."""
    import tempfile

    from deeplearning4j_tpu.serving.replay import run_replay

    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_SERVE_ARTIFACT", os.path.join(here, "SERVE_r01.json"))
    tpath = os.path.join(tempfile.mkdtemp(prefix="serving_replay_"),
                         "telemetry.jsonl")
    scoreboard = run_replay(
        model="lm", seed=0, n_requests=120, burst=4, mean_gap_s=0.002,
        lengths=(8, 16, 32), batch_sizes=(1, 2, 4), max_wait_ms=4.0,
        replicas=2, telemetry_path=tpath, artifact_path=artifact,
        emit=_emit_info)
    _emit_info({"metric": "serving_replay_artifact", "path": artifact,
                "warmed_buckets": scoreboard["warmed_buckets"],
                "n_ok": scoreboard["n_ok"],
                "client_failed": scoreboard["client"]["failed"]})


def bench_serving_generate() -> None:
    """Autoregressive generation serving bench (serving/replay.py
    run_generation_replay): the seeded prompt-length x output-length
    trace streams through POST /generate against a warmed
    GenerationEngine — prefill/decode split over the paged KV cache —
    and the scoreboard reconstructs from telemetry alone: tokens/sec
    (higher-is-better), TTFT p50/p99 and peak cache-page occupancy
    (lower-is-better; benchdiff inverts), and the zero-retrace row. The
    SERVE_r02 artifact lands next to the BENCH one; the round gate is
    benchdiff vs the previous generation artifact."""
    import tempfile

    from deeplearning4j_tpu.serving.replay import run_generation_replay

    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_SERVE_GEN_ARTIFACT", os.path.join(here,
                                                    "SERVE_r02.json"))
    tpath = os.path.join(tempfile.mkdtemp(prefix="serving_generate_"),
                         "telemetry.jsonl")
    scoreboard = run_generation_replay(
        seed=0, n_requests=48, burst=2, mean_gap_s=0.004,
        prompt_lengths=(8, 16, 32), output_lengths=(4, 8, 16),
        slots=4, page_size=16, replicas=2, telemetry_path=tpath,
        artifact_path=artifact, emit=_emit_info)
    _emit_info({"metric": "serving_generate_artifact", "path": artifact,
                "warmed_shapes": scoreboard["warmed_shapes"],
                "n_ok": scoreboard["n_ok"],
                "total_tokens": scoreboard["total_tokens"],
                "decode_steps": scoreboard["decode_steps"],
                "client_failed": scoreboard["client"]["failed"]})


def bench_serving_speculative() -> None:
    """Decode raw-speed serving bench (serving/replay.py
    run_speculative_replay): three A/B-interleaved arms of the same
    seeded generation trace — baseline greedy decode, speculative
    decode (n-gram draft + one fixed-shape verify step per window), and
    the int8-quantized paged KV cache — each against its own freshly
    warmed engine. Headlines: `accepted_tokens_per_step` (median tokens
    emitted per verify step per active slot; > 1.0 means drafts paid
    off), `draft_overhead_us` and `sample_us` (lower), the
    slots-per-HBM-byte ratio of the int8 cache, and the two PARITY
    gates — speculative and quantized greedy token streams must match
    the baseline arm request-for-request (0 mismatches), on top of the
    standing zero-retrace row per arm. The SERVE_r04 artifact lands
    next to the BENCH one; the round gate is benchdiff vs the previous
    r04 artifact."""
    import tempfile

    from deeplearning4j_tpu.serving.replay import run_speculative_replay

    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_SERVE_SPEC_ARTIFACT", os.path.join(here,
                                                     "SERVE_r04.json"))
    tpath = os.path.join(tempfile.mkdtemp(prefix="serving_speculative_"),
                         "telemetry.jsonl")
    scoreboard = run_speculative_replay(
        seed=0, n_requests=24, burst=2, mean_gap_s=0.004,
        prompt_lengths=(8, 16, 32), output_lengths=(4, 8, 16),
        slots=4, page_size=16, speculative_k=4, repeats=2,
        telemetry_path=tpath, artifact_path=artifact, emit=_emit_info)
    _emit_info({"metric": "serving_speculative_artifact", "path": artifact,
                "n_ok": scoreboard["n_ok"],
                "parity_mismatches": scoreboard["parity_mismatches"],
                "slots_per_hbm_byte_x": scoreboard["slots_per_hbm_byte_x"],
                "repeats": scoreboard["repeats"]})


def bench_input_pipeline() -> None:
    """Async input-pipeline bench (data/bench_worker.py) on the 2x4
    fleet matrix: a 2-process x 4-virtual-device fleet trains the same
    MLP through the stock fit() path with the input pipeline ON
    (depth-2 prefetch of device-resident batches) vs OFF (depth 0 — the
    pre-ISSUE-12 synchronous conversion), interleaved A/B per repeat.
    Headlines: pipelined/sync wall ratio on the INPUT-bound workload
    (record fetch+decode > step; the fetch's IO-latency component is
    what prefetch provably hides on a contended host) and steady-state
    `input_wait` p99 on the COMPUTE-bound workload (~0: the dequeue
    never stalls once the producer is ahead). Latency rows carry
    lower_is_better for benchdiff; the round gate is benchdiff vs the
    previous INPUT artifact."""
    from deeplearning4j_tpu.distributed.launcher import launch_local
    from deeplearning4j_tpu.serving.replay import write_artifact

    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_INPUT_ARTIFACT", os.path.join(here, "INPUT_r01.json"))
    results = launch_local(
        [sys.executable, "-m", "deeplearning4j_tpu.data.bench_worker"],
        n_processes=2, local_device_count=4, timeout=600.0)
    bad = [r for r in results if r.returncode != 0]
    if bad:
        raise RuntimeError(
            "input-pipeline fleet failed: "
            + "; ".join(f"p{r.process_id} rc={r.returncode} "
                        f"({r.exit_class})" for r in bad)
            + "\n" + bad[0].output[-2000:])
    payload = None
    for line in results[0].lines:
        if line.startswith("RESULT "):
            payload = json.loads(line[len("RESULT "):])
    if payload is None:
        raise RuntimeError("worker p0 printed no RESULT line:\n"
                           + results[0].output[-2000:])
    ib, cb = payload["input_bound"], payload["compute_bound"]
    lines = [
        {"metric": "input_pipeline_speedup", "value": ib["speedup"],
         "unit": "x", "ratio_spread": ib["ratio_spread"],
         "sync_step_ms": ib["sync_step_ms"],
         "pipelined_step_ms": ib["pipelined_step_ms"],
         "n_processes": payload["n_processes"],
         "depth": payload["depth"], "workload": "input_bound"},
        {"metric": "input_pipeline_compute_bound_speedup",
         "value": cb["speedup"], "unit": "x",
         "ratio_spread": cb["ratio_spread"],
         "sync_step_ms": cb["sync_step_ms"],
         "pipelined_step_ms": cb["pipelined_step_ms"],
         "workload": "compute_bound"},
        {"metric": "input_pipeline_input_wait_p99_ms",
         "value": cb["input_wait_p99_ms"], "unit": "ms",
         "lower_is_better": True,
         "input_wait_p50_ms": cb["input_wait_p50_ms"],
         "n_wait_spans": cb["n_wait_spans"],
         "workload": "compute_bound"},
        {"metric": "input_pipeline_input_bound_wait_p99_ms",
         "value": ib["input_wait_p99_ms"], "unit": "ms",
         "lower_is_better": True,
         "input_wait_p50_ms": ib["input_wait_p50_ms"],
         "workload": "input_bound"},
    ]
    for line in lines:
        _emit_info(line)
    summary = write_artifact(artifact, lines)
    _emit_info({"metric": "input_pipeline_artifact", "path": artifact,
                "regressions": summary.get("regressions", 0)})


def bench_placement_search() -> None:
    """Automatic placement search bench (reshard/search.py): the
    predicted-vs-measured rank gate on the launcher matrix's device
    grids (2x2 -> 4, 3x2 -> 6, 2x4 -> 8 virtual devices — the same
    single-process-equivalent-grid idiom the stage-3 collective audit
    compiles its fleet entries on; cross-process model placement is
    still guarded off, so the multi-process half of the search is
    proven by the elastic re-plan timeline test instead).

    Per grid: search the builtin `lm` profile under the FORWARD
    objective (this container cannot execute TP train steps — the
    pre-existing donation-alias class — so the measured step is the
    forward pass and the cost model scores the matching surface), then
    run the top-2 predicted placements plus the deliberately-bad
    control (the worst-ranked feasible candidate) each in its own
    subprocess (reshard/bench_arm.py) and compare orderings. A pair
    counts as a RANK VIOLATION only when the prediction separates it
    confidently (score ratio >= 2x) and the measurement inverts it past
    a 15% noise band — CPU containers promise ordering, never absolute
    ms. Any violation exits 1; the PLAN artifact (benchdiff-diffable:
    scores/ms/violations are lower-is-better, winner changes are named)
    lands next to the BENCH ones."""
    from deeplearning4j_tpu.reshard.search import (
        BUILTIN_PROFILES,
        FleetShape,
        Objective,
        emit_search_event,
        search_placement,
    )
    from deeplearning4j_tpu.serving.replay import write_artifact

    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_PLAN_ARTIFACT", os.path.join(here, "PLAN_r01.json"))
    GRIDS = (("2x2", 4), ("3x2", 6), ("2x4", 8))
    MARGIN = 2.0      # predicted score ratio that arms a pair
    NOISE_TOL = 0.15  # measured inversion slack (CPU noise band)
    BATCH = 48
    objective = Objective(global_batch=BATCH, step="forward",
                          zero1_options=(False,))
    lines = []
    total_violations = 0
    for grid, n in GRIDS:
        t0 = time.perf_counter()
        result = search_placement(BUILTIN_PROFILES["lm"], FleetShape(1, n),
                                  objective=objective)
        emit_search_event(result, path="bench", grid=grid,
                          search_ms=(time.perf_counter() - t0) * 1e3)
        arms = list(result.candidates[:2])
        control = result.candidates[-1]
        if control.describe() not in {a.describe() for a in arms}:
            arms.append(control)
        measured = []
        measured_bytes = []
        for cand in arms:
            # cost-model calibration handoff, WINNER arm only: the arm
            # reconciles the search's predicted per-device bytes
            # against its measured peak (telemetry/costbook.py
            # reconcile -> `cost_drift` event) and reports the
            # measurement back on RESULT. The control arm's memory
            # model is a ranking penalty, not a calibrated prediction
            # — reconciling it would fire the drift detector on every
            # healthy run
            spec = {"devices": n, "placement": cand.placement.to_json(),
                    "batch": BATCH, "repeats": 8, "seed": 0}
            if cand is result.best:
                spec["predicted_bytes"] = float(cand.memory_bytes)
            env = dict(os.environ)
            env.setdefault("JAX_PLATFORMS", "cpu")
            out = subprocess.run(
                [sys.executable, "-m",
                 "deeplearning4j_tpu.reshard.bench_arm",
                 json.dumps(spec)],
                capture_output=True, text=True, timeout=420, env=env)
            payload = [l for l in out.stdout.splitlines()
                       if l.startswith("RESULT ")]
            if out.returncode != 0 or not payload:
                raise RuntimeError(
                    f"placement bench arm {cand.describe()} on grid "
                    f"{grid} failed (rc={out.returncode}):\n"
                    + (out.stderr or out.stdout)[-2000:])
            res = json.loads(payload[-1][len("RESULT "):])
            measured.append(res["ms_per_step"])
            measured_bytes.append(res.get("measured_bytes", 0))
        violations = 0
        concordant = discordant = 0
        for i in range(len(arms)):
            for j in range(i + 1, len(arms)):
                si, sj = float(arms[i].score), float(arms[j].score)
                if measured[i] < measured[j]:
                    concordant += 1
                elif measured[i] > measured[j]:
                    discordant += 1
                separated = (si == 0 and sj > 0) or \
                    (si > 0 and sj / si >= MARGIN)
                if separated and measured[i] > measured[j] * (1 + NOISE_TOL):
                    violations += 1
        tau = round((concordant - discordant)
                    / max(1, concordant + discordant), 3)
        total_violations += violations
        best = result.best
        lines.append({
            "metric": f"plan_winner::{grid}", "value": float(best.score),
            "lower_is_better": True, "winner": best.describe(),
            "candidates": len(result.candidates),
            "pruned": len(result.pruned), "devices": n})
        for cand, ms, mb in zip(arms, measured, measured_bytes):
            lines.append({"metric":
                          f"plan_predicted::{grid}::{cand.describe()}",
                          "value": float(cand.score),
                          "lower_is_better": True})
            lines.append({"metric":
                          f"plan_measured_ms::{grid}::{cand.describe()}",
                          "value": ms, "lower_is_better": True})
            if mb:
                lines.append({"metric":
                              f"plan_measured_bytes::{grid}::"
                              f"{cand.describe()}",
                              "value": int(mb), "unit": "bytes",
                              "lower_is_better": True})
        # the winner's predicted-vs-measured memory, folded symmetric
        # (>= 1; 0 = no measurement): the per-grid calibration headline
        # the cost_drift events back with full provenance
        if measured_bytes and measured_bytes[0] and best.memory_bytes > 0:
            r = float(measured_bytes[0]) / float(best.memory_bytes)
            lines.append({"metric": f"plan_cost_drift_ratio::{grid}",
                          "value": round(max(r, 1.0 / r), 4),
                          "lower_is_better": True,
                          "predicted_bytes": float(best.memory_bytes),
                          "measured_bytes": int(measured_bytes[0])})
        lines.append({"metric": f"plan_rank_kendall_tau::{grid}",
                      "value": tau})
    lines.append({"metric": "plan_predicted_rank_violations",
                  "value": total_violations, "lower_is_better": True,
                  "margin": MARGIN, "noise_tol": NOISE_TOL})
    for line in lines:
        _emit_info(line)
    summary = write_artifact(artifact, lines)
    _emit_info({"metric": "placement_search_artifact", "path": artifact,
                "regressions": summary.get("regressions", 0),
                "rank_violations": total_violations})
    if total_violations:
        raise SystemExit(
            f"placement_search: {total_violations} predicted-vs-measured "
            "rank violation(s) — the cost model ordered a confidently-"
            "separated pair against the measurement")


# Sharded-embedding + ANN-serving bench config (ISSUE 19). Sizes were
# swept on the virtual-CPU mesh: the partition count matches the
# corpus's natural cluster count so the refine stage probes ~nprobe/P
# of the table — the regime where partition-then-refine beats one
# brute-force matmul even on CPU (measured 8.4x at this config; the
# gate floor is 5x). The smoke test runs the same code at toy sizes
# via `_embed_run` without the full-config gates.
EMBED_DIMS = dict(
    vocab=131072, dim=64, n_partitions=1024, n_clusters=1024,
    batch=1024, negative=5, window=5, seq_len=25, train_steps=20,
    query_batch=128, qps_reps=20, k=10, recall_floor=0.95,
    speedup_floor=5.0, ep_grid=(1, 2), lr=0.025, seed=0,
)


def _embed_clustered_corpus(rng, v: int, d: int, n_clusters: int):
    """Synthetic embedding-table snapshot with cluster structure (real
    embedding tables cluster — the recall/nprobe trade needs it)."""
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, v)
    noise = 0.15 * rng.normal(size=(v, d))
    return (centers[assign] + noise).astype(np.float32)


def _embed_run(cfg: dict, emit=None) -> dict:
    """Run the embedding bench at `cfg` sizes; returns {"lines": [...],
    "gates": {...}}. Shared by bench_embed (full config, gated) and the
    tests' off-TPU smoke (toy config, ungated)."""
    from deeplearning4j_tpu.util.virtual_devices import ensure_cpu_devices

    ensure_cpu_devices(8)
    import jax

    from deeplearning4j_tpu.embedding.ann import brute_force_topk, recall_at_k
    from deeplearning4j_tpu.embedding.corpus import (
        prefetched,
        sequence_pair_batches,
        with_negatives,
    )
    from deeplearning4j_tpu.embedding.engine import (
        EngineLookupView,
        ShardedEmbeddingEngine,
    )
    from deeplearning4j_tpu.embedding.serving import EmbeddingServingEngine
    from deeplearning4j_tpu.serving.buckets import BucketLattice
    from deeplearning4j_tpu.telemetry import Recorder

    emit = emit or (lambda line: None)
    v, d = cfg["vocab"], cfg["dim"]
    b, k_neg, window = cfg["batch"], cfg["negative"], cfg["window"]
    steps, k = cfg["train_steps"], cfg["k"]
    rng = np.random.default_rng(cfg["seed"])
    events: list = []
    rec = Recorder()
    rec.add_sink(events.append)
    cum = np.arange(1, v + 1, dtype=np.float64) / v   # uniform unigram

    # ---------------- train: prefetched pair feed, per-ep throughput
    lines: list = []
    rates, mem_bytes, view = {}, {}, None
    train_retraces = 0
    seq_len = cfg["seq_len"]
    pairs_per_seq = 2 * window * seq_len - window * (window + 1)
    n_seq = (steps + 2) * b // pairs_per_seq + 3
    for ep in cfg["ep_grid"]:
        eng = ShardedEmbeddingEngine(v, d, ep=ep, negative=k_neg,
                                     seed=3, recorder=rec)
        seqs = [rng.integers(0, v, size=seq_len) for _ in range(n_seq)]
        feed = prefetched(
            with_negatives(
                sequence_pair_batches(seqs, batch_size=b, window=window,
                                      seed=5 + ep),
                cum, k_neg, seed=7 + ep),
            depth=4)
        centers, contexts, negs = next(feed)
        loss = eng.sgns_step(centers, contexts, negs, cfg["lr"])  # compile
        jax.block_until_ready(loss)
        tc0 = eng.trace_count
        t0 = time.perf_counter()
        for _ in range(steps):
            centers, contexts, negs = next(feed)
            loss = eng.sgns_step(centers, contexts, negs, cfg["lr"])
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        feed.close()
        rates[ep] = steps * b / dt
        mem_bytes[ep] = eng.table_bytes_per_device()
        retraces = eng.trace_count - tc0
        spans = [e for e in events
                 if e.get("event") == "span"
                 and e.get("name") == "scatter_add" and e.get("ep") == ep]
        scatter_us = (1e6 * float(np.median([e["seconds"]
                                             for e in spans[1:]]))
                      if len(spans) > 1 else 0.0)
        gather_bytes = spans[-1]["ep_gather_bytes"] if spans else 0
        lines.append({
            "metric": ("embed_train_tokens_per_sec" if ep == 1
                       else f"embed_train_tokens_per_sec_ep{ep}"),
            "value": round(rates[ep], 1), "unit": "pairs/sec", "ep": ep,
            "batch": b, "steps": steps, "negative": k_neg,
            "retraces_after_warmup": int(retraces)})
        lines.append({
            "metric": f"embed_ep{ep}_ep_gather_bytes",
            "value": int(gather_bytes), "unit": "bytes",
            "lower_is_better": True, "ep": ep,
            "rows_per_step": b * (2 + k_neg)})
        lines.append({
            "metric": f"embed_mem_table_bytes_ep{ep}",
            "value": int(mem_bytes[ep]), "unit": "bytes",
            "lower_is_better": True, "ep": ep})
        if ep == 1:
            lines.append({
                "metric": "embed_scatter_add_us",
                "value": round(scatter_us, 1), "unit": "us",
                "lower_is_better": True, "n_spans": len(spans)})
            lines.append({
                "metric": "embed_train_recompiles_after_warmup",
                "value": int(retraces), "unit": "count",
                "lower_is_better": True})
            train_retraces = int(retraces)
            view = EngineLookupView(eng)
    ep_grid = list(cfg["ep_grid"])
    ratio = (mem_bytes[ep_grid[-1]] / mem_bytes[1]
             if len(ep_grid) > 1 and mem_bytes[1] else 1.0)
    if len(ep_grid) > 1:
        lines.append({
            "metric": "embed_ep_sharding_ratio", "value": round(ratio, 4),
            "unit": "x", "expected": round(1.0 / ep_grid[-1], 4),
            "source": "memstat ledger, per-device table bytes"})

    # ---------------- serving: publish a snapshot, calibrate, measure
    vecs = _embed_clustered_corpus(rng, v, d, cfg["n_clusters"])
    view.set_vectors(vecs)
    q = cfg["query_batch"]
    buckets = tuple(sorted({1, 4, 16, q}))
    serve = EmbeddingServingEngine(
        view, n_partitions=cfg["n_partitions"],
        lattice=BucketLattice(batch_sizes=buckets), k_grid=(k,),
        recall_floor=cfg["recall_floor"], calibration_queries=q,
        seed=1, recorder=rec)
    serve.start()
    tc0 = serve.trace_count

    # /embed round trip: served rows must be the published snapshot rows
    ids = np.asarray(rng.choice(v, size=min(16, q), replace=False),
                     np.int64)
    embed_req = serve.submit_embed(ids)
    if not embed_req.wait(60.0) or embed_req.error:
        raise RuntimeError(f"/embed round trip failed: {embed_req.error}")
    got = embed_req.result["vectors"]
    embed_exact = bool(np.allclose(got, vecs[ids], atol=1e-6))

    # query set drawn like the calibration sample: corpus rows
    qrng = np.random.default_rng(cfg["seed"] + 17)
    queries = vecs[qrng.choice(v, size=q, replace=False)]
    reps = cfg["qps_reps"]
    t0 = time.perf_counter()
    for _ in range(reps):
        search_req = serve.submit_search(queries, k)
        if not search_req.wait(120.0) or search_req.error:
            raise RuntimeError(f"/search failed: {search_req.error}")
    ann_dt = time.perf_counter() - t0
    ann_qps = reps * q / ann_dt
    res = search_req.result

    brute = jax.jit(lambda x: brute_force_topk(vecs, x, k))
    b_ids, _ = brute(queries)
    jax.block_until_ready(b_ids)           # compile + exact baseline ids
    t0 = time.perf_counter()
    for _ in range(reps):
        bi, bs = brute(queries)
    jax.block_until_ready(bs)
    brute_dt = time.perf_counter() - t0
    brute_qps = reps * q / brute_dt
    recall = recall_at_k(np.asarray(res["ids"]), np.asarray(b_ids))
    search_retraces = serve.trace_count - tc0
    serve.drain(30.0)

    speedup = ann_qps / brute_qps if brute_qps else 0.0
    lines.extend([
        {"metric": "embed_recall_at_k", "value": round(recall, 4),
         "unit": "recall", "k": k, "nprobe": serve.nprobe,
         "floor": cfg["recall_floor"],
         "calibrated_recall": serve.calibrated_recall},
        {"metric": "embed_queries_per_sec", "value": round(ann_qps, 1),
         "unit": "queries/sec", "query_batch": q, "k": k,
         "nprobe": serve.nprobe, "n_partitions": serve.index.n_partitions,
         "capacity": serve.index.capacity},
        {"metric": "embed_brute_force_queries_per_sec",
         "value": round(brute_qps, 1), "unit": "queries/sec",
         "query_batch": q, "vocab": v, "dim": d},
        {"metric": "embed_ann_speedup_vs_brute", "value": round(speedup, 2),
         "unit": "x", "floor": cfg["speedup_floor"]},
        {"metric": "embed_search_recompiles_after_warmup",
         "value": int(search_retraces), "unit": "count",
         "lower_is_better": True, "warmup_s": serve.warmup_s},
        {"metric": "embed_endpoint_roundtrip", "value": 1.0, "unit": "ok",
         "embed_rows_exact": embed_exact, "served": serve.served,
         "failed_requests": serve.failed},
    ])
    for line in lines:
        emit(line)
    return {"lines": lines,
            "gates": {"recall": recall, "speedup": speedup,
                      "sharding_ratio": ratio,
                      "train_retraces": train_retraces,
                      "search_retraces": int(search_retraces),
                      "embed_exact": embed_exact}}


def bench_embed() -> None:
    """Sharded embedding engine + ANN serving bench (ISSUE 19): SGNS
    train throughput over the prefetched pair feed at ep=1 and ep=2
    (per-device table bytes from the memstat ledger must halve),
    then ANN /search queries/sec and recall@10 vs exact brute force
    over a published clustered snapshot, with zero-retrace gates on
    both the train step and the warmed search path. Writes
    EMBED_r01.json (override: DL4J_TPU_EMBED_ARTIFACT)."""
    from deeplearning4j_tpu.serving.replay import write_artifact

    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_EMBED_ARTIFACT", os.path.join(here, "EMBED_r01.json"))
    out = _embed_run(EMBED_DIMS, emit=_emit_info)
    summary = write_artifact(artifact, out["lines"])
    _emit_info({"metric": "embed_artifact", "path": artifact,
                "regressions": summary.get("regressions", 0)})
    g = out["gates"]
    failures = []
    if g["recall"] < EMBED_DIMS["recall_floor"]:
        failures.append(f"recall@{EMBED_DIMS['k']} {g['recall']:.4f} < "
                        f"{EMBED_DIMS['recall_floor']}")
    if g["speedup"] < EMBED_DIMS["speedup_floor"]:
        failures.append(f"ANN speedup {g['speedup']:.2f}x < "
                        f"{EMBED_DIMS['speedup_floor']}x vs brute force")
    if not (0.4 <= g["sharding_ratio"] <= 0.6):
        failures.append(f"ep{EMBED_DIMS['ep_grid'][-1]}/ep1 table-bytes "
                        f"ratio {g['sharding_ratio']:.3f} not ~0.5")
    if g["train_retraces"]:
        failures.append(f"{g['train_retraces']} post-warmup retrace(s) "
                        "on the train step")
    if g["search_retraces"]:
        failures.append(f"{g['search_retraces']} post-warmup retrace(s) "
                        "on the search path")
    if not g["embed_exact"]:
        failures.append("/embed rows diverged from the published table")
    if failures:
        raise SystemExit("embed gates failed: " + "; ".join(failures))


MODES = {
    "lenet": bench_lenet,
    "vgg16": bench_vgg16,
    "word2vec": bench_word2vec,
    "resnet_dp": bench_resnet_dp,
    "transformer": bench_transformer,
    "transformer_d64": bench_transformer_d64,
    "transformer_large": bench_transformer_large,
    "masked": bench_transformer_masked,
    "longcontext": bench_longcontext,
    "longcontext_chunked": bench_longcontext_chunked,
    "longcontext_chunked_dropout": bench_longcontext_chunked_dropout,
    "moe": bench_moe,
    "dropout": bench_transformer_dropout,
    "ringhop": bench_ringhop,
    "serving_replay": bench_serving_replay,
    "serving_generate": bench_serving_generate,
    "serving_speculative": bench_serving_speculative,
    "input_pipeline": bench_input_pipeline,
    "placement_search": bench_placement_search,
    "embed": bench_embed,
}


def _probe_backend() -> str:
    """The jax backend the mode subprocesses will see, probed in a
    throwaway child: the parent sweep never initializes a backend (a
    chip belongs to one process; the parent would hold it against its
    own mode children). subprocess.run returns only after the child has
    exited, so the chip the probe took is released before the first
    mode starts. A probe that fails is an error — an unknown backend
    must not read as "off-TPU" and turn crashed modes into skips."""
    out = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        env=dict(os.environ), capture_output=True, text=True, timeout=180)
    backend = out.stdout.strip()
    if out.returncode != 0 or not backend:
        raise RuntimeError(
            f"backend probe failed (rc={out.returncode}): "
            f"{out.stderr[-2000:]}")
    return backend


def _trace_check(tpath: str, rec, collected: list) -> int:
    """Run `tracetool check` (subprocess — the CLI contract itself is
    what CI exercises) over the sweep's telemetry, write the TRACE
    artifact, and fold the detector rows into the metric record.
    Returns 1 when a gating anomaly (post-warmup retrace / rank skew /
    live-bytes leak) fired, 0 otherwise."""
    here = os.path.dirname(os.path.abspath(__file__))
    artifact = os.environ.get(
        "DL4J_TPU_TRACE_ARTIFACT", os.path.join(here, "TRACE_r01.json"))
    out = subprocess.run(
        [sys.executable, os.path.join(here, "tools", "tracetool.py"),
         "check", tpath, "--json", "--fail-on", "retrace,straggler,leak"],
        capture_output=True, text=True, timeout=300)
    try:
        payload = json.loads(out.stdout)
    except (ValueError, TypeError):
        rec.error("trace_check", error=f"rc={out.returncode}",
                  traceback_str=(out.stderr or out.stdout or "")[-4000:])
        return 1 if out.returncode else 0
    findings = payload.get("findings", [])
    subprocess.run(
        [sys.executable, os.path.join(here, "tools", "tracetool.py"),
         "stats", tpath, "--artifact", artifact],
        capture_output=True, text=True, timeout=300)
    skews = [f.get("skew_ms", 0.0) for f in findings
             if f.get("anomaly") == "straggler"]
    lines = [
        {"metric": "trace_anomaly_count", "value": len(findings),
         "unit": "count", "lower_is_better": True,
         "gating": payload.get("gating", 0)},
        {"metric": "straggler_skew_ms",
         "value": round(max(skews), 3) if skews else 0.0, "unit": "ms",
         "lower_is_better": True},
    ]
    lines.extend(_memory_rows(tpath, findings))
    for f in findings:
        rec.anomaly(f.get("anomaly", "unknown"),
                    **{k: v for k, v in f.items() if k != "anomaly"})
    for line in lines:
        print(json.dumps(line), flush=True)
        rec.metric(line)
        collected.append(json.dumps(line))
    if out.returncode == 1:
        print(json.dumps({"metric": "trace_check",
                          "error": f"{payload.get('gating')} gating "
                                   "anomaly(ies): retrace/rank-skew in "
                                   "the sweep's own telemetry"}),
              flush=True)
        return 1
    return 0


def _memory_rows(tpath: str, findings: list) -> list:
    """The sweep's memory/MFU headline rows, computed from its own
    telemetry (the `memory`/`cost`/`request` events the modes emitted):
    `hbm_peak_bytes` (max live bytes any process saw), `leak_count` and
    `cost_drift_ratio` (regress on ANY increase — the rise-from-zero
    rule), and `mfu_live` (cost-book flops over measured forward time,
    0.0 when no device peak is on the record — CPU sweeps). Emitted
    unconditionally so benchdiff/requote always have the row to
    compare, even from a truncated artifact."""
    from deeplearning4j_tpu.telemetry import trace as trace_mod

    try:
        tl = trace_mod.load_timeline(tpath)
        report = trace_mod.memory_report(tl)
    except Exception:
        return []
    peaks = [row.get("peak_bytes", 0)
             for row in report["processes"].values()]
    leaks = [f for f in findings if f.get("anomaly") == "leak"]
    drifts = [f for f in findings if f.get("anomaly") == "cost_drift"]
    worst_drift = 0.0
    for f in drifts:
        r = float(f.get("ratio", 0.0) or 0.0)
        if r > 0:
            worst_drift = max(worst_drift, r, 1.0 / r)
    # per-forward MFU: join request events (forward wall time, bucket)
    # with the cost book's flops for that bucket; the device peak rides
    # the warmup memory event
    costs, peak = {}, 0.0
    for ev in tl.events:
        if ev.get("event") == "cost" and ev.get("entry") == "forward":
            costs[tuple(ev.get("shape") or [])] = float(
                ev.get("flops", 0) or 0)
        elif ev.get("event") == "memory" and ev.get("peak_flops"):
            peak = max(peak, float(ev["peak_flops"]))
    mfu_vals = []
    if peak > 0:
        for ev in tl.events:
            if (ev.get("event") == "request" and ev.get("forward_s")
                    and ev.get("bucket")):
                fl = costs.get(tuple(ev["bucket"]), 0.0)
                if fl > 0:
                    mfu_vals.append(min(1.0, fl / (
                        float(ev["forward_s"]) * peak)))
    return [
        {"metric": "hbm_peak_bytes", "value": max(peaks) if peaks else 0,
         "unit": "bytes", "lower_is_better": True,
         "samples": sum(row.get("samples", 0)
                        for row in report["processes"].values())},
        {"metric": "leak_count", "value": len(leaks), "unit": "count",
         "lower_is_better": True},
        {"metric": "cost_drift_ratio", "value": round(worst_drift, 4),
         "lower_is_better": True},
        {"metric": "mfu_live",
         "value": round(sum(mfu_vals) / len(mfu_vals), 4)
         if mfu_vals else 0.0, "unit": "fraction",
         "forwards": len(mfu_vals)},
    ]


def _run_all() -> int:
    """Run each mode in a subprocess (isolated jax platform init).

    The sweep keeps TWO records: stdout metric lines (the driver
    artifact, tail-truncated to ~2000 bytes) and a shared telemetry
    JSONL log (`telemetry_bench.jsonl` unless DL4J_TPU_TELEMETRY names
    another path) that every child appends to — per-mode spans, full
    stderr/tracebacks of failing modes (VERDICT r5 #1: the
    transformer_large traceback was unrecoverable from the truncated
    tail), and every metric line verbatim.

    OFF-TPU, a mode lost to the environment (the vgg16 CPU-contention
    timeout class, or any per-mode crash under the CPU emulator) is
    classified as a SKIPPED-ENV mode — a `{"metric": <mode>, "skipped":
    "env: ..."}` line plus the full stderr in telemetry — instead of
    failing the sweep: off-TPU the sweep is a smoke environment, and
    rc must stay the gate for failures on the real chip (ROADMAP "get
    the sweep to rc=0")."""
    from deeplearning4j_tpu.telemetry import Recorder, set_default
    from deeplearning4j_tpu.telemetry.artifact import build_summary

    rc = 0
    collected = []
    skipped_env = []
    backend = _probe_backend()
    env_skippable = backend != "tpu"
    tpath = os.environ.get("DL4J_TPU_TELEMETRY") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "telemetry_bench.jsonl")
    with open(tpath, "w"):
        pass  # fresh log per sweep; children append
    rec = Recorder(tpath)
    set_default(rec)
    rec.meta(role="bench-sweep", modes=list(MODES), backend=backend)

    def _env_skip(mode, kind, stderr_text):
        """One skipped-env mode: a metric line that says so (it rides
        `collected` into the summary), the FULL stderr in telemetry,
        and NO rc contribution."""
        skipped_env.append(mode)
        rec.error(f"mode:{mode}", error=f"skipped-env: {kind}",
                  traceback_str=stderr_text or "")
        line = {"metric": mode, "skipped": f"env: off-TPU {kind}"}
        print(json.dumps(line), flush=True)
        rec.metric(line)
        collected.append(json.dumps(line))

    for mode in MODES:
        env = dict(os.environ)
        env["DL4J_TPU_TELEMETRY"] = tpath
        # every bench run carries `memory` events: the fit loops sample
        # on this cadence (telemetry/memstat.py on_step; serving warmup
        # samples regardless), feeding the leak/headroom detectors and
        # the hbm_peak_bytes row below
        env.setdefault("DL4J_TPU_MEM_EVERY", "4")
        if mode == "resnet_dp":
            # the DP-speedup bench needs a multi-device mesh; force the
            # virtual CPU cluster regardless of how many real chips exist
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=8")
        out = None
        timed_out = False
        timeout_stderr = ""
        t_mode = time.perf_counter()
        for attempt in range(3):
            try:
                attempt_out = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), mode],
                    env=env, capture_output=True, text=True, timeout=900)
            except subprocess.TimeoutExpired as exc:
                timed_out = True
                partial = exc.stderr or b""
                timeout_stderr = (partial.decode("utf-8", "replace")
                                  if isinstance(partial, bytes)
                                  else partial)
                break
            out = attempt_out
            # retry only when the child was killed by a signal (rc < 0 —
            # e.g. XLA CPU's 40s collectives-rendezvous abort when host
            # contention starves the virtual-device threads); ordinary
            # nonzero exits are deterministic — report them
            if out.returncode >= 0:
                break
            if attempt < 2:
                time.sleep(20)  # let transient contention drain
        seconds = round(time.perf_counter() - t_mode, 3)
        if out is None:
            rec.event("span", name=f"mode:{mode}", ok=False, seconds=seconds)
            if env_skippable:
                # the vgg16 class: a 900s wall-clock bust on a contended
                # CPU host is the environment, not the code
                _env_skip(mode, "timeout (CPU contention)", timeout_stderr)
                continue
            print(json.dumps({"metric": mode, "error": "timeout"}), flush=True)
            rec.error(f"mode:{mode}", error="timeout",
                      traceback_str=timeout_stderr)
            rc = 1
            continue
        if timed_out:  # only reachable after a signal-killed first attempt
            rec.event("span", name=f"mode:{mode}", ok=False, seconds=seconds)
            if env_skippable:
                _env_skip(mode, f"rc={out.returncode}, retry timeout",
                          out.stderr)
                continue
            sys.stderr.write(out.stderr[-2000:])
            rec.error(f"mode:{mode}",
                      error=f"rc={out.returncode}, retry timeout",
                      traceback_str=out.stderr)
            print(json.dumps({"metric": mode,
                              "error": f"rc={out.returncode}, retry timeout"}),
                  flush=True)
            rc = 1
            continue
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
                collected.append(line)
        rec.event("span", name=f"mode:{mode}", ok=out.returncode == 0,
                  seconds=seconds, rc=out.returncode)
        if out.returncode != 0:
            if env_skippable:
                # per-mode crash off-TPU: the full stderr lands in
                # telemetry via _env_skip; the sweep stays rc=0
                _env_skip(mode, f"crash rc={out.returncode}", out.stderr)
                continue
            sys.stderr.write(out.stderr[-2000:])
            # the FULL stderr/traceback goes to the telemetry log (the
            # stdout echo above is still tail-truncated by the driver);
            # the last exception line is also folded INTO the json error
            # line so the cause survives any truncation of stdout too
            rec.error(f"mode:{mode}", error=f"rc={out.returncode}",
                      traceback_str=out.stderr)
            exc_lines = [l.strip() for l in out.stderr.splitlines()
                         if l.strip()]
            print(json.dumps({"metric": mode,
                              "error": f"rc={out.returncode}",
                              "exc": exc_lines[-1][:300] if exc_lines
                              else ""}),
                  flush=True)
            rc = 1
    # the sweep audits its OWN telemetry (ISSUE 15): tracetool check
    # over the shared log + the fleet modes' .pN shards — a post-warmup
    # retrace in the serving replays or cross-process rank skew in the
    # fleet modes fails the sweep even when every mode exited 0 (the
    # zero-retrace and lockstep contracts' runtime witnesses). Spike
    # kinds stay informational: a contended CPU host's input stalls are
    # the environment, not the code.
    rc = max(rc, _trace_check(tpath, rec, collected))
    # gate-carrying trailing summary (telemetry/artifact.py): the driver
    # keeps the END of the captured stdout, so early lines scroll out of
    # the artifact (r4 lost the LeNet line; r5 lost five modes' gate
    # fields — VERDICT r5 #6). This one line restates every metric:value
    # pair, every gate field under `gates`, and names each regressed
    # metric; tools/requote_bench.py and tools/benchdiff.py invert it.
    summary = build_summary(collected)
    if skipped_env:
        # the summary line names what the off-TPU environment ate, so a
        # clean rc=0 artifact is never mistaken for full coverage
        summary["skipped_env"] = skipped_env
    print(json.dumps(summary), flush=True)
    rec.metric(summary)
    rec.close()
    return rc


def main() -> int:
    if len(sys.argv) > 1:
        mode = sys.argv[1]
        if mode not in MODES:
            sys.stderr.write(f"unknown mode {mode}; one of {list(MODES)}\n")
            return 2
        from deeplearning4j_tpu.util.compile_cache import (
            enable_compile_cache,
        )

        enable_compile_cache()
        rec = _recorder()
        rec.meta(role="bench-mode", mode=mode)
        try:
            # a crash inside the span leaves an `error` event with the
            # FULL traceback in the telemetry log (the truncation-proof
            # copy) and still propagates — the stderr text and nonzero
            # rc the parent sweep expects are unchanged
            with rec.span(f"run:{mode}", mode=mode):
                MODES[mode]()
        finally:
            rec.close()
        return 0
    return _run_all()


if __name__ == "__main__":
    sys.exit(main())
