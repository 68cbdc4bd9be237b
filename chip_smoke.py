#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: trainer + /generate server
    python chip_smoke.py --chips 4   # four chips: set_mesh 2x2 steps vs one device, nothing else

Drives the main path once through the entry points a user calls, at the
full width of the largest LM the repo ships (bench.py `transformer_large`:
d_model 1024, 8 heads of 128, d_ff 4096, vocab 10000, bf16, batch 32 x
seq 512, 6 layers), weights and tokens made from --seed:

  device   fail unless jax.devices()[0].platform == "tpu"
  trainer  transformer_lm(...).fit on a repeated seeded batch; loss finite
           and lower; the compiled step's text carries the flash forward /
           backward and the softmax-xent head as tpu_custom_call kernels
  kernels  the fused LayerNorm (16384 x 1024) and fused sampling (serving
           vocab padded to the lane tile) compiled on the chip, each
           against its plain-jnp reference
  server   ServingServer + GenerationEngine on the same net, POST /generate
           over localhost; every request answers, zero compiles after
           warm-up, cached decode agrees with the un-cached forward

One process, one chip; no child process. Any phase that fails raises and
the script exits non-zero. The last line of stdout is the result:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.

--rehearse runs the same code at a tiny size on the CPU (kernels in
interpret mode) to find wrong paths before chip time is spent. It never
prints a TPU device.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import threading
import time
import urllib.request

FULL = dict(vocab=10000, d_model=1024, n_heads=8, n_layers=6, d_ff=4096,
            seq=512, batch=32, steps=8, prompts=(40, 64, 200, 256, 17, 130),
            new_tokens=32)
TINY = dict(vocab=2048, d_model=256, n_heads=2, n_layers=1, d_ff=512,
            seq=512, batch=2, steps=3, prompts=(40, 64, 200),
            new_tokens=4)

# bf16 tolerances, stated with the results they gate
TOL_DECODE_LOGP = 0.25   # max |log p| gap, cached decode vs plain forward
TOL_LN = 0.0625          # max |y| gap, fused LayerNorm vs f32 reference
TOL_MESH_LOSS = 0.05     # per-step loss gap, 2x2 mesh vs one device


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def build_lm(cfg, seed):
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(
        vocab_size=cfg["vocab"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_layers=cfg["n_layers"], d_ff=cfg["d_ff"],
        max_length=cfg["seq"], dtype="bfloat16", seed=seed)
    net.init()
    return net


def token_batch(cfg, seed):
    """The bench.py lm_mode_net_ds shape: [batch, seq] tokens with
    next-token labels."""
    import numpy as np

    from deeplearning4j_tpu.datasets.api import DataSet

    rng = np.random.default_rng(seed)
    toks = np.asarray(
        rng.integers(0, cfg["vocab"], (cfg["batch"], cfg["seq"])), np.int32)
    return DataSet(toks, np.roll(toks, -1, axis=1))


def kernel_counts(text):
    """{pallas kernel name: tpu_custom_call count} in a compiled
    program's text (the ops name every pallas_call)."""
    found = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # jit(step)/jvp(flash_fwd_qkv)/pallas_call, .../name/pallas_call
        m = re.search(r'op_name="[^"]*?([A-Za-z0-9_]+)\)*/pallas_call"',
                      line)
        name = m.group(1) if m else "unnamed"
        found[name] = found.get(name, 0) + 1
    return found


def fit_steps(net, ds, n):
    """n optimizer steps through net.fit on the same batch; the loss of
    each (a host fetch per step, which also waits for the device)."""
    losses = []
    for _ in range(n):
        net.fit(ds)
        losses.append(float(net.score_value))
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    return losses


# ---------------------------------------------------------------- phases

def phase_device(chips, rehearse):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if not rehearse and d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator (jax.devices()[0].platform is "
            f"{d.platform!r}, not 'tpu'); --rehearse is the CPU run")
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, "
            f"jax reports {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_trainer(cfg, seed, on_tpu):
    import jax

    net = build_lm(cfg, seed)
    ds = token_batch(cfg, seed)
    n_params = sum(int(a.size) for a in jax.tree.leaves(net.params))
    log(f"trainer: transformer_lm d_model={cfg['d_model']} "
        f"heads={cfg['n_heads']} layers={cfg['n_layers']} "
        f"d_ff={cfg['d_ff']} vocab={cfg['vocab']} bf16, "
        f"{n_params / 1e6:.1f}M params, batch {cfg['batch']} x "
        f"seq {cfg['seq']}")

    # the step net.fit runs, compiled ahead so its text can be read
    step = net._get_train_step()
    batch = net._batch_dict(net._to_mds(ds))
    t0 = time.perf_counter()
    compiled = step.lower(net.params, net.opt_state, net.state,
                          jax.random.PRNGKey(0), batch).compile()
    log(f"trainer: train step compiled in "
        f"{time.perf_counter() - t0:.1f} s")
    kernels = kernel_counts(compiled.as_text())
    log(f"trainer: tpu_custom_call kernels in the compiled step: "
        f"{json.dumps(kernels, sort_keys=True)}")
    if on_tpu:
        L = cfg["n_layers"]
        want = {"flash_fwd_qkv": L, "flash_bwd_qkv": L,
                "softmax_xent_fwd": 1, "softmax_xent_dx": 1,
                "softmax_xent_dwdb": 1}
        if kernels != want:
            raise AssertionError(
                f"compiled train step carries {kernels}, expected {want}: "
                f"a layer took a reference path")

    t0 = time.perf_counter()
    losses = fit_steps(net, ds, cfg["steps"])
    log(f"trainer: {cfg['steps']} fit steps in "
        f"{time.perf_counter() - t0:.1f} s (first includes the jit's own "
        f"compile or cache read); step losses "
        f"{[round(v, 4) for v in losses]}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"loss did not fall on the repeated batch: {losses}")
    return net


def phase_kernels(net, cfg, seed, on_tpu):
    """The two Pallas kernels of the main path's neighbourhood that no
    entry point dispatches to: the LM's LayerNorm keeps the jnp form
    (nn/layers/attention.py — the fused kernel lost its A/B), and
    /generate is greedy (serving/engine.py argmaxes on device; no
    temperature parameter). Both run here compiled, at the LM's widths,
    against their references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import fused_sampling
    from deeplearning4j_tpu.ops.fused_layernorm import fused_layer_norm

    n_tok, C = cfg["batch"] * cfg["seq"], cfg["d_model"]
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k1, (n_tok, C), jnp.bfloat16)
    g = 1.0 + 0.1 * jax.random.normal(k2, (C,), jnp.bfloat16)
    b = 0.1 * jax.random.normal(k3, (C,), jnp.bfloat16)

    def ref_ln(x, g, b):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return y * g.astype(jnp.float32) + b.astype(jnp.float32)

    def fused_loss(x, g, b):
        return (fused_layer_norm(x, g, b).astype(jnp.float32) ** 2).mean()

    def ref_loss(x, g, b):
        return (ref_ln(x, g, b) ** 2).mean()

    fwd = jax.jit(fused_layer_norm).lower(x, g, b).compile()
    bwd = jax.jit(jax.grad(fused_loss, argnums=(0, 1, 2))).lower(
        x, g, b).compile()
    ln_kernels = kernel_counts(fwd.as_text())
    for name, n in kernel_counts(bwd.as_text()).items():
        ln_kernels[name] = max(ln_kernels.get(name, 0), n)
    y_err = float(jnp.abs(fwd(x, g, b).astype(jnp.float32)
                          - ref_ln(x, g, b)).max())
    dx, dg, db = bwd(x, g, b)
    rdx, rdg, rdb = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(x, g, b)
    dg_err = float(jnp.abs(dg.astype(jnp.float32)
                           - rdg.astype(jnp.float32)).max())
    dx_err = float(jnp.abs(dx.astype(jnp.float32)
                           - rdx.astype(jnp.float32)).max())
    log(f"kernels: fused LayerNorm {n_tok} x {C}: {json.dumps(ln_kernels)}"
        f"; max |y - ref| {y_err:.4f} (tolerance {TOL_LN}), "
        f"max |dgamma - ref| {dg_err:.5f}, max |dx - ref| {dx_err:.2e}")
    if not (y_err <= TOL_LN and dg_err <= TOL_LN and dx_err <= TOL_LN):
        raise AssertionError("fused LayerNorm disagrees with its reference")
    if on_tpu and not (ln_kernels.get("fused_layer_norm_fwd")
                       and ln_kernels.get("fused_layer_norm_bwd")):
        raise AssertionError(f"fused LayerNorm not compiled: {ln_kernels}")

    # sampling over the net's own next-token distribution: 8 rows of the
    # plain forward, vocab padded to the 128-lane tile with -inf
    V = cfg["vocab"]
    Vp = (V + 127) // 128 * 128
    rng = np.random.default_rng(seed + 1)
    toks = np.asarray(rng.integers(0, V, (8, 64)), np.int32)
    probs = jnp.asarray(net.output(toks))[:, -1, :].astype(jnp.float32)
    logits = jnp.pad(jnp.log(jnp.maximum(probs, 1e-30)),
                     ((0, 0), (0, Vp - V)), constant_values=-1e30)
    noise = fused_sampling.gumbel_noise(k4, 8, Vp)
    knobs = dict(temperature=0.8, top_k=40, top_p=0.95)
    if not fused_sampling.supports(8, Vp):
        raise AssertionError(f"[8, {Vp}] outside fused_sample's envelope")
    samp = jax.jit(lambda lg, nz: fused_sampling.fused_sample(
        lg, nz, **knobs)).lower(logits, noise).compile()
    got = np.asarray(samp(logits, noise))
    want = np.asarray(jax.jit(lambda lg, nz: fused_sampling._select_body(
        lg, nz, knobs["temperature"], knobs["top_k"],
        knobs["top_p"]))(logits, noise))
    s_kernels = kernel_counts(samp.as_text())
    log(f"kernels: fused_sample [8, {Vp}] {knobs}: "
        f"{json.dumps(s_kernels)}; tokens {got.tolist()} "
        f"reference {want.tolist()}")
    if not (np.array_equal(got, want) and (got < V).all()):
        raise AssertionError("fused_sample disagrees with its reference")
    if on_tpu and not s_kernels.get("fused_sample"):
        raise AssertionError(f"fused_sample not compiled: {s_kernels}")


def post_generate(url, tokens, max_new, rid):
    body = json.dumps({"tokens": [int(t) for t in tokens],
                       "max_new_tokens": max_new, "id": rid}).encode()
    req = urllib.request.Request(
        url + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        lines = [json.loads(ln) for ln in resp.read().splitlines() if ln]
    return lines[-1]


def phase_server(net, cfg, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.serving.buckets import BucketLattice
    from deeplearning4j_tpu.serving.engine import GenerationEngine
    from deeplearning4j_tpu.serving.server import ServingServer

    new, page = cfg["new_tokens"], 16
    lattice = BucketLattice(batch_sizes=[1], seq_lens=[64, 256])
    engine = GenerationEngine(net, lattice, slots=4, max_new_tokens=new,
                              page_size=page)
    t0 = time.perf_counter()
    compiles = engine.warmup()
    traces_warm = engine.trace_count
    log(f"server: warm-up compiled {compiles} programs (prefill buckets "
        f"64, 256 + the 4-slot decode step) in "
        f"{time.perf_counter() - t0:.1f} s")
    server = ServingServer(engine, port=0).start()
    rng = np.random.default_rng(seed + 2)
    prompts = [np.asarray(rng.integers(0, cfg["vocab"], n), np.int32)
               for n in cfg["prompts"]]
    answers = [None] * len(prompts)

    def client(i):
        answers[i] = post_generate(server.url, prompts[i], new, f"smoke{i}")

    try:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        server.stop()
    for i, a in enumerate(answers):
        if (a is None or not a.get("done") or a.get("error")
                or len(a["tokens"]) != new):
            raise AssertionError(f"request {i} did not answer whole: {a}")
    post_warm = engine.trace_count - traces_warm
    log(f"server: {len(answers)} POST /generate requests (prompts "
        f"{list(cfg['prompts'])} tokens, {new} new each, 4 slots) answered "
        f"in {wall:.2f} s; tokens out {stats['tokens_out']}; compiles "
        f"after warm-up {post_warm}; ttft_s "
        f"{[a['timing']['ttft_s'] for a in answers]}")
    if post_warm != 0 or stats["failed"] != 0:
        raise AssertionError(
            f"{post_warm} compiles after warm-up, {stats['failed']} failed")

    # cached decode vs plain forward, request 0 (greedy, as all are):
    # prefill + single-token steps over the paged cache, fed the tokens
    # the server emitted, against net.output over the whole sequence
    prompt, emitted = prompts[0], answers[0]["tokens"]
    L = len(prompt)
    Tb = lattice.seq_bucket(L)
    capacity = (lattice.max_seq + new + page - 1) // page * page
    cache = net.init_kv_cache(1, capacity, "f32", page)
    padded = np.zeros((1, Tb), np.int32)
    padded[0, :L] = prompt
    kmask = (np.arange(Tb)[None, :] < L).astype(np.float32)
    zero = np.zeros(1, np.int32)
    prefill = jax.jit(net.prefill_fn("f32", page))
    decode = jax.jit(net.incremental_decode_fn("f32", page))
    p, cache = prefill(net.params, net.state, cache, padded, kmask, zero,
                       zero, np.asarray([L - 1], np.int32))
    rows = [p[0]]
    for k in range(new - 1):
        p, cache = decode(net.params, net.state, cache,
                          np.asarray([emitted[k]], np.int32),
                          np.asarray([L + k], np.int32))
        rows.append(p[0])
    cached = jnp.stack(rows).astype(jnp.float32)            # [new, V]
    full = np.concatenate([prompt, np.asarray(emitted[:-1], np.int32)])
    plain = jnp.asarray(net.output(full[None, :]))[0, L - 1:, :].astype(
        jnp.float32)                                        # [new, V]
    if not bool(jnp.isfinite(cached).all()):
        raise AssertionError("cached decode produced non-finite values")
    cached_greedy = np.asarray(jnp.argmax(cached, -1)).tolist()
    # compare where the distribution has mass: the plain forward's top 50
    top = jnp.argsort(-plain, axis=-1)[:, :50]
    lp_c = jnp.log(jnp.take_along_axis(cached, top, -1) + 1e-30)
    lp_p = jnp.log(jnp.take_along_axis(plain, top, -1) + 1e-30)
    gap = float(jnp.abs(lp_c - lp_p).max())
    agree = sum(int(a == b) for a, b in zip(
        cached_greedy, np.asarray(jnp.argmax(plain, -1)).tolist()))
    log(f"server: request 0 cached decode vs plain forward over "
        f"{new} positions: max |log p| gap on the top-50 tokens {gap:.4f} "
        f"(bf16 tolerance {TOL_DECODE_LOGP}); argmax agrees at "
        f"{agree}/{new}; served tokens == cached-decode argmax: "
        f"{cached_greedy == emitted}")
    if cached_greedy != emitted:
        raise AssertionError(
            f"server emitted {emitted}, cached decode says {cached_greedy}")
    if gap > TOL_DECODE_LOGP:
        raise AssertionError(
            f"cached decode is {gap} from the plain forward in log p")


def phase_mesh(cfg, seed, on_tpu):
    """--chips 4: the same LM for a few steps on a 2x2 data x model mesh
    through set_mesh, against the same steps on one device."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh

    ds = token_batch(cfg, seed)
    steps = min(cfg["steps"], 4)

    one = build_lm(cfg, seed)
    t0 = time.perf_counter()
    ref = fit_steps(one, ds, steps)
    log(f"mesh: one device, {steps} steps in "
        f"{time.perf_counter() - t0:.1f} s: "
        f"{[round(v, 4) for v in ref]}")
    del one

    devs = jax.devices()[:4]
    mesh = make_mesh({"data": 2, "model": 2}, devices=devs)
    net = build_lm(cfg, seed)
    net.set_mesh(mesh, axes={"data": "data", "model": "model"})
    t0 = time.perf_counter()
    got = fit_steps(net, ds, steps)
    log(f"mesh: 2x2 data x model, {steps} steps in "
        f"{time.perf_counter() - t0:.1f} s: "
        f"{[round(v, 4) for v in got]}")
    gaps = [abs(a - b) for a, b in zip(got, ref)]
    log(f"mesh: per-step |loss gap| {[round(g, 5) for g in gaps]} "
        f"(tolerance {TOL_MESH_LOSS})")
    if max(gaps) > TOL_MESH_LOSS:
        raise AssertionError(f"mesh losses {got} vs one device {ref}")

    # parameters really spread: bytes of param shards per device
    per_dev = {d.id: 0 for d in devs}
    total = 0
    for leaf in jax.tree.leaves(net.params):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    log(f"mesh: param bytes held per device {per_dev} of {total} total")
    if not all(0 < v < total for v in per_dev.values()):
        raise AssertionError(f"params not sharded over 4 devices: {per_dev}")
    # the batch: how the compiled step takes it, and what each device holds
    step = net._get_train_step()
    batch = net._batch_dict(net._to_mds(ds))
    compiled = step.lower(net.params, net.opt_state, net.state,
                          jax.random.PRNGKey(0), batch).compile()
    batch_sh = jax.tree.leaves(compiled.input_shardings[0][4])
    log(f"mesh: batch enters the step as "
        f"{sorted({str(s.spec) for s in batch_sh})} on mesh "
        f"{dict(mesh.shape)}; kernels per device program: "
        f"{json.dumps(kernel_counts(compiled.as_text()), sort_keys=True)}")
    if not all("data" in str(s.spec) for s in batch_sh):
        raise AssertionError(f"batch not split over 'data': {batch_sh}")
    mem = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in devs}
    log(f"mesh: peak_bytes_in_use per device {mem}")
    if on_tpu and not all(mem.values()):
        raise AssertionError(f"a device held nothing: {mem}")


# ------------------------------------------------------------------ main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU, kernels interpreted")
    args = ap.parse_args()
    t_start = time.perf_counter()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            from deeplearning4j_tpu.util.virtual_devices import (
                cpu_device_flags)

            os.environ["XLA_FLAGS"] = cpu_device_flags(
                args.chips, os.environ.get("XLA_FLAGS", ""))
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0,
                    "cache_misses": 0}

    def on_event(name, **_):
        key = name.rsplit("/", 1)[-1]
        if name.startswith("/jax/compilation_cache/") and key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    device = phase_device(args.chips, args.rehearse)
    on_tpu = device["platform"] == "tpu"
    log(f"compile cache: {cache_dir}"
        + (" (from JAX_COMPILATION_CACHE_DIR)"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR") else ""))
    cfg = dict(TINY if args.rehearse else FULL)
    if args.rehearse:
        # off-TPU the fused head is opt-in; take it, interpreted
        from deeplearning4j_tpu.ops import fused_softmax_xent

        fused_softmax_xent.FORCE_FUSED = True

    if args.chips == 4:
        phase_mesh(cfg, args.seed, on_tpu)
    else:
        net = phase_trainer(cfg, args.seed, on_tpu)
        phase_kernels(net, cfg, args.seed, on_tpu)
        phase_server(net, cfg, args.seed)

    from deeplearning4j_tpu.ops import autotune

    log(f"tuning keys resolved (table active: {autotune.table_active()}): "
        f"{json.dumps(autotune.resolved_keys(), sort_keys=True)}")
    log(f"compile cache: {cache_events['compile_requests_use_cache']} "
        f"compile requests, {cache_events['cache_hits']} hits, "
        f"{cache_events['cache_misses']} written")
    log(f"wall {time.perf_counter() - t_start:.1f} s on {device['kind']} "
        f"x {device['count']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
