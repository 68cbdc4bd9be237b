"""Traffic kinds `serve_open` and `serve_closed`: the program's
`ServingServer` over a `GenerationEngine`, loaded over localhost HTTP.

This process holds the chip and runs the server and nothing else; the
load generator (harness/loadgen.py) is a child process that never imports
JAX, is started before the window and reaped after it. Once the window
has closed and the peak memory is read, the server is stopped and freed,
and the plain reference runs once over a seeded sample of the requests
the window finished (the longest among them) with the tokens they were
served. Which model is served, its seeded weights and its reference are the
configuration's family's (`spec.family_of`).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from harness import device, spec, traffic
from harness.spec import BENCH_DIR

# what the client's clock gives; BENCHMARK.json names the ones a cell reports
CLIENT_METRICS = ("serve_tokens_per_s", "tpot_ms_p95", "request_ms_mean",
                  "ttft_ms_p90")


class Served:
    """The server under test, up and warm."""

    def __init__(self, config: dict, seed: int, log=lambda m: None):
        from deeplearning4j_tpu.serving.buckets import BucketLattice
        from deeplearning4j_tpu.serving.engine import GenerationEngine
        from deeplearning4j_tpu.serving.server import ServingServer

        self.family = spec.family_of(config)
        self.dims = self.family.dims_of(config)
        # every key of `deployment` but the prefill buckets is an argument
        # of GenerationEngine by that name
        dep = dict(config["deployment"])
        seq_lens = dep.pop("prefill_seq_lens")
        t = time.perf_counter()
        self.net = self.family.serving_net(config, seed, self.dims)
        import jax

        jax.block_until_ready(self.net.params)
        log(f"weights on the device in {time.perf_counter() - t:.1f} s")
        self.engine = GenerationEngine(
            self.net, BucketLattice(batch_sizes=[1], seq_lens=seq_lens), **dep)
        t = time.perf_counter()
        n = self.engine.warmup()
        log(f"{n} serving programs warm in {time.perf_counter() - t:.1f} s")
        self.server = ServingServer(self.engine, port=0).start()
        self.host, self.port = self.server._httpd.server_address[:2]
        self.traces_warm = self.engine.trace_count

    def warm_request(self, vocab: int) -> None:
        """One short request through the whole HTTP path."""
        from harness import loadgen

        req = {"id": "warm", "prompt": [1] * 16, "max_new": 2}
        rec = loadgen.new_record(dict(req, due_s=None, client=None))
        loadgen.post_generate(self.host, self.port, req, rec,
                              time.monotonic() + 120)
        if rec["error"] or not rec["tokens"]:
            raise RuntimeError(f"the warm-up request failed: {rec}")

    def compiles_since_warm(self) -> int:
        return self.engine.trace_count - self.traces_warm

    def stop(self) -> None:
        """Stop the server and free its weights and cache. The stopped
        engine itself lives on (the HTTP handler class and the metrics
        hold it), so what it holds on the device is taken out of it."""
        self.server.stop()
        for w in self.engine.fleet_workers():
            w.cache = None
        self.engine.weights.publish(None, None, 0)
        self.net.params = None
        self.engine = self.server = self.net = None


def run_load(served: Served, mix: dict, seed: int, seconds: float,
             grace: float, lead_s: float = 1.5, on_open=None) -> dict:
    """One window of the mix against the server, from a child process.
    Returns the load generator's record file."""
    tmp = tempfile.mkdtemp(prefix="bench_load_")
    mix_path, out_path = os.path.join(tmp, "mix.json"), os.path.join(tmp, "out.json")
    with open(mix_path, "w") as fh:
        json.dump(mix, fh)
    t0 = time.monotonic() + lead_s
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"),
         "--mix", mix_path, "--seed", str(seed), "--vocab",
         str(served.dims["V"]), "--seconds", str(seconds), "--host",
         served.host, "--port", str(served.port), "--start-at", repr(t0),
         "--grace", str(grace), "--out", out_path], env=env)
    try:
        time.sleep(max(0.0, t0 - time.monotonic()))
        if on_open is not None:
            on_open(time.perf_counter())
        rc = child.wait(timeout=seconds + grace + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise RuntimeError(f"the load generator exited with {rc}")
    with open(out_path) as fh:
        out = json.load(fh)
    for p in (mix_path, out_path):
        os.remove(p)
    os.rmdir(tmp)
    return out


def client_metrics(load: dict, kind: str) -> dict:
    """The end-to-end numbers, on the client's clock, over all requests."""
    t0, seconds, recs = load["t0"], load["seconds"], load["records"]
    t_end = t0 + seconds
    ttft, whole, gaps, late, failed, in_window = [], [], [], [], 0, 0
    finished = []
    for r in recs:
        ok = (not r["error"] and r["tokens"] is not None
              and len(r["tokens"]) == r["max_new"])
        if not ok:
            failed += 1
        ts = r["t_tokens"]
        in_window += sum(1 for t in ts if t0 <= t <= t_end)
        gaps.extend(1e3 * (b - a) for a, b in zip(ts, ts[1:]))
        if r["due_s"] is not None:
            due = t0 + r["due_s"]
            if r["t_sent"] is not None:
                late.append(1e3 * (r["t_sent"] - due))
            if ts and ok:
                ttft.append(1e3 * (ts[0] - due))
                whole.append(1e3 * (r["t_done"] - due))
            else:       # failed, refused or unfinished: the worst
                ttft.append(float("inf"))
                whole.append(float("inf"))
        if ok and r["t_done"] is not None and r["t_done"] <= t_end:
            finished.append(r)
    worst = max([x for x in whole if np.isfinite(x)] + [1e3 * (seconds + 60.0)])
    ttft = sorted(x if np.isfinite(x) else worst for x in ttft)
    whole = [x if np.isfinite(x) else worst for x in whole]
    out = {"attempted": len(recs), "failed": failed, "finished": finished,
           "tokens_in_window": in_window, "n_gaps": len(gaps),
           "late_ms_p95": traffic.percentile(late, 95) if late else None,
           "late_ms_max": max(late) if late else None,
           "serve_tokens_per_s": in_window / seconds,
           "tpot_ms_p95": traffic.percentile(gaps, 95) if gaps else None,
           "request_ms_mean": float(np.mean(whole)) if whole else None,
           "ttft_ms_mean": float(np.mean(ttft)) if ttft else None,
           "ttft_ms_p90": traffic.percentile(ttft, 90) if ttft else None,
           "ttft_ms_p50": traffic.percentile(ttft, 50) if ttft else None}
    return out


def pick_sample(finished: list, seed: int, n: int, min_tokens: int) -> list:
    """A sample of the finished requests drawn from the seed, with the
    longest (prompt + served tokens) in it: `n` requests, and more of the
    same draw until they hold `min_tokens` served tokens."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 19])
    longest = max(range(len(finished)), key=lambda i: (
        finished[i]["prompt_len"] + len(finished[i]["tokens"]), -i))
    rest = [i for i in range(len(finished)) if i != longest]
    picked, tokens = [], 0
    for i in [longest] + [int(i) for i in rng.permutation(rest)]:
        if len(picked) >= n and tokens >= min_tokens:
            break
        picked.append(i)
        tokens += len(finished[i]["tokens"])
    return [finished[i] for i in picked]


def prompts_of(mix: dict, seed: int, vocab: int, seconds: float) -> dict:
    """{request id: prompt} made again from the seed (the load generator's
    records carry lengths, not prompts); closed-loop rounds share prompts."""
    return {r["id"]: r["prompt"]
            for r in traffic.make_requests(mix, seed, vocab, seconds)}


def gap_readings(gaps) -> dict:
    """The two numbers compared, from the sampled requests' per-token
    gaps: the widest (one altered token shows in it) and the mean over all
    the sample's tokens (steady from seed to seed, it tells a lower
    precision from rounding)."""
    if not gaps:
        return {"token_gap": float("inf"), "token_gap_mean": float("inf")}
    flat = np.concatenate(gaps)
    return {"token_gap": float(flat.max()), "token_gap_mean": float(flat.mean())}


def serve_checks(sample, gaps, compiles, limits) -> dict:
    bad = sum(1 for r in sample if r["error"] or r["tokens"] is None
              or len(r["tokens"]) != r["max_new"])
    read = gap_readings(gaps)
    return {
        "token_gap": [read["token_gap"], limits["token_gap"]],
        "token_gap_mean": [read["token_gap_mean"], limits["token_gap_mean"]],
        "sample_short": [float(max(0, int(limits["min_sample_tokens"])
                                   - sum(len(r["tokens"]) for r in sample))), 0],
        "answered": [float(bad), limits["answered"]],
        "compiles_in_window": [float(compiles), 0],
    }


def run(ctx) -> dict:
    config, mix, seed = ctx.config, ctx.traffic, ctx.seed
    served = Served(config, seed, ctx.log)
    family, dims = served.family, served.dims
    served.warm_request(dims["V"])
    trace = ctx.start_trace()
    marks = {}

    def opened(t0):
        marks["t0"] = t0
        ctx.window_opens(t0, trace)

    load = run_load(served, mix, seed, ctx.seconds,
                    float(mix["grace_s"]), on_open=opened)
    m = client_metrics(load, mix["kind"])
    ctx.log(f"load generator: {m['attempted']} requests, {m['failed']} failed, "
            f"{len(m['finished'])} finished in the window, "
            f"{m['tokens_in_window']} tokens in the window; it ran late by "
            f"p95 {m['late_ms_p95']} ms, max {m['late_ms_max']} ms; was "
            f"ready {load['ready_before_start_s']:.2f} s before the start")
    ctx.log("client side: " + json.dumps(
        {k: m[k] for k in CLIENT_METRICS + ("ttft_ms_mean", "ttft_ms_p50",
                                            "n_gaps")}))
    compiles = served.compiles_since_warm()
    peak = device.memory_peak_bytes()
    traced = ctx.finish_trace(trace)
    served.stop()
    del served

    sample = pick_sample(m["finished"], seed, int(mix["check_requests"]),
                         int(ctx.limits["min_sample_tokens"]))
    prompts = prompts_of(mix, seed, dims["V"], ctx.seconds)
    t = time.perf_counter()
    gaps = family.served_gaps(sample, prompts, seed, dims)
    ctx.log(f"reference over {len(sample)} requests, "
            f"{sum(len(r['tokens']) for r in sample)} served tokens, in "
            f"{time.perf_counter() - t:.1f} s; widest gaps "
            f"{[round(float(g.max()), 4) for g in gaps]}")
    checks = serve_checks(sample, gaps, compiles, ctx.limits)
    t0 = marks["t0"]
    return {
        "attempted": m["attempted"], "failed": m["failed"], "checks": checks,
        "memory_peak_bytes": peak,
        "end_to_end": {k: m[k] for k in CLIENT_METRICS},
        "facts": {"window": (t0, t0 + ctx.seconds), "dims": dims,
                  "load": load, "client": m, "traced": traced,
                  "mono_minus_perf": time.monotonic() - time.perf_counter()},
    }
