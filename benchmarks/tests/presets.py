"""Tiny presets for the CPU rehearsals. They live with the tests, never in
benchmarks/configs: a cell runs published widths only."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MODEL = {"model_type": "gpt2", "n_embd": 64, "n_head": 2, "n_layer": 2,
              "n_inner": 128,
              "vocab_size": 256, "n_positions": 128,
              "layer_norm_epsilon": 1e-5, "compute_dtype": "bfloat16",
              "param_dtype": "float32"}
GPT2 = {"model_type": "gpt2"}       # spec.family_of(GPT2): the family module
TINY_TRAIN = dict(TINY_MODEL, training={
    "remat": True, "updater": "adam", "learning_rate": 3e-4, "adam_b1": 0.9,
    "adam_b2": 0.999, "adam_eps": 1e-8, "dropout": 0.0})
TINY_SERVE = dict(TINY_MODEL, seeded_weights={
    "embed_gain": 12.0, "qk_gain": 2.5, "resid_gain": 0.3, "head_gain": 4.0},
    deployment={
    "slots": 4, "max_new_tokens": 16, "page_size": 16, "kv_dtype": "f32",
    "prefill_seq_lens": [16, 32], "replicas": 1, "max_queue": 64})
TRAIN_MIX = {"kind": "train", "batch": 4, "seq_len": 32,
             "trace_start_s": 0.3, "trace_seconds": 1.0}
OPEN_MIX = {"kind": "serve_open", "rate_per_s": 6.0, "shape_seed": 1,
            "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                           "min": 4, "max": 32},
            "max_new_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                               "min": 2, "max": 16},
            "grace_s": 20, "check_requests": 3, "trace_start_s": 0.5,
            "trace_seconds": 1.0}
CLOSED_MIX = {"kind": "serve_closed", "clients": 6, "requests_per_client": 2,
              "shape_seed": 1, "prompt_len": {"dist": "uniform", "min": 4,
                                              "max": 32},
              "max_new_tokens": {"dist": "fixed", "value": 16}, "grace_s": 20,
              "check_requests": 2, "trace_start_s": 0.5, "trace_seconds": 1.0}
# limits of the tiny presets, between the readings of the same CPU runs
# (seeds 100..111): sound runs read grad_norm <= 0.029, grad_proj <= 0.031,
# change_norm <= 0.023; the float8 control reads grad_proj 0.052..0.075 on
# seeds 100..103; the faults read 0.3 and more. Serving, over ALL requests
# of both mixes on seven seeds (PR 30): sound runs read token_gap 0..0.131
# (one prompt in six reads 0.05..0.13, so a limit of 0.05 failed whenever
# the sample drew it) and token_gap_mean <= 0.0013; the altered token reads
# token_gap 10.2..14.1 on eight runs
TRAIN_LIMITS = {"grad_norm": 0.1, "grad_proj": 0.045, "change_norm": 0.1}
LOOSE_TRAIN_LIMITS = {"grad_norm": 1.0, "grad_proj": 1.0, "change_norm": 1.0}
SERVE_LIMITS = {"token_gap": 0.5, "token_gap_mean": 0.005, "answered": 0,
                "min_sample_tokens": 4}


def bench_with(cell_name: str, like: str) -> dict:
    """BENCHMARK.json with one more cell that reports what `like` reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench = copy.deepcopy(bench)
    src = next(c for c in bench["workloads"] if c["name"] == like)
    bench["workloads"].append(dict(src, name=cell_name))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell_name)
    return bench
