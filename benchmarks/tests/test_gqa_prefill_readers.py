"""The reader of grouped attention's prefill kernel (`gqa_prefill_roofline`)
on a made-up trace: three prefill programs, two of them with the kernel's
eight events, a decode program between them, and the `prefill_chunk`
spans (with their `dispatch` children) that launched them, laid on the
trace's clock through the sync mark; on a program without the kernel (the
parent's), in another family's cell and on an untraced run it reads
nothing."""
import json

import numpy as np
import pytest
from harness import device, spec
from harness.spans import SpanLog

# Trinity's counts: 48 query heads of 128, six window layers of 4,096 and
# two full ones
SLIDING = (True, True, True, False, True, True, True, False)
DIMS = {"Hq": 48, "Hk": 8, "d": 128, "L": 8, "window": 4096,
        "sliding": SLIDING}
CONFIG = {"model_type": "afmoe"}
KERNEL = 'custom_call_target="tpu_custom_call"'
SYNC_S, SYNC_NS = 900.0, 2e9      # host perf_counter and trace ns of the mark
MS = 1e6
# (kind, dispatch ms, module start ms, duration ms, kernel ms a layer,
#  start, n_real)
PROGRAMS = (("prefill_chunk", 1.0, 5.0, 30.0, 0.3, 0, 1024),
            ("decode_step", 6.0, 36.0, 12.0, 0.0, 0, 0),
            ("prefill_chunk", 34.0, 49.0, 40.0, 1.1, 8192, 900),
            ("prefill_chunk", 50.0, 90.0, 45.0, 0.0, 4096, 1024))


def _facts(kernel=True, **over):
    log, modules, ops = SpanLog(), [], []
    for i, (kind, disp, start, dur, kern, first, n_real) in \
            enumerate(PROGRAMS):
        t0 = SYNC_S + disp / 1e3
        log.spans.append(("dispatch", t0, t0 + 1e-3,
                          {"t0": t0, "t1": t0 + 1e-3, "parent_id": f"s{i}"}))
        fields = {"t0": t0, "t1": t0 + 3e-3, "span_id": f"s{i}"}
        if kind == "prefill_chunk":
            fields.update(start=first, n_real=n_real, bucket=[1, 1024])
        log.spans.append((kind, t0, t0 + 3e-3, fields))
        a = SYNC_NS + start * MS
        name = "jit_counted_prefill(1)" if kind == "prefill_chunk" \
            else "jit_counted_step(2)"
        modules.append((name, a, dur * MS, ""))
        ops.append((f"fusion.{i}", a, 2 * MS, "kOutput"))
        for layer in range(8 if kern and kernel else 0):
            ops.append((f"gqa_prefill.{layer + 1}", a + (3 + 3 * layer) * MS,
                        kern * MS, KERNEL))
    traced = {"chips": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}],
              "t_on": SYNC_S, "t_off": SYNC_S + 0.3, "window_s": 0.3,
              "t_sync": SYNC_S, "sync_ns": SYNC_NS}
    facts = {"config": CONFIG, "dims": DIMS, "peaks": device.PEAKS["TPU v5e"],
             "traced": traced, "spans": log}
    facts.update(over)
    return facts


def _keys(first, n):
    """Keys the queries at first .. first + n - 1 see over the 8 layers."""
    ctx = np.arange(first, first + n) + 1.0
    return float(np.sum(2 * ctx + 6 * np.minimum(ctx, 4096)))


def test_the_reader_of_the_grouped_prefill_kernel_on_a_made_up_trace():
    roof = spec.layer_reader("gqa_prefill_roofline")(_facts())
    flops = 4 * 48 * 128 * (_keys(0, 1024) + _keys(8192, 900))
    assert roof == pytest.approx(
        100.0 * flops / 197e12 / (8 * (0.3 + 1.1) * 1e-3))
    assert 0 < roof < 100
    assert json.dumps(roof)


def test_the_parent_another_family_and_an_untraced_run_read_nothing():
    read = spec.layer_reader("gqa_prefill_roofline")
    assert read(_facts(kernel=False)) is None
    assert read(_facts(config={"model_type": "gpt2"},
                       dims={"d": 2048, "H": 16, "L": 24, "V": 50257})) is None
    assert read(_facts(traced=None)) is None
    assert read(_facts(spans=None)) is None
