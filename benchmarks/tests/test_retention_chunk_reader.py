"""The reader of power retention's prefill kernel
(`retention_chunk_roofline`) on a recorded trace, in test_brumby.py's
way: three prefill programs at Brumby-14B's published sizes, two with the
kernel's eight events (one a chunk that starts a sequence, one a chunk
further on), a decode program between them, and the `prefill_chunk`
spans (with their `dispatch` children) that launched them, laid on the
trace's clock through the sync mark; on a program without the kernel
(the parent's), in another family's cell and on an untraced run it reads
nothing."""
import json

import presets
import pytest
from harness import device, spec
from harness.spans import SpanLog

CELL = "serve_brumby14b_l8_longdoc"
KERNEL = 'custom_call_target="tpu_custom_call"'
SYNC_S, SYNC_NS = 900.0, 2e9      # host perf_counter and trace ns of the mark
MS = 1e6
# (kind, dispatch ms, module start ms, duration ms, kernel ms a layer,
#  start, n_real)
PROGRAMS = (("prefill_chunk", 1.0, 5.0, 40.0, 1.0, 0, 1024),
            ("decode_step", 6.0, 46.0, 23.0, 0.0, 0, 0),
            ("prefill_chunk", 44.0, 70.0, 45.0, 1.2, 1024, 700),
            ("prefill_chunk", 71.0, 116.0, 50.0, 0.0, 2048, 1024))


def published():
    bench = spec.load_benchmark()
    return spec.config_of(bench, spec.cell_of(bench, CELL))


def _facts(kernel=True, **over):
    config = published()
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
            ops.append((f"retention_chunk.{layer + 1}",
                        a + (3 + 4 * layer) * MS, kern * MS, KERNEL))
    traced = {"chips": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}],
              "t_on": SYNC_S, "t_off": SYNC_S + 0.3, "window_s": 0.3,
              "t_sync": SYNC_S, "sync_ns": SYNC_NS}
    facts = {"config": config, "dims": spec.family_of(config).dims_of(config),
             "peaks": device.PEAKS["TPU v5e"], "traced": traced, "spans": log}
    facts.update(over)
    return facts


def test_the_reader_of_the_retention_chunk_kernel_on_a_recorded_trace():
    """40 query and 8 key-value heads of 128, 8 layers: a token's state
    update is 2 x 8 x 129 x 8,256 operations a layer, its read-out 2 x 40
    x 129 x 8,256; the first chunk starts a sequence and counts no
    read-out."""
    roof = spec.layer_reader("retention_chunk_roofline")(_facts())
    per_head = 2 * 129 * 8256 * 8
    ops = 1024 * 8 * per_head + 700 * (8 + 40) * per_head
    assert roof == pytest.approx(
        100.0 * ops / 197e12 / (8 * (1.0 + 1.2) * 1e-3))
    assert 0 < roof < 100
    assert json.dumps(roof)


def test_the_parent_another_family_and_an_untraced_run_read_nothing():
    read = spec.layer_reader("retention_chunk_roofline")
    assert read(_facts(kernel=False)) is None
    assert read(_facts(config=presets.GPT2,
                       dims={"d": 2048, "H": 16, "L": 24, "V": 50257})) is None
    assert read(_facts(traced=None)) is None
    assert read(_facts(spans=None)) is None
