"""The join of device programs to the spans that dispatched them
(harness/programs.py), the reduction of their ops to regions
(harness/regions.py) and the six readers of them, on made-up traces: the
loop running one program ahead, chunk and step alternating, the first
program dispatched before the trace started, one module missing from the
trace, and a program without a `regions` table (the parent's)."""
import pytest
from harness import programs, regions, spec
from harness.spans import SpanLog

SYNC_S, SYNC_NS = 700.0, 5e9      # host perf_counter and trace ns of the mark
MS = 1e6
KERNEL = 'custom_call_target="tpu_custom_call"'
# (kind, dispatch, module start, duration in ms, in the trace, n_real)
PROGRAMS = (
    ("decode_step", -4.0, 1.0, 5.0, True, 0),     # dispatched before the trace
    ("prefill_chunk", 3.0, 6.0, 10.0, True, 1000),
    ("decode_step", 8.0, 16.0, 5.0, True, 0),
    ("prefill_chunk", 18.0, 21.0, 10.0, False, 700),   # lost from the trace
    ("decode_step", 24.0, 31.0, 5.0, True, 0),
    ("prefill_chunk", 33.0, 36.0, 8.0, True, 300),
)
MODULE = {"decode_step": "jit_counted_step(11)",
          "prefill_chunk": "jit_counted_prefill(12)"}
DECODE_TABLE = {"while.1": "moe/experts", "dot.2": "moe/experts",
                "fusion.3": "attention", "gqa_decode.4": "attention",
                "add.5": "norm"}
CHUNK_TABLE = {"while.1": "moe", "fusion.3": "attention/cache_write",
               "add.5": "head"}


def _ops(kind, start):
    """A program's ops from `start` ms: a loop of 2 ms holding two body
    ops of 0.5 ms, a fusion of 1 ms, a kernel of 1 ms (decode) or none,
    an op no table names (0.25 ms), a norm of 0.25 ms; idle between."""
    a = SYNC_NS + start * MS
    ops = [("while.1", a, 2 * MS, ""), ("dot.2", a + 0.2 * MS, 0.5 * MS, ""),
           ("dot.2", a + 1.0 * MS, 0.5 * MS, ""),
           ("fusion.3", a + 2.0 * MS, 1 * MS, "kOutput"),
           ("copy.9", a + 3.5 * MS, 0.25 * MS, ""),
           ("add.5", a + 3.75 * MS, 0.25 * MS, "kLoop")]
    if kind == "decode_step":
        ops.append(("gqa_decode.4", a + 4.0 * MS, 1 * MS, KERNEL))
    return ops


def _facts(tables=True, shift_ms=0.0):
    log, modules, ops = SpanLog(), [], []
    for i, (kind, disp, start, dur, traced, n_real) in enumerate(PROGRAMS):
        t0 = SYNC_S + disp / 1e3
        sid = f"s{i}"
        log.spans.append(("dispatch", t0, t0 + 1e-3,
                          {"t0": t0, "t1": t0 + 1e-3, "parent_id": sid}))
        fields = {"t0": t0, "t1": t0 + 3e-3, "span_id": sid,
                  "program": i + 1}
        if kind == "prefill_chunk":
            fields.update(n_real=n_real, bucket=[1, 1024])
        log.spans.append((kind, t0, t0 + 3e-3, fields))
        if traced:
            modules.append((MODULE[kind], SYNC_NS + (start + shift_ms) * MS,
                            dur * MS, ""))
            ops.extend((n, a + shift_ms * MS, d, x)
                       for n, a, d, x in _ops(kind, start))
    if tables:
        log.events.append(("regions", SYNC_S - 9, {
            "entry": "decode", "shape": [4, 64], "module": "jit_counted_step",
            "ops": DECODE_TABLE}))
        log.events.append(("regions", SYNC_S - 9, {
            "entry": "prefill", "shape": [1, 1024],
            "module": "jit_counted_prefill", "ops": CHUNK_TABLE}))
        log.events.append(("regions", SYNC_S - 9, {
            "entry": "prefill", "shape": [1, 512],
            "module": "jit_counted_prefill", "ops": {"add.5": "embed"}}))
    traced = {"chips": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}],
              "t_on": SYNC_S, "t_off": SYNC_S + 0.5, "window_s": 0.5,
              "t_sync": SYNC_S, "sync_ns": SYNC_NS}
    return {"traced": traced, "spans": log, "window": (SYNC_S - 5, SYNC_S + 20)}


def test_each_module_meets_the_span_that_dispatched_it():
    joined = programs.join(_facts())
    got = [(p["kind"], p["span"]["program"]) for p in joined["programs"]]
    assert got == [("decode_step", 1), ("prefill_chunk", 2),
                   ("decode_step", 3), ("decode_step", 5),
                   ("prefill_chunk", 6)]
    assert joined["matched"] == 5 and joined["unmatched"] == 0
    assert joined["min_lag_s"] == pytest.approx(3e-3)
    # the modules' clock 0.05 ms early is within the tolerance; 0.5 ms
    # early pairs a module with a span already taken, or none
    early = programs.join(_facts(shift_ms=-0.05))
    assert early["matched"] == 5
    assert early["min_lag_s"] == pytest.approx(2.95e-3)
    late = programs.join(_facts(shift_ms=-5.5))
    assert late["unmatched"] >= 1


def test_regions_add_up_to_each_programs_busy_time():
    """A loop keeps what its body leaves; an op no table names is
    `other`; a chunk reads the table of its own bucket."""
    progs = regions.programs(_facts())
    assert [p["kind"] for p in progs] == ["decode_step", "prefill_chunk",
                                          "decode_step", "decode_step",
                                          "prefill_chunk"]
    step, chunk = progs[0], progs[1]
    assert step["busy_s"] == pytest.approx(4.5e-3)
    assert step["regions"] == pytest.approx({
        "moe": 2e-3, "attention": 2e-3, "other": 0.25e-3, "norm": 0.25e-3})
    assert chunk["busy_s"] == pytest.approx(3.5e-3)
    assert chunk["regions"] == pytest.approx({
        "moe": 1e-3, "other": 1.25e-3, "attention": 1e-3, "head": 0.25e-3})
    for p in progs:
        assert sum(p["regions"].values()) == pytest.approx(p["busy_s"])
    assert regions.breakdown(_facts())[0] == ["decode/moe",
                                              pytest.approx(6e-3)]


def test_the_six_readers_on_a_made_up_trace():
    facts = _facts()
    read = {n: spec.layer_reader(n)(facts) for n in (
        "decode_device_ms", "prefill_device_ms_per_ktok",
        "decode_attention_ms", "prefill_attention_ms_per_ktok",
        "decode_moe_ms", "prefill_moe_ms_per_ktok")}
    assert read == pytest.approx({
        "decode_device_ms": 4.5,
        # two chunks seen (the lost one is not counted): 1,000 + 300 tokens
        "prefill_device_ms_per_ktok": 2 * 3.5 / 1.3,
        "decode_attention_ms": 2.0,
        "prefill_attention_ms_per_ktok": 2 * 1.0 / 1.3,
        "decode_moe_ms": 2.0,
        "prefill_moe_ms_per_ktok": 2 * 1.0 / 1.3})


def test_a_program_without_tables_reads_its_device_time_alone():
    """The parent records no `regions` table: the two device readers read
    what they read with one, the four region readers nothing; an
    untraced run reads nothing at all."""
    with_tables, without = _facts(), _facts(tables=False)
    for name in ("decode_device_ms", "prefill_device_ms_per_ktok"):
        read = spec.layer_reader(name)
        assert read(without) == pytest.approx(read(with_tables))
        assert read({"traced": None, "spans": None}) is None
    for name in ("decode_attention_ms", "prefill_attention_ms_per_ktok",
                 "decode_moe_ms", "prefill_moe_ms_per_ktok"):
        assert spec.layer_reader(name)(_facts(tables=False)) is None
    assert regions.breakdown(without) == [
        ["decode/other", pytest.approx(3 * 4.5e-3)],
        ["prefill/other", pytest.approx(2 * 3.5e-3)]]
