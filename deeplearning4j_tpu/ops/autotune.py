"""Kernel autotuning layer — block sizes as data, not literals.

Every Pallas kernel in ops/ sizes its grid through this module. Until r8
the knobs were frozen module constants ("swept on v5e once",
BLOCK_Q_MAX = 512 et al.); the ROADMAP "push MFU" item calls for
perf-library discipline per Dragon-Alpha (arXiv 2305.08819): every
kernel variant benchmarked, budgeted, and regression-gated. This module
is the knob half of that loop — `tools/kerneltune.py` is the bench half.

Resolution order for a kernel's block parameters:

1. an active `override(...)` context (tests and the kerneltune sweep
   force candidate variants through the real dispatch);
2. a checked-in tuning-table entry
   (`deeplearning4j_tpu/ops/tuning_table.json`) keyed on
   ``(kernel, T, D, causal, dropout, masked)`` — applied ON TPU only
   (or under ``DL4J_TPU_TUNING=force``), so off-TPU/interpret runs are
   bit-identical to the deterministic fallback;
3. the deterministic heuristics (the pre-r8 constants, now living
   here) — any table miss degrades to exactly the old behavior.

Table schema (version 1)::

    {"version": 1,
     "provenance": {"device_kind": ..., "backend": ..., "date": ...,
                    "tool": "tools/kerneltune.py", ...},
     "entries": {
       "flash_fwd|T512|D64|c1|d0|m0": {
           "block_q": 512, "block_k": 512, "g": 8,
           "best_us": 129.0, "default_us": 263.0},
       ...}}

Entry params are kernel-specific: flash_fwd/flash_bwd take
``block_q``/``block_k``/``g``; flash_fwd_qkv(+_pair)/flash_bwd_qkv
(+_pair) take ``g``; flash_chunk takes ``chunk``; fused_layer_norm takes
``rows``; softmax_xent takes ``block_n``/``block_v`` (caps — the row
count varies per call while the key is (V, d), so the caps feed the same
divisor search the defaults do); decode_attn takes ``block_k`` (the key
block a decode step streams the paged KV cache in — page multiples
dividing the cache capacity S, keyed on (S, head_dim));
decode_attn_q8 takes ``block_k`` too, further constrained to page-size
multiples (the int8 cache's scale grid is per page, so a key block must
cover whole pages); sample takes ``rows`` (the fused sampling kernel's
row block over the [B, V] logits, keyed on (B, V) with the
fused_layer_norm stat-row legality rule); neg_softmax takes ``rows``
(the fused negative-sampling sampled-softmax kernel's row block over
the [B, D] center/context strips, keyed on (B, D) with the same
stat-row rule for its [1, B] positive-score row). Every resolved
value is validated
against the kernel's structural constraints (divisibility, lane tiling,
unroll budget) before use; an invalid entry falls back to the
heuristics rather than producing an uncompilable grid.

Timings in entries are provenance, not configuration — the resolution
functions read only the param fields.

graftlint G016 enforces the inverse contract: Pallas block-size/grid
literals hardcoded outside this module are findings.

Pure stdlib at module level (the tools/ stub-import idiom); jax is
imported lazily inside `table_active` only.
"""

from __future__ import annotations

import json
import os
import threading

# Hardware tile constants (structural, not tunable): the MXU is 128x128,
# the VPU lane width is 128 — every block's minor dim is a multiple of
# LANES and sequence blocks are multiples of BLOCK.
LANES = 128
BLOCK = 128

# ---------------------------------------------------------------- defaults
#
# The deterministic heuristics — the pre-r8 frozen knobs, each with its
# original measurement note. These are the fallback for every table miss
# and the ONLY resolution used off-TPU (bit-identical interpret runs).

# Flash-attention block caps (swept on v5e, r2): larger q/k blocks
# amortize the per-program fixed cost and feed the MXU bigger dots; the
# caps keep scores [bq, bk] f32 and the full-T K/V copies inside VMEM.
DEFAULT_BLOCK_Q_MAX = 512
DEFAULT_BLOCK_K_MAX = 512

# Fused softmax-xent blocks (swept on v5e at N=16384, d=256, V=10240,
# r2+r5): 1024-row blocks x 2048-wide vocab chunks under the 32MB scoped
# limit; wider chunks and smaller row blocks both LOSE.
DEFAULT_XENT_BLOCK_N = 1024
DEFAULT_XENT_BLOCK_V = 2048

# Fused layer-norm row block (r3).
DEFAULT_LN_ROW_BLOCK = 512

# Fused sampling row block (r16): each program reduces a [rows, V]
# logits block to `rows` token ids, so the row block trades program
# count against the f32 score strip's VMEM footprint at wide vocabs.
DEFAULT_SAMPLE_ROW_BLOCK = 256

# Fused negative-sampling sampled-softmax row block (r19): each program
# scores a [rows, D] center strip against its positive row and [rows, K,
# D] negative block, so the row block trades program count against the
# [rows, K, D] negative block's VMEM footprint.
DEFAULT_NEG_SOFTMAX_ROW_BLOCK = 128

# Decode-attention key block (r11): single-query attention against a
# paged KV cache streams the cache in blocks of block_k key positions
# (page multiples) with a running-max/lse merge, and stops at the last
# block a live row can see: a shorter block reads fewer rows past the
# longest live row and costs more loop passes. The cap feeds a divisor
# search over the cache capacity S (which is page-quantized, so
# divisors exist). 128 is the winner of a sweep of 64 / 128 / 256 / 320
# on a v5e (16 slots of 1,280 positions, heads of 128, rows filled to
# 80-380: PERF.md section 6, PR 32); all four lie within 3 %.
DEFAULT_DECODE_BLOCK_K = 128

# Grouped decode attention (`ops/decode_attention.gqa_decode`, PR 37):
# the rows of every key-value head one program instance of the kernel
# holds. A block of all 8 heads at 512 rows of 128 is 2 MB of keys and
# values (2.6 us at a v5e's 819 GB/s against some 0.35 us a grid step;
# 4 MB of VMEM double-buffered); each slot stops at its own last live
# block, so a shorter block reads fewer rows past a slot's length and
# costs more steps. The cap feeds a divisor search over the entry's rows
# (4,096 in a ring, the capacity in a full layer). Not swept yet.
DEFAULT_GQA_BLOCK_K = 512

# Latent attention's prefill chunk (`ops/prefill_attention.prefill_flash`,
# PR 38): a program instance holds a block of a row's chunk queries and a
# block of its context, and expands that block's keys and values from the
# latent rows once for all the block's queries, so a query block of the
# whole 1,024-token chunk expands each key block once. The rows of a
# query block are scored in sub-blocks, and a sub-block that sees no key
# of the block is skipped, and a program holds a group of heads. Each
# block feeds a divisor search; a head count the group does not divide
# takes one head a program. Swept on a v5e at
# 128 heads, 1,024 queries at positions 0 / 1,024 / 3,072 (PERF.md
# section 6, PR 38): keys 512 | 1,024, sub-blocks 128-1,024 rows, 1-16
# heads a program; 512 keys, 512 rows and 4 heads read 1.50 / 2.71 /
# 5.11 ms a layer, 1,024 keys 1.75 / 2.49 / 4.62 (most chunks start at 0).
DEFAULT_PREFILL_BLOCK_Q = 1024
DEFAULT_PREFILL_BLOCK_K = 512
DEFAULT_PREFILL_SUB_ROWS = 512
DEFAULT_PREFILL_HEADS = 4

# Grouped attention's prefill chunk (`ops/prefill_attention.gqa_prefill`,
# PR 40): a program instance holds a block of a row's chunk queries for
# all G query heads of one key-value head, and one block of that head's
# keys and values: of the entry's rows as found (`BLOCK_K` rows of the
# ring or the full entry), then of the chunk's own (`BLOCK_K` keys, in
# lanes of 128). The rows of a query block are scored in sub-blocks; a
# sub-block that sees no key of the block is skipped. Each block feeds a
# divisor search.
DEFAULT_GQA_PREFILL_BLOCK_Q = 1024
DEFAULT_GQA_PREFILL_BLOCK_K = 1024
DEFAULT_GQA_PREFILL_SUB_ROWS = 128

# Kernel-proven chunk-tile lengths for the long-context loop, largest
# first (the single home for the tiling envelope quoted in error
# messages). 8192 is the monolithic kernels' VMEM envelope at
# head_dim <= 128 (0.69 MFU in-model; 15360+ busts VMEM with
# 512-blocks) — the D-aware bound below shrinks the cap as D grows.
CHUNK_TILES = (8192, 4096, 2048, 1024, 512)

# The backward's VMEM working set streams full-tile [T, D] K/V (resp.
# Q/dO) pairs, so the proven tile LENGTH scales inversely with head
# dim: tile * max(D, 128) <= TILE_ELEM_BUDGET keeps the working set at
# or below the measured D=128 envelope (8192 * 128). D=256 caps tiles
# at 4096, D=512 at 2048 — the "D-aware tile bound" tier (ADVICE r5 #2:
# D > 128 long-T previously had no supported path at all).
TILE_ELEM_BUDGET = CHUNK_TILES[0] * 128

ENV_TUNING = "DL4J_TPU_TUNING"  # "force" | "off" | unset (TPU-only)

SCHEMA_VERSION = 1

TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tuning_table.json")

# Params each kernel family may tune; validation rejects anything else.
KERNEL_PARAMS = {
    "flash_fwd": ("block_q", "block_k", "g"),
    "flash_bwd": ("block_q", "block_k", "g"),
    "flash_fwd_qkv": ("g",),
    "flash_bwd_qkv": ("g",),
    "flash_fwd_qkv_pair": ("g",),
    "flash_bwd_qkv_pair": ("g",),
    "flash_chunk": ("chunk",),
    "fused_layer_norm": ("rows",),
    "softmax_xent": ("block_n", "block_v"),
    "decode_attn": ("block_k",),
    "decode_attn_q8": ("block_k",),
    "sample": ("rows",),
    "neg_softmax": ("rows",),
}

# Timing/provenance fields an entry may carry alongside its params.
ENTRY_META_FIELDS = ("best_us", "default_us", "candidates", "source")


def pick_block(n: int, cap: int, base: int = BLOCK) -> int:
    """Largest power-of-two divisor of n up to cap (n % base == 0
    assumed). The shared divisor search of the flash and fused-head
    kernels — and the validator tuned caps feed."""
    b = base
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return min(b, n)


def config_key(kernel: str, T: int, D: int, *, causal: bool = False,
               dropout: bool = False, masked: bool = False) -> str:
    """The table key: kernel|T|D|causal|dropout|masked. T and D are the
    kernel's own dims (flash: sequence x head_dim; fused_layer_norm:
    rows x feature dim; softmax_xent: vocab x feature dim)."""
    return (f"{kernel}|T{int(T)}|D{int(D)}|c{int(bool(causal))}"
            f"|d{int(bool(dropout))}|m{int(bool(masked))}")


def parse_key(key: str) -> dict:
    """Inverse of config_key — used by kerneltune/benchdiff to name
    entries. Raises ValueError on a malformed key."""
    parts = key.split("|")
    if len(parts) != 6:
        raise ValueError(f"malformed tuning key {key!r}")
    kernel, t, d, c, dr, m = parts
    if not (t[:1] == "T" and d[:1] == "D" and c[:1] == "c"
            and dr[:1] == "d" and m[:1] == "m"):
        raise ValueError(f"malformed tuning key {key!r}")
    return {"kernel": kernel, "T": int(t[1:]), "D": int(d[1:]),
            "causal": bool(int(c[1:])), "dropout": bool(int(dr[1:])),
            "masked": bool(int(m[1:]))}


def validate_table(table) -> list[str]:
    """Schema check -> list of problems (empty = valid). Used by the
    loader (a broken checked-in table must fail loudly at load, not as
    a Mosaic error mid-compile), kerneltune before writing, and the
    round-trip tests."""
    problems = []
    if not isinstance(table, dict):
        return ["table is not a JSON object"]
    if table.get("version") != SCHEMA_VERSION:
        problems.append(f"version {table.get('version')!r} != "
                        f"{SCHEMA_VERSION}")
    entries = table.get("entries")
    if not isinstance(entries, dict):
        return problems + ["missing 'entries' object"]
    for key, entry in entries.items():
        try:
            cfg = parse_key(key)
        except ValueError as exc:
            problems.append(str(exc))
            continue
        allowed = KERNEL_PARAMS.get(cfg["kernel"])
        if allowed is None:
            problems.append(f"{key}: unknown kernel {cfg['kernel']!r}")
            continue
        if not isinstance(entry, dict):
            problems.append(f"{key}: entry is not an object")
            continue
        for field, value in entry.items():
            if field in ENTRY_META_FIELDS:
                continue
            if field not in allowed:
                problems.append(f"{key}: param {field!r} not tunable "
                                f"for {cfg['kernel']} (allowed: "
                                f"{list(allowed)})")
            elif not isinstance(value, int) or value < 1:
                problems.append(f"{key}: param {field!r} must be a "
                                f"positive int, got {value!r}")
    return problems


# ------------------------------------------------------------ table state

_lock = threading.Lock()
_cache: dict = {"path": None, "table": None}
_overrides: list[dict] = []  # innermost last; each {key -> params}
_resolved: dict[str, str] = {}  # key -> source of the last lookup


def load_table(path: str | None = None) -> dict:
    """Load (and cache) the tuning table. A missing file is an empty
    table (every lookup falls back); a malformed file raises at load."""
    path = path or TABLE_PATH
    with _lock:
        if _cache["path"] == path and _cache["table"] is not None:
            return _cache["table"]
        if not os.path.exists(path):
            table = {"version": SCHEMA_VERSION, "provenance": {},
                     "entries": {}}
        else:
            with open(path) as fh:
                table = json.load(fh)
            problems = validate_table(table)
            if problems:
                raise ValueError(
                    f"invalid tuning table {path}: " + "; ".join(problems))
        _cache["path"] = path
        _cache["table"] = table
        return table


def reload_table(path: str | None = None) -> dict:
    """Drop the cache and re-read (kerneltune just rewrote the file)."""
    with _lock:
        _cache["path"] = None
        _cache["table"] = None
    return load_table(path)


def table_active() -> bool:
    """Whether table entries apply. Off-TPU the answer is no (interpret
    runs stay bit-identical to the deterministic fallback — the tier-1
    contract); DL4J_TPU_TUNING=force/off overrides for tests and
    debugging."""
    env = os.environ.get(ENV_TUNING, "").lower()
    if env in ("force", "1", "on"):
        return True
    if env in ("off", "0"):
        return False
    try:
        import jax
        return jax.default_backend() == "tpu"
    except Exception:  # jax absent (tools stub imports): fallback only
        return False


class override:
    """Context manager forcing explicit params for a kernel config —
    the hook kerneltune times candidates through and the parity tests
    pin variants with. Matches by exact config key, or by bare kernel
    name for every config of that kernel::

        with autotune.override({"flash_fwd": {"block_q": 256}}):
            flash_attention(q, k, v, causal=True)
    """

    def __init__(self, mapping: dict):
        self.mapping = dict(mapping)

    def __enter__(self):
        _overrides.append(self.mapping)
        return self

    def __exit__(self, *exc):
        _overrides.remove(self.mapping)
        return False


def lookup(kernel: str, T: int, D: int, *, causal: bool = False,
           dropout: bool = False, masked: bool = False) -> dict | None:
    """The raw entry for a config (override > table > None). Callers go
    through the typed resolution functions below, which validate."""
    key = config_key(kernel, T, D, causal=causal, dropout=dropout,
                     masked=masked)
    for mapping in reversed(_overrides):
        forced = mapping.get(key, mapping.get(kernel))
        if forced is not None:
            _resolved[key] = "override"
            return forced
    entry = (load_table()["entries"].get(key) if table_active()
             else None)
    _resolved[key] = "table" if entry else "default"
    return entry


def resolved_keys() -> dict:
    """Every config key a kernel has looked up in this process -> where
    its params came from ("override", "table", or "default" = the swept
    heuristics). What a run on the chip prints to say which tuning-table
    entries it really used."""
    return dict(_resolved)


# ------------------------------------------------------------- resolution

def _valid_block(b, T) -> bool:
    return (isinstance(b, int) and b >= BLOCK and b % BLOCK == 0
            and T % b == 0)


def flash_blocks(T: int, D: int, *, causal: bool, dropout: bool,
                 masked: bool, kernel: str = "flash_fwd") -> tuple[int, int]:
    """(block_q, block_k) for the monolithic flash kernels. Tuned values
    must be lane-tile multiples dividing T; anything else falls back to
    the swept 512-caps divisor search."""
    e = lookup(kernel, T, D, causal=causal, dropout=dropout, masked=masked)
    if e:
        bq, bk = e.get("block_q"), e.get("block_k")
        if _valid_block(bq, T) and _valid_block(bk, T):
            return bq, bk
    return (pick_block(T, DEFAULT_BLOCK_Q_MAX),
            pick_block(T, DEFAULT_BLOCK_K_MAX))


def flash_g(kernel: str, BH: int, T: int, D: int, *, causal: bool,
            dropout: bool, masked: bool) -> int | None:
    """Tuned per-program G-batching for a flash kernel, or None (caller
    falls back to the VMEM-budget heuristic). A tuned G must divide the
    batch*head count it is applied to."""
    e = lookup(kernel, T, D, causal=causal, dropout=dropout, masked=masked)
    if e:
        g = e.get("g")
        if isinstance(g, int) and g >= 1 and BH % g == 0:
            return g
    return None


def max_tile_for_dim(D: int | None) -> int:
    """Largest kernel-proven chunk tile for a head dim: the D-aware
    bound (tile * max(D, 128) <= TILE_ELEM_BUDGET). None means the
    caller has no head-dim information — treated as the D <= 128
    envelope (the pre-r8 behavior)."""
    if not D or D <= LANES:
        return CHUNK_TILES[0]
    for c in CHUNK_TILES:
        if c * D <= TILE_ELEM_BUDGET:
            return c
    return 0


def chunk_tile(T: int, D: int | None, *, causal: bool, dropout: bool,
               masked: bool, fits) -> int | None:
    """Tuned chunk length for the long-context loop, or None. `fits` is
    the caller's structural predicate (divisibility + unroll budget) so
    the validation rule lives with the loop, not here."""
    e = lookup("flash_chunk", T, D or 0, causal=causal, dropout=dropout,
               masked=masked)
    if e:
        c = e.get("chunk")
        if (isinstance(c, int) and c in CHUNK_TILES
                and c <= max_tile_for_dim(D) and fits(c)):
            return c
    return None


def decode_block(S: int, D: int) -> int:
    """Key-block length in which attention walks a cache of capacity S
    (ops/decode_attention.py): the walk stops at the last block a live
    query can see, so a shorter block bounds the live rows more finely
    and costs more loop passes. The tuned value must
    divide S (the cache capacity is page-quantized, so page-multiple
    candidates always divide); any miss falls back to the largest
    divisor of S within the swept cap — deterministic, so off-TPU runs
    (table inactive) are bit-identical to the fallback."""
    e = lookup("decode_attn", S, D)
    if e:
        bk = e.get("block_k")
        if isinstance(bk, int) and 1 <= bk <= S and S % bk == 0:
            return bk
    if S <= DEFAULT_DECODE_BLOCK_K:
        return S
    for bk in range(DEFAULT_DECODE_BLOCK_K, 0, -1):
        if S % bk == 0:
            return bk
    return S  # unreachable: 1 divides S


def decode_block_q8(S: int, D: int, page_size: int) -> int:
    """Key-block length for the int8 quantized decode-attention variant
    (ops/decode_attention.py). Same contract as `decode_block` with one
    extra structural rule: the block must be a multiple of the cache
    page size, because dequantization broadcasts one per-page scale
    across each page inside a block — a block may not split a page.
    The cache capacity S is page-quantized, so page-multiple divisors
    always exist; the fallback takes the largest one within the swept
    cap (deterministic, bit-identical off-TPU)."""
    ps = max(1, int(page_size))
    e = lookup("decode_attn_q8", S, D)
    if e:
        bk = e.get("block_k")
        if (isinstance(bk, int) and 1 <= bk <= S and S % bk == 0
                and bk % ps == 0):
            return bk
    if S <= DEFAULT_DECODE_BLOCK_K:
        return S
    cap = DEFAULT_DECODE_BLOCK_K // ps * ps
    for bk in range(max(cap, ps), 0, -ps):
        if S % bk == 0:
            return bk
    return S  # unreachable: S is a page multiple, so ps divides S


def sample_rows(B: int, V: int) -> int:
    """Row block for the fused sampling kernel (ops/fused_sampling.py).
    The [1, B] token row uses (1, bn) blocks, legal only when bn is a
    lane-tile multiple or the whole batch — the fused_layer_norm
    stat-row rule, enforced for tuned values too."""
    e = lookup("sample", B, V)
    if e:
        bn = e.get("rows")
        if (isinstance(bn, int) and bn >= 8 and B % bn == 0
                and (bn % LANES == 0 or bn == B)):
            return bn
    b = 8
    while b * 2 <= DEFAULT_SAMPLE_ROW_BLOCK and B % (b * 2) == 0:
        b *= 2
    return b


def neg_softmax_rows(B: int, D: int) -> int:
    """Row block for the fused negative-sampling sampled-softmax kernel
    (ops/fused_neg_softmax.py). Its [1, B] positive-score row uses
    (1, bn) blocks, so the same stat-row legality rule as `sample_rows`
    applies: bn a lane-tile multiple or the whole batch."""
    e = lookup("neg_softmax", B, D)
    if e:
        bn = e.get("rows")
        if (isinstance(bn, int) and bn >= 8 and B % bn == 0
                and (bn % LANES == 0 or bn == B)):
            return bn
    b = 8
    while b * 2 <= DEFAULT_NEG_SOFTMAX_ROW_BLOCK and B % (b * 2) == 0:
        b *= 2
    return b


def ln_rows(N: int, C: int) -> int:
    """Row block for fused_layer_norm. The [1, N] stat rows use (1, bn)
    blocks, legal only when bn is a lane-tile multiple or the whole row
    dim — the same rule supports() gates on, enforced here for tuned
    values too."""
    e = lookup("fused_layer_norm", N, C)
    if e:
        bn = e.get("rows")
        if (isinstance(bn, int) and bn >= 8 and N % bn == 0
                and (bn % LANES == 0 or bn == N)):
            return bn
    b = 8
    while b * 2 <= DEFAULT_LN_ROW_BLOCK and N % (b * 2) == 0:
        b *= 2
    return b


def xent_blocks(N: int, d: int, V: int) -> tuple[int, int]:
    """(block_n, block_v) for the fused softmax-xent head. Tuned values
    are CAPS (the key is (V, d) while N varies per call): block_n feeds
    the same divisor search as the default, block_v is floored to a
    lane multiple and capped at the vocab."""
    e = lookup("softmax_xent", V, d)
    bn_cap, bv_cap = DEFAULT_XENT_BLOCK_N, DEFAULT_XENT_BLOCK_V
    if e:
        tbn, tbv = e.get("block_n"), e.get("block_v")
        if isinstance(tbn, int) and tbn >= BLOCK and tbn % BLOCK == 0:
            bn_cap = tbn
        if isinstance(tbv, int) and tbv >= LANES and tbv % LANES == 0:
            bv_cap = tbv
    bn = pick_block(N, bn_cap)
    # VMEM working set scales with d*bv: shrink the chunk as the feature
    # dim grows (the swept envelope is bn=1024 x bv=2048 at d=256);
    # floor at 512 lanes, cap at the swept width and the vocab itself
    bv = max(512, min(bv_cap, (bv_cap * 256 // d) // LANES * LANES))
    return bn, min(V, bv)
