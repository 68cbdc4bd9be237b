"""Page-block KV-cache accounting on the bucket lattice.

The generation engine's device cache is ONE static allocation per
replica — ``[n_slots, capacity, H, D]`` per attention layer — because a
jitted decode step needs a fixed shape to keep the zero-retrace promise.
What varies per request is how much of a slot's row it actually earns:
this module is the page-granular accounting overlay on that static
allocation.

* Capacities are QUANTIZED to the ``(max_seqlen_bucket, page_size)``
  grid: a slot's key budget is ``quantize(prompt_bucket + max_new,
  page_size)`` — never a raw request length — so every shape the jit
  sees is a lattice point and neither prefill nor decode ever retraces.
* A per-replica ``PagePool`` holds the page budget. Admission reserves a
  request's worst-case pages (its quantized prompt + output budget) up
  front; completion (or failure) releases them. Reserving up front means
  exhaustion can ONLY happen at admission — a mid-decode slot never
  discovers it has nowhere to write — so the failure mode is a graceful
  queue/503 at the front door, not a crash (tier-1:
  tests/test_generation.py page-pool exhaustion).
* Occupancy is on the record: the pool tracks pages in use and the
  high-water mark, and the engine emits a ``page_pool`` telemetry event
  on every reserve/release — the ``serving_generate_page_occupancy``
  headline (lower is better: the same traffic served with fewer
  resident pages is more HBM left for replicas) reconstructs from those
  events alone.

* What a slot costs is read from the attention layers' OWN cache specs
  (nn/decode.cache_specs: {layer: {array: (shape of one slot, dtype
  name)}}): keys and values in the net's compute dtype, int8 codes plus
  one f32 scale per (page, head) under ``kv_dtype="int8"``, one latent
  row a position for a latent-attention layer, a STATE of fixed size
  for a retention layer (an array whose spec carries a third entry,
  "slot": it does not grow with the capacity and is billed to the slot,
  never to its positions), a RING of rows for an attention layer with a
  window (an array whose spec carries the positions it holds, fewer
  than the capacity: it is billed to those), and for a layer inside a
  loop one block of its arrays a pass (a pass axis first: a slot's
  bytes are passes x rows x layers). `bytes_per_slot` is the single home for that
  arithmetic: it bills exactly the arrays the device holds, and the
  replay artifact's ``slots_per_hbm_byte`` uplift row (gate: >= 1.8x)
  is computed from it, not re-derived ad hoc.

Pure stdlib: importable under the graftlint AST stage's no-jax stubs.
"""

from __future__ import annotations

import threading

DEFAULT_PAGE_SIZE = 16

KV_DTYPES = ("f32", "int8")


def validate_kv_dtype(kv_dtype: str) -> str:
    """The serving cache dtype knob ('f32' | 'int8'), validated once at
    the engine front door so a typo fails at construction, not as a
    shape error mid-replay."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    return kv_dtype


_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _nbytes(shape, dtype: str) -> int:
    n = _ITEMSIZE[dtype]
    for dim in shape:
        n *= int(dim)
    return n


def bytes_per_slot(cache_specs: dict) -> int:
    """HBM bytes one decode slot's cache costs across all layers: the
    bytes of every array of `cache_specs` (nn/decode.py: {layer: {array:
    (shape of one slot, dtype name[, "slot"])}}), rows and states alike,
    which is what `init_cache` allocates a slot of."""
    return sum(_nbytes(shape, dtype) for arrays in cache_specs.values()
               for shape, dtype, *_per in arrays.values())


def _kinds(cache_specs: dict, per_slot: bool, capacity: int = 1) -> dict:
    """{array name: bytes over all layers that keep such an array}, of
    the arrays marked "slot" (`per_slot`: bytes a slot) or of the others
    (bytes a position the array holds: `capacity` of them, or the number
    its spec gives)."""
    out: dict = {}
    for arrays in cache_specs.values():
        for name, (shape, dtype, *per) in arrays.items():
            if (per == ["slot"]) == per_slot:
                held = 1 if per_slot else (per[0] if per else capacity)
                out[name] = out.get(name, 0) + _nbytes(shape, dtype) / held
    return out


def row_kinds(cache_specs: dict, capacity: int) -> dict:
    """{array name: bytes one position holds over all layers that keep
    such an array}: the kinds of row in the cache ("k", "v"; "ckv", "kpe"
    for a latent row; "k_win", "v_win" for a window layer's ring) for
    the engine's `meta` event and /stats. An array with fewer entries
    than positions (a page's scale) is billed to the positions it
    covers; a ring to the positions IT holds (`window_kinds`), whatever
    the capacity; an array marked "slot" (a state) is no row and is left
    to `slot_kinds`."""
    return {k: round(v, 3)
            for k, v in _kinds(cache_specs, False, capacity).items()}


def window_kinds(cache_specs: dict) -> dict:
    """{array name: rows a slot} of the row arrays whose spec says that
    they hold fewer positions than the capacity: the rings."""
    return {name: int(per[0]) for arrays in cache_specs.values()
            for name, (_shape, _dtype, *per) in arrays.items()
            if per and per != ["slot"]}


def slot_kinds(cache_specs: dict) -> dict:
    """{array name: bytes one SLOT holds over all layers that keep such
    an array} for the arrays marked "slot": a state of fixed size ("s",
    "z" of a retention layer), whatever the capacity."""
    return {k: int(v) for k, v in _kinds(cache_specs, True).items()}


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages covering `n_tokens` key slots (ceil)."""
    if n_tokens <= 0:
        return 0
    return -(-int(n_tokens) // int(page_size))


def quantize(n_tokens: int, page_size: int) -> int:
    """`n_tokens` rounded UP to the page grid — the only key-capacity
    shapes the device cache (and therefore the jit) ever sees."""
    return pages_for(n_tokens, page_size) * int(page_size)


class PagePool:
    """Thread-safe page budget for one replica's cache allocation.

    `try_reserve` either takes the whole reservation or none of it (no
    partial grants — a half-admitted request would deadlock the slot
    machine); `release` returns pages at completion. The high-water
    mark (`peak_in_use`) is the occupancy headline's numerator."""

    def __init__(self, n_pages: int, page_size: int = DEFAULT_PAGE_SIZE):
        if n_pages < 1 or page_size < 1:
            raise ValueError(
                f"page pool needs n_pages >= 1 and page_size >= 1; got "
                f"{n_pages} pages of {page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._in_use = 0
        self.peak_in_use = 0
        self._lock = threading.Lock()

    def pages_for(self, n_tokens: int) -> int:
        return pages_for(n_tokens, self.page_size)

    def try_reserve(self, n_pages: int) -> bool:
        with self._lock:
            if self._in_use + n_pages > self.n_pages:
                return False
            self._in_use += n_pages
            self.peak_in_use = max(self.peak_in_use, self._in_use)
            return True

    def release(self, n_pages: int) -> None:
        with self._lock:
            if n_pages > self._in_use:
                raise ValueError(
                    f"releasing {n_pages} pages with only {self._in_use} "
                    "reserved — double release")
            self._in_use -= n_pages

    @property
    def in_use(self) -> int:
        with self._lock:
            return self._in_use

    @property
    def occupancy(self) -> float:
        return self.in_use / self.n_pages

    @property
    def peak_occupancy(self) -> float:
        with self._lock:
            return self.peak_in_use / self.n_pages

    def describe(self) -> dict:
        with self._lock:
            return {"pages_total": self.n_pages,
                    "page_size": self.page_size,
                    "pages_in_use": self._in_use,
                    "pages_peak": self.peak_in_use}


class CachePlan:
    """The quantized cache geometry one replica allocates: `n_slots`
    rows of `capacity` key slots, where capacity is the largest prompt
    bucket plus the output budget, rounded up to the page grid. The
    default pool budget is exactly the allocation (`n_slots` rows'
    pages); passing a smaller `pool_pages` models a tighter HBM budget
    — admission then queues before the slots run out."""

    def __init__(self, max_seq_bucket: int, max_new_tokens: int,
                 n_slots: int, page_size: int = DEFAULT_PAGE_SIZE,
                 pool_pages: int | None = None, kv_dtype: str = "f32"):
        if n_slots < 1:
            raise ValueError(f"need n_slots >= 1, got {n_slots}")
        self.page_size = int(page_size)
        self.max_new_tokens = int(max_new_tokens)
        self.n_slots = int(n_slots)
        self.kv_dtype = validate_kv_dtype(kv_dtype)
        self.capacity = quantize(max_seq_bucket + max_new_tokens,
                                 page_size)
        self.pages_per_slot = self.capacity // self.page_size
        self.pool_pages = (self.n_slots * self.pages_per_slot
                           if pool_pages is None else int(pool_pages))

    def cache_specs(self, net) -> dict:
        """The net's attention layers' cache specs at this plan's
        geometry (nn/decode.cache_specs)."""
        return net.kv_cache_specs(self.capacity, self.kv_dtype,
                                  self.page_size)

    def bytes_per_slot(self, net) -> int:
        """This plan's per-slot HBM bill (see module `bytes_per_slot`)."""
        return bytes_per_slot(self.cache_specs(net))

    def make_pool(self) -> PagePool:
        return PagePool(self.pool_pages, self.page_size)

    def request_pages(self, prompt_bucket: int, max_new: int) -> int:
        """A request's worst-case reservation: its QUANTIZED prompt
        bucket plus output budget — the page-grid point, never the raw
        length, so accounting and shapes stay on the same lattice."""
        return pages_for(prompt_bucket + max_new, self.page_size)

    def describe(self, net=None) -> dict:
        """The geometry; with `net`, also what its layers keep in it:
        `rows` ({kind of row: bytes a token over all layers}) and
        `bytes_per_token`, their sum: what a token costs while every
        layer still holds it, every pass of a looped net's layers
        counted (`passes`: how many times a token runs the net's loop, 1
        without one; a layer inside it keeps a row a pass); `windows` ({kind of row: rows a slot}) for
        the kinds that are rings and hold fewer positions than the
        capacity; `states` ({kind of state: bytes a slot over all
        layers}) and `state_bytes_per_slot`, their sum; `bytes_per_slot`,
        what `init_cache` allocates a slot of: every kind of row times
        the positions it holds (its window, else the capacity) plus the
        states."""
        out = {"n_slots": self.n_slots, "capacity": self.capacity,
               "page_size": self.page_size,
               "pages_per_slot": self.pages_per_slot,
               "pool_pages": self.pool_pages,
               "max_new_tokens": self.max_new_tokens,
               "kv_dtype": self.kv_dtype}
        if net is not None:
            specs = self.cache_specs(net)
            rows, states = row_kinds(specs, self.capacity), slot_kinds(specs)
            out["rows"] = rows
            out["bytes_per_token"] = round(sum(rows.values()), 3)
            out["windows"] = window_kinds(specs)
            out["states"] = states
            out["state_bytes_per_slot"] = sum(states.values())
            out["bytes_per_slot"] = bytes_per_slot(specs)
            out["passes"] = passes_of(net)
        return out


def passes_of(net) -> int:
    """How many times a token runs the net's loop (a graph's `LoopConf`),
    1 for a net without one."""
    loop = getattr(net.conf, "loop", None)
    return 1 if loop is None else int(loop.times)
