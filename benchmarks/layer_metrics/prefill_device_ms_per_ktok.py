"""model step (nn/decode.py's walk, compiled as the engine's prefill
programs): the device time of the prefill chunks per thousand real
prompt tokens, in ms. Each `counted_prefill` module of the traced
window, up to the trace's stop, is joined to the `prefill_chunk` span
that dispatched it (harness/programs.py), which says how many of the
bucket's tokens are real (`n_real`); the modules' busy time on the chip
(harness/regions.py) summed, over the summed `n_real` / 1,000. Needs no
region of the program."""
from harness import regions


def read(facts):
    progs = regions.of_kind(facts, "prefill_chunk")
    k = regions.ktok(progs)
    if not progs or k <= 0:
        return None
    return 1e3 * sum(p["busy_s"] for p in progs) / k
