"""model step (nn/decode.py's walk, compiled as the engine's decode
program): the median device time of one decode program, in ms. Each
`counted_step` module of the traced window, up to the trace's stop, is
joined to the `decode_step` span that dispatched it
(harness/programs.py); its time is the union of its ops' intervals on
the chip (harness/regions.py), whatever the host did around it. Needs no
region of the program."""
import statistics

from harness import regions


def read(facts):
    progs = regions.of_kind(facts, "decode_step")
    if not progs:
        return None
    return 1e3 * statistics.median(p["busy_s"] for p in progs)
