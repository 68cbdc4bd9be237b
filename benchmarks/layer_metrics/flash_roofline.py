"""kernels (ops/flash_attention.py): the flash kernels' share of their
roofline over the traced window. For every executed `flash_*` kernel
event: the least time the chip could take for that call, max(FLOPs / peak
FLOP/s, bytes / peak B/s) from its shapes (harness/flops.py), summed, over
the summed device time of those events. At head_dim 128 and T 2048 the
bound is compute."""
import re

from harness import flops, xplane


# every flash kernel the program names, the longest name first
KERNEL = re.compile("(" + "|".join(sorted(flops.FLASH_PRODUCTS, key=len,
                                           reverse=True)) + ")")


def read(facts):
    t, peaks = facts.get("traced"), facts.get("peaks")
    if not t or not t.get("chips") or not peaks:
        return None
    dims = facts["dims"]
    shape = (facts["batch"], dims["H"], facts["seq_len"], dims["d"] // dims["H"])
    ideal = spent = 0.0
    for name, self_ns, leaf, detail in xplane.self_times(t["chips"][0]["ops"]):
        m = KERNEL.search(name)
        if not (leaf and m and xplane.is_kernel(detail)):
            continue
        f, b = flops.flash_kernel_cost(m.group(1), *shape)
        ideal += max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
        spent += self_ns / 1e9
    if spent <= 0.0:
        return None
    return 100.0 * ideal / spent
