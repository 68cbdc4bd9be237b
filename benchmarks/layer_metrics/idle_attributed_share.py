"""device: of the seconds the chip sat idle in the traced window, the share
whose gap's middle lies in a leaf span of the program, that is, everything
`xplane.idle_gaps` over the leaf spans does not call `unattributed`. At
100 % every idle gap of the device is named by what the host was doing."""
from harness import host_loop, xplane


def read(facts):
    t, spans = facts.get("traced"), facts.get("spans")
    if not t or not t.get("chips") or spans is None \
            or t.get("t_sync") is None:
        return None
    z = t["t_sync"]
    leaves = [(n, a - z, b - z) for n, a, b in host_loop.leaf_spans(spans)]
    gaps = xplane.idle_gaps(t["chips"], leaves, n=1 << 30,
                            offset_ns=t.get("sync_ns") or 0.0)
    total = sum(s for _who, s in gaps)
    if not leaves or total <= 0.0:
        return None
    return 100.0 * (1.0 - dict(gaps).get("unattributed", 0.0) / total)
