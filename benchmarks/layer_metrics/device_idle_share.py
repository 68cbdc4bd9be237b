"""device: the share of the traced window in which no operation ran on the
chip: 1 - union of the device's op intervals / the traced window."""
from harness import xplane


def read(facts):
    return xplane.idle_share_percent(facts.get("traced"))
