"""admission and slots (serving/engine.py, kvcache.py): the 95th percentile
of the summary lines' `timing.queue_s`, enqueue to admission into a slot."""
from harness import traffic


def read(facts):
    xs = [1e3 * r["timing"]["queue_s"] for r in facts["load"]["records"]
          if r.get("timing") and r["timing"].get("queue_s") is not None]
    return traffic.percentile(xs, 95) if xs else None
