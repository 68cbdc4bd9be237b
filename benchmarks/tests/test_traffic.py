"""The traffic generator: every seed gets the same work in the same order."""
from harness import traffic
from presets import CLOSED_MIX, OPEN_MIX


def test_open_loop_same_work_every_seed():
    a = traffic.make_requests(OPEN_MIX, 1, 256, 10.0)
    b = traffic.make_requests(OPEN_MIX, 2**31 + 5, 256, 10.0)
    assert len(a) == len(b) == 60
    assert 0 <= a[0]["due_s"] and a[-1]["due_s"] < 10.0
    # the same schedule and sizes in the same order; other token ids
    assert [(r["due_s"], len(r["prompt"]), r["max_new"]) for r in a] \
        == [(r["due_s"], len(r["prompt"]), r["max_new"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_same_seed_same_requests():
    assert traffic.make_requests(OPEN_MIX, 7, 256, 5.0) \
        == traffic.make_requests(OPEN_MIX, 7, 256, 5.0)


def test_closed_loop_clients():
    reqs = traffic.make_requests(CLOSED_MIX, 3, 256, 5.0)
    assert len(reqs) == 12 and {r["client"] for r in reqs} == set(range(6))
    assert all(r["due_s"] is None and r["max_new"] == 16 for r in reqs)
    other = traffic.make_requests(CLOSED_MIX, 4, 256, 5.0)
    assert [len(r["prompt"]) for r in reqs] == [len(r["prompt"]) for r in other]
    assert [r["prompt"] for r in reqs] != [r["prompt"] for r in other]


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert traffic.percentile(xs, 90) == 90 and traffic.percentile(xs, 95) == 95
    assert traffic.percentile([5.0], 95) == 5.0


def test_open_loop_gaps_are_exponential():
    import statistics

    due = [r["due_s"] for r in traffic.make_requests(OPEN_MIX, 1, 256, 100.0)]
    gaps = [b - a for a, b in zip(due, due[1:])]
    assert 0.8 < statistics.pstdev(gaps) / statistics.mean(gaps) < 1.2
