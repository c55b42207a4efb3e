"""The traffic generator: seeded, in range, and the same work for every
seed."""
import json
from pathlib import Path

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from benchlib import traffic as tr

TRAFFIC = sorted((Path(bench_tiny.BENCH) / "traffic").glob("*.json"))


def _load(path):
    return json.loads(path.read_text())


def _per_cycle(t):
    return int(t["block"]) * int(t["cycle"])


def _schedule(t, seed, vocab=65536):
    """Two cycles."""
    if t["loop"] == "open":
        return tr.open_schedule(t, seed, vocab, first_cycle=-1, cycles=2)
    gen = tr.closed_stream(t, seed, vocab)
    return [next(gen) for _ in range(2 * _per_cycle(t))]


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_same_seed_same_schedule(path):
    t = _load(path)
    a, b = _schedule(t, 2**33 + 5), _schedule(t, 2**33 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_lengths_and_tokens_in_range(path):
    t = _load(path)
    vocab = 65536
    by_class = {c["name"]: c for c in t["classes"]}
    for r in _schedule(t, 17, vocab=vocab):
        c = by_class[r.cls]
        o = c["output"]
        assert o["min"] <= r.max_new <= o["max"]
        lo = sum(s.get("fixed", s.get("min", 0)) for s in c["prompt"])
        hi = sum(s.get("fixed", s.get("max", 0)) for s in c["prompt"])
        assert lo <= r.prompt.size <= hi
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < vocab


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_every_seed_asks_the_same_work(path):
    t = _load(path)
    a, b = _schedule(t, 1), _schedule(t, 99)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_every_cycle_reaches_both_ends_of_each_range(path):
    t = _load(path)
    n = _per_cycle(t)
    sched = _schedule(t, 2**31 + 9)
    for c in range(2):
        cyc = sched[c * n:(c + 1) * n]
        for cls in t["classes"]:
            mine = [r for r in cyc if r.cls == cls["name"]]
            o = cls["output"]
            assert {o["min"], o["max"]} <= {r.max_new for r in mine}
            seg = cls["prompt"][-1]
            lens = {r.prompt.size - sum(s.get("fixed", 0)
                                        for s in cls["prompt"][:-1])
                    for r in mine}
            assert {seg["min"], seg["max"]} <= lens


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_every_block_is_a_cross_section(path):
    t = _load(path)
    n, k = int(t["block"]), int(t["cycle"])
    sched = _schedule(t, 5)
    for cls in t["classes"]:
        q = tr.lognormal_quantiles(cls["output"],
                                   int(round(cls["share"] * n)) * k)
        strata = [q[j * k:(j + 1) * k] for j in range(len(q) // k)]
        for b in range(2 * k):
            outs = sorted(r.max_new for r in sched[b * n:(b + 1) * n]
                          if r.cls == cls["name"])
            # one from each run of ``cycle`` adjacent quantiles
            assert all(s[0] <= x <= s[-1] for s, x in zip(strata, outs))


def test_open_loop_cycles_hold_the_same_gaps_and_work():
    t = _load(Path(bench_tiny.BENCH) / "traffic" / "chat.json")
    n, span = _per_cycle(t), tr.cycle_span(t)
    assert span == n / t["rate_per_s"]
    sched = tr.open_schedule(t, 3, 65536, first_cycle=-1, cycles=3)
    assert len(sched) == 3 * n
    for c in range(3):
        cyc = sched[c * n:(c + 1) * n]
        due = [r.due_s for r in cyc]
        start = (c - 1) * span
        assert due == sorted(due) and due[0] == start
        assert all(start <= d < start + span for d in due)
        gaps = np.diff(due + [start + span])
        np.testing.assert_allclose(sorted(gaps),
                                   tr.exponential_gaps(n, span),
                                   rtol=1e-9, atol=1e-9)
        assert sorted(r.prompt.size for r in cyc) == sorted(
            r.prompt.size for r in sched[:n])
    other = [r.due_s for r in tr.open_schedule(t, 4, 65536, 0, 1)]
    assert other != [r.due_s for r in sched[n:2 * n]]


def test_lognormal_quantiles_are_stratified_and_clipped():
    spec = {"median": 100, "min": 50, "max": 400}
    assert tr.sigma_of(spec) == pytest.approx(np.log(4) / 2)
    q = tr.lognormal_quantiles(spec, 101)
    assert q == sorted(q) and q[50] == 100
    assert min(q) == 50 and max(q) == 400
    assert tr.lognormal_quantiles({"fixed": 7}, 3) == [7, 7, 7]


def test_percentile_interpolates():
    assert tr.percentile([4, 1, 3, 2], 50) == 2.5
    assert tr.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert tr.percentile([7], 95) == 7
