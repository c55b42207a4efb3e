"""The trace reduction on a short window recorded on a TPU v5e through the
benchmark's own driver (a one-layer Chameleon-width engine serving chat),
checked against a second, independent reading of the same file."""
import gzip
import shutil
from pathlib import Path

import pytest

import bench_tiny
from benchlib.tracefile import WINDOW_MARK, reduce_xplane

FIXTURE = Path(bench_tiny.BENCH) / "tests" / "data" / "v5e_chat.xplane.pb.gz"


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v5e_chat.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _independent(path):
    """Window, busy time (a sweep over +1/-1 edges) and kernel time per
    program, read straight from the planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    w0, w1 = window
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    edges = []
    kernels = {}
    mods = [(m.start_ns, m.start_ns + m.duration_ns, m.name)
            for m in lines["XLA Modules"]]
    for ev in lines["XLA Ops"]:
        s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
        if e <= s and not (ev.duration_ns == 0 and w0 < ev.start_ns < w1):
            continue
        edges += [(s, 1), (e, -1)]
        if "tpu_custom_call" in ev.name:
            owner = [n for ms, me, n in mods if ms <= ev.start_ns <= me]
            key = owner[0].split("(")[0]
            kernels[key] = kernels.get(key, 0.0) + (e - s)
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(edges, key=lambda x: (x[0], -x[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return (w1 - w0) * 1e-9, busy * 1e-9, {k: v * 1e-9
                                          for k, v in kernels.items()}


def test_reduction_agrees_with_an_independent_reading(xplane):
    tr = reduce_xplane(xplane)
    window_s, busy_s, kernels = _independent(xplane)
    assert tr.window_s == pytest.approx(window_s, rel=1e-12)
    assert 0.0 < tr.busy_s() <= tr.window_s
    assert tr.busy_s() == pytest.approx(busy_s, rel=1e-9)
    for prog in ("jit__decode_paged_fn", "jit__chunk_paged_fn"):
        assert tr.kernel_s(prog) == pytest.approx(kernels.get(prog, 0.0),
                                                  rel=1e-9, abs=1e-12)
    assert tr.kernel_s("jit__decode_paged_fn") > 0.0


def test_breakdown_lists(xplane):
    tr = reduce_xplane(xplane)
    ops = tr.top_ops(10)
    assert 0 < len(ops) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    gaps = tr.idle_gaps(10)
    assert len(gaps) <= 10
    assert all(name.startswith("host: ") for name, _ in gaps)
    assert sum(v for _, v in gaps) <= tr.window_s - tr.busy_s() + 1e-9


def test_a_window_outside_the_trace_holds_no_work(xplane):
    tr = reduce_xplane(xplane, window=(-2e9, -1e9))
    assert tr.busy_s() == 0.0 and tr.kernel_s("jit_") == 0.0
