"""Device idle put down to the engine's spans, and the per-token gap tail
(``benchlib.spans``): exact on a synthetic trace, nothing to read on a
trace without engine spans, in agreement with an independent sweep on a
short window recorded on a TPU v5e, and reported by a traced CPU run."""
import gzip
import shutil
import types
from pathlib import Path

import pytest

import bench_tiny
from benchlib import spans
from benchlib.readers import RunView
from benchlib.tracefile import WINDOW_MARK, Op, Trace, reduce_xplane

DATA = Path(bench_tiny.BENCH) / "tests" / "data"
MS = 1e6                                     # ns


def _view(trace, ticks=(3, 5), requests=(), times=(0.0, 1.0)):
    served = types.SimpleNamespace(
        ticks={"trace0": ticks[0], "trace1": ticks[1]},
        times={"window0": times[0], "window1": times[1]},
        requests=list(requests))
    return RunView(served, None, None, 0.0, trace)


def _synthetic():
    """Device busy 10-20, 30-70 ms of a 100 ms window; idle 0-10, 20-30
    and 70-100.  Engine spans cover 20-35 and 70-90 (sync 20-28 and
    75-80); JAX's own span inside the sync counts for nothing."""
    ops = [Op("jit_f", "%a = f()", s * MS, e * MS)
           for s, e in ((10, 20), (30, 60), (55, 70))]
    host = [("engine.sync", 20 * MS, 28 * MS),
            ("np.asarray(jax.Array)", 20 * MS, 30 * MS),
            ("engine.finish", 28 * MS, 32 * MS),
            ("engine.submit", 25 * MS, 35 * MS),
            ("engine.wait", 70 * MS, 90 * MS),
            ("engine.sync", 75 * MS, 80 * MS)]
    return Trace((0.0, 100 * MS), [ops], host)


def test_synthetic_trace_splits_idle_exactly():
    split = spans.idle_split_s(_synthetic())
    assert split["idle"] == pytest.approx(0.050, abs=1e-12)
    assert split["sync"] == pytest.approx(0.013, abs=1e-12)     # 8 + 5
    assert split["host"] == pytest.approx(0.017, abs=1e-12)     # 2 + 15
    view = _view(_synthetic())                                  # 2 ticks
    assert spans.idle_ms_per_tick(view, "sync") == pytest.approx(6.5)
    assert spans.idle_ms_per_tick(view, "host") == pytest.approx(8.5)
    assert spans.idle_ms_per_tick(_view(_synthetic(), (4, 4)),
                                  "sync") is None


def test_nothing_to_read_without_engine_spans_or_device_ops():
    tr = _synthetic()
    no_spans = Trace(tr.window, tr.devices,
                     [h for h in tr.host if not h[0].startswith("engine.")])
    no_ops = Trace(tr.window, [[]], tr.host)
    assert spans.idle_split_s(no_spans) is None
    assert spans.idle_split_s(no_ops) is None
    assert spans.idle_split_s(None) is None


def test_older_chip_trace_without_engine_spans_reads_nothing(tmp_path):
    path = tmp_path / "v5e_chat.xplane.pb"
    with gzip.open(DATA / "v5e_chat.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    view = _view(reduce_xplane(str(path)))
    assert spans.idle_ms_per_tick(view, "sync") is None
    assert spans.idle_ms_per_tick(view, "host") is None


def _independent(path):
    """Seconds of device idle under ``engine.sync`` and under other
    ``engine.*`` spans only, by a sweep over the +1/-1 edges of device ops,
    sync spans and engine spans read straight from the planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = pd.find_plane_with_name("/host:CPU")
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
           for line in host.lines for ev in line.events]
    ((w0, w1),) = [(s, e) for name, s, e in evs if name == WINDOW_MARK]
    edges = []                                  # (t, device, sync, engine)
    for name, s, e in evs:
        if name.startswith("engine.") and e > w0 and s < w1:
            sync = int(name == "engine.sync")
            edges += [(max(s, w0), 0, sync, 1), (min(e, w1), 0, -sync, -1)]
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [ev for line in plane.lines if line.name == "XLA Ops"
               for ev in line.events
               if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1]
        if ops:
            for ev in ops:
                edges += [(max(ev.start_ns, w0), 1, 0, 0),
                          (min(ev.start_ns + ev.duration_ns, w1), -1, 0, 0)]
            break
    sync_idle = host_idle = 0.0
    dev = syn = eng = 0
    last = w0
    for t, d, s, g in sorted(edges) + [(w1, 0, 0, 0)]:
        if dev == 0 and syn > 0:
            sync_idle += t - last
        elif dev == 0 and eng > 0:
            host_idle += t - last
        dev, syn, eng, last = dev + d, syn + s, eng + g, t
    return sync_idle * 1e-9, host_idle * 1e-9


@pytest.fixture(scope="module")
def spans_xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v5e_chat_spans.xplane.pb"
    with gzip.open(DATA / "v5e_chat_spans.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_chip_trace_split_agrees_with_an_independent_sweep(spans_xplane):
    tr = reduce_xplane(spans_xplane)
    split = spans.idle_split_s(tr)
    sync_s, host_s = _independent(spans_xplane)
    assert split["sync"] > 0.0
    assert split["sync"] == pytest.approx(sync_s, rel=1e-9)
    assert split["host"] == pytest.approx(host_s, rel=1e-9)
    assert split["idle"] == pytest.approx(tr.window_s - tr.busy_s(),
                                          rel=1e-9)
    assert split["sync"] + split["host"] <= split["idle"] + 1e-12


def test_token_gaps_count_the_later_token_in_the_window():
    req = types.SimpleNamespace(token_times=[0.5, 1.0, 1.25, 2.5, 3.0])
    old = types.SimpleNamespace(generated=[1, 2])    # no token_times field
    view = _view(None, requests=[types.SimpleNamespace(req=req),
                                 types.SimpleNamespace(req=None)],
                 times=(1.0, 2.5))
    assert spans.token_gaps_s(view) == [0.5, 0.25, 1.25]
    assert spans.token_gap_ms(view, 100) == pytest.approx(1250.0)
    assert spans.token_gaps_s(_view(None, requests=[
        types.SimpleNamespace(req=old)])) is None


def test_traced_chat_run_reports_the_token_gap_tail():
    cell = bench_tiny.tiny_cell("chameleon-34b.l6.chat")
    res, _ = bench_tiny.run_tiny(cell, seed=2**32 + 11, trace=True)
    assert res["correct"]
    assert res["metrics"]["decode_gap_p95_ms.latency"]["value"] > 0.0
