"""The harness end to end on the CPU at a tiny size, without its look for
a chip: a sound run reads correct and reports its metrics."""
import bench_tiny


def _assert_line(res):
    assert list(res)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert res["device"]["platform"] == "cpu"


def test_sound_run_is_correct():
    cell = bench_tiny.tiny_cell("chameleon-34b.l6.chat")
    res, lines = bench_tiny.run_tiny(cell, seed=2**33 + 3)
    _assert_line(res)
    assert res["correct"], (res, lines)
    assert res["failed"] == 0 and res["attempted"] > 0
    names = set(res["metrics"])
    assert {"tpot_p75_ms", "setup_s"} <= names
    assert lines[-1].startswith("check missing")


def test_traced_run_reports_per_layer_metrics():
    cell = bench_tiny.tiny_cell("command-r-35b.l5.rag")
    res, _ = bench_tiny.run_tiny(cell, seed=9, trace=True)
    _assert_line(res)
    assert res["correct"]
    assert {"decode_rows_per_tick.batch", "mfu.batch"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res
