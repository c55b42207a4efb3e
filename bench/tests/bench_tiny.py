"""A cell of the benchmark cut to a size a CPU test can run: the cell's
own files, with widths, depth, lengths and rates scaled down."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import spec  # noqa: E402
from benchlib.peaks import Peaks  # noqa: E402


def load_run():
    """``bench/run.py`` as a module of its own name."""
    if "bench_run" in sys.modules:
        return sys.modules["bench_run"]
    s = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(s)
    sys.modules["bench_run"] = mod
    s.loader.exec_module(mod)
    return mod


def tiny_cell(name: str, d_model: int = 128, vocab: int = 512):
    """The cell ``name`` cut down."""
    cell = spec.load_cell(name)
    c = cell.config
    c.update(hidden_size=d_model, intermediate_size=2 * d_model,
             num_attention_heads=4, num_key_value_heads=2,
             head_dim=d_model // 4, num_hidden_layers=2, vocab_size=vocab)
    c["serving"] = {"max_slots": 4, "max_seq": 128, "num_pages": None}
    _cut_traffic(cell.traffic)
    return cell


def fixture_cell(config_file: str, like: str):
    """A cell of a configuration that exists only among the tests' data:
    the configuration file ``config_file`` (from the repository's root)
    under the traffic and the metrics of the benchmark's cell ``like``,
    the traffic cut down.  Its modules are found beside the file."""
    bench = spec.load_benchmark()
    like_w = next(w for w in bench["workloads"] if w["name"] == like)
    conf = json.loads((ROOT / config_file).read_text())
    name = f"{conf['name']}.{like_w['traffic']}"

    def also_here(entry):
        e = dict(entry)
        if like in e.get("workloads", ()):
            e["workloads"] = e["workloads"] + [name]
        return e

    cell = spec.load_cell(name, bench={
        "configs": [{"name": conf["name"], "file": config_file}],
        "workloads": [{"name": name, "config": conf["name"],
                       "traffic": like_w["traffic"], "chips": 1}],
        "end_to_end": [also_here(e) for e in bench["end_to_end"]],
        "per_layer": [also_here(m) for m in bench["per_layer"]]})
    _cut_traffic(cell.traffic)
    # every finished request is checked (100-250 tokens): served in
    # float32, the configuration's control (bfloat16) puts another token
    # first only at near ties, on some 6-17% of tokens
    cell.traffic["check"] = {"requests": 64, "tokens": 10**6,
                             "min_tokens": 60}
    return cell


def _cut_traffic(t):
    for cl in t["classes"]:
        for seg in cl["prompt"]:
            if "fixed" in seg:
                seg["fixed"] = 48
                seg["tokens"] = [4, 260]
            else:
                seg.update(median=30, min=8, max=60)
        cl["output"].update(median=16, min=4, max=40)
    t["ramp_s"] = 0.5
    if "rate_per_s" in t:
        t["rate_per_s"] = 4.0
    t["check"] = {"requests": 3, "tokens": 40, "min_tokens": 10}
    t["trace"] = {"offset_s": 0.3, "seconds": 0.5}


def run_tiny(cell, seed: int = 7, seconds: float = 2.0, trace: bool = False,
             control: bool = False):
    """One run of a tiny cell on the CPU: the harness without its look
    for a chip."""
    from repro.core.resources import NodeCapacity

    run = load_run()
    return run.run_cell(
        cell, seed, seconds, trace, require_tpu=False,
        capacity=NodeCapacity(hbm_bytes=16 << 30, chips=1,
                              flops_per_s=1e12),
        peaks=Peaks(1e12, 1e11, "test"), use_cache=False, control=control)
