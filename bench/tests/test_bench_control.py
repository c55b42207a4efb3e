"""The control of the output check: the plain reference computed one
precision step below the configuration (float8 matmuls) must read far
worse than the program's bf16 path on the same served tokens, and, put in
the program's place, must make a run read not correct at the
configuration's own limit.  On the chip this is read at each cell's own
size (``PERF.md``); here at a size a test can hold, on three seeds."""
import gc

import numpy as np
import pytest

import bench_tiny
from benchlib import check as chk
from benchlib import serve, spec
from benchlib.weights import make_weights

CELLS = ["chameleon-34b.l6.chat", "command-r-35b.l5.rag"]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_far_above_the_program(name):
    from repro.core.resources import NodeCapacity
    from repro.models.model import build_model

    cell = bench_tiny.tiny_cell(name, d_model=256, vocab=1024)
    conf, traffic = cell.config, cell.traffic
    arch = spec.load_architecture(conf)
    cfg = arch.model_config(conf)
    ref = spec.load_reference(conf)
    prog, ctl = [], []
    for seed in (1, 2, 3):
        w = make_weights(build_model(cfg).init, cfg.d_model, seed,
                         dtype=cfg.pdtype, arch_rule=arch.weight_rule)
        system, engine = serve.build_system(
            cfg, conf, w,
            capacity=NodeCapacity(hbm_bytes=16 << 30, chips=1,
                                  flops_per_s=1e12))
        engine.warmup()
        serve.drive(engine, traffic, seed, 1.5, cfg.vocab_size,
                    serve.CompileCounter(), wait_s=0.0)
        done = [r for r in engine.completed.values() if r.generated]
        sample = chk.sample(done, seed, 3, 60)
        assert sum(len(r.generated) for r in sample) >= 30
        pad = int(conf["serving"]["max_seq"])
        prog.append(chk.logit_gaps(ref, w, conf, sample, pad).max())
        ctl.append(chk.logit_gaps(
            ref, w, conf, chk.as_control(ref, w, conf, sample, pad),
            pad).max())
        del system, engine
        gc.collect()
    assert min(ctl) > 3 * max(prog), (prog, ctl)
    assert np.all(np.isfinite(ctl))


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_not_correct(name):
    cell = bench_tiny.tiny_cell(name, d_model=256, vocab=1024)
    res, lines = bench_tiny.run_tiny(cell, seed=2**32 + 11, control=True)
    gap = res["checks"]["logit_gap"]
    assert gap["limit"] == cell.config["check"]["logit_gap_limit"]
    assert not res["correct"], lines
    assert gap["value"] > gap["limit"], lines
