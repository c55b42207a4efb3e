"""A configuration file names its architecture module, and the harness
takes the model from that module alone: a routed-expert model whose three
files (configuration, architecture, reference) live only among the tests'
data runs through the harness with no edit of it."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchlib import spec, weights

FIXTURE = bench_tiny.BENCH / "tests" / "data"
FIXTURE_FILE = "bench/tests/data/tiny-moe.json"
LIKE = "chameleon-34b.l6.chat"


def _configurations():
    """(name, configuration, the directory of its modules): every
    configuration ``BENCHMARK.json`` names, and the fixture."""
    out = []
    for c in spec.load_benchmark()["configs"]:
        path = bench_tiny.ROOT / c["file"]
        out.append((c["name"], json.loads(path.read_text()), path.parent))
    conf = json.loads((bench_tiny.ROOT / FIXTURE_FILE).read_text())
    out.append((conf["name"], conf, FIXTURE))
    return out


CONFIGURATIONS = {name: (conf, d) for name, conf, d in _configurations()}


@pytest.mark.parametrize("name", sorted(CONFIGURATIONS))
def test_every_leaf_has_a_rule(name):
    from repro.models.model import build_model

    conf, directory = CONFIGURATIONS[name]
    arch = spec.load_architecture(conf, directory)
    cfg = arch.model_config(conf)
    abstract = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    assert flat
    for p, a in flat:
        path = "/".join(str(getattr(k, "key", k)) for k in p)
        kind, std = weights.rule(path, a.shape, cfg.d_model, arch.weight_rule)
        assert kind in ("normal", "one_plus", "zeros") and std >= 0.0, path


@pytest.mark.parametrize("name", sorted(CONFIGURATIONS))
def test_a_leaf_no_rule_knows_raises(name):
    conf, directory = CONFIGURATIONS[name]
    arch = spec.load_architecture(conf, directory)
    with pytest.raises(ValueError, match="no weight rule"):
        weights.rule("stack/blocks/attn/w_made_up", (2, 16, 16), 16,
                     arch.weight_rule)


def test_every_configuration_names_its_modules():
    for c in spec.load_benchmark()["configs"]:
        conf = json.loads((bench_tiny.ROOT / c["file"]).read_text())
        arch = spec.load_architecture(conf)
        for fn in ("model_config", "weight_rule", "work"):
            assert callable(getattr(arch, fn)), (c["name"], fn)
        assert callable(spec.load_reference(conf).logits_summary)


def test_the_harness_names_no_architecture():
    files = [bench_tiny.BENCH / "run.py",
             *sorted((bench_tiny.BENCH / "benchlib").glob("*.py"))]
    for f in files:
        text = f.read_text()
        for word in ("family=", "attn_type=", "activation=", "tiny-moe",
                     "moe_architecture", "moe_reference"):
            assert word not in text, (f.name, word)


def test_fixture_reference_matches_the_program_forward():
    from repro.kernels import ops
    from repro.models.model import build_model

    conf, directory = CONFIGURATIONS["tiny-moe"]
    arch = spec.load_architecture(conf, directory)
    cfg = dataclasses.replace(arch.model_config(conf), param_dtype="float32",
                              compute_dtype="float32")
    model = build_model(cfg)
    w = weights.make_weights(model.init, cfg.d_model, 11, dtype=jnp.float32,
                             arch_rule=arch.weight_rule)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 90,
                                             dtype=np.int32)
    ops.set_impl("ref")
    try:
        with jax.default_matmul_precision("highest"):
            logits, _ = model.forward(w, {"tokens": jnp.asarray(toks)[None]})
    finally:
        ops.set_impl(None)
    lg = np.asarray(logits[0], np.float64)
    rows = np.arange(0, 90, 3)
    chosen = np.argsort(lg[rows], -1)[:, -2]          # the runner-up
    ref = spec.load_reference(conf, directory)
    mx, am, pk = ref.logits_summary(w, conf, toks, rows, chosen, pad_to=128)
    np.testing.assert_allclose(mx, lg[rows].max(-1), atol=2e-5)
    np.testing.assert_array_equal(am, lg[rows].argmax(-1))
    np.testing.assert_allclose(pk, lg[rows, chosen], atol=2e-5)
    mx_c, _, _ = ref.logits_summary(w, conf, toks, rows, chosen, pad_to=128,
                                    control=True)
    assert np.abs(mx_c - lg[rows].max(-1)).max() > 1e-3


def test_fixture_run_is_correct():
    cell = bench_tiny.fixture_cell(FIXTURE_FILE, LIKE)
    res, lines = bench_tiny.run_tiny(cell, seed=2**33 + 5)
    assert res["correct"], (res, lines)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"tpot_p75_ms", "setup_s"} <= set(res["metrics"])
    gap = res["checks"]["logit_gap"]
    assert gap["limit"] == cell.config["check"]["logit_gap_limit"]


def test_fixture_control_is_not_correct():
    cell = bench_tiny.fixture_cell(FIXTURE_FILE, LIKE)
    res, lines = bench_tiny.run_tiny(cell, seed=2**32 + 13, control=True)
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"], lines
