"""The plain reference against the program's own forward pass, at a
small size in float32, for both configurations' block layouts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchlib import spec
from benchlib.weights import make_weights


@pytest.mark.parametrize("name", ["chameleon-34b.l6.chat",
                                  "command-r-35b.l5.rag"])
def test_reference_matches_the_program_forward(name):
    from repro.kernels import ops
    from repro.models.model import build_model

    conf = bench_tiny.tiny_cell(name).config
    arch = spec.load_architecture(conf)
    cfg = dataclasses.replace(arch.model_config(conf), param_dtype="float32",
                              compute_dtype="float32")
    model = build_model(cfg)
    w = make_weights(model.init, cfg.d_model, 11, dtype=jnp.float32,
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
    ref = spec.load_reference(conf)
    mx, am, pk = ref.logits_summary(w, conf, toks, rows, chosen, pad_to=128)
    np.testing.assert_allclose(mx, lg[rows].max(-1), atol=2e-5)
    np.testing.assert_array_equal(am, lg[rows].argmax(-1))
    np.testing.assert_allclose(pk, lg[rows, chosen], atol=2e-5)
    # the float8 control lands measurably off the float32 logits
    mx_c, _, _ = ref.logits_summary(w, conf, toks, rows, chosen, pad_to=128,
                                    control=True)
    assert np.abs(mx_c - lg[rows].max(-1)).max() > 1e-3
