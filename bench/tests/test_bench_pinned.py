"""What the two accepted configurations read, pinned: the program's model,
the weight tree and its bits, and every FLOP and byte count (the parameter
counts are the hand counts of ``test_bench_work.py``).
The values are those of the harness before configurations named their
architecture module; a change here moves the benchmark's cells."""
import hashlib
import json

import jax
import numpy as np
import pytest

import bench_tiny
from benchlib import spec
from benchlib.weights import make_weights
from test_bench_work import CONFIGS, PROGRESS

from repro.models.config import ModelConfig
from repro.models.model import build_model

NAMES = ["chameleon-34b.l6", "command-r-35b.l5"]
CELLS = {"chameleon-34b.l6": "chameleon-34b.l6.chat",
         "command-r-35b.l5": "command-r-35b.l5.rag"}

MODEL = {
    "chameleon-34b.l6": ModelConfig(
        name="chameleon-34b.l6", family="dense", num_layers=6, d_model=8192,
        num_heads=64, num_kv_heads=8, head_dim=128, d_ff=22016,
        vocab_size=65536, activation="swiglu", attn_type="full",
        qk_norm=True, norm="rmsnorm", norm_eps=1e-05, parallel_block=False,
        tie_embeddings=False, rope_theta=10000.0, param_dtype="bfloat16",
        compute_dtype="bfloat16"),
    "command-r-35b.l5": ModelConfig(
        name="command-r-35b.l5", family="dense", num_layers=5, d_model=8192,
        num_heads=64, num_kv_heads=8, head_dim=128, d_ff=22528,
        vocab_size=256000, activation="swiglu", attn_type="full",
        qk_norm=False, norm="layernorm", norm_eps=1e-05, parallel_block=True,
        tie_embeddings=True, rope_theta=8000000.0, param_dtype="bfloat16",
        compute_dtype="bfloat16"),
}

LEAVES = {
    "chameleon-34b.l6": [
        ("embed/embedding", (65536, 8192)),
        ("head/w_head", (8192, 65536)),
        ("stack/blocks/attn/k_norm", (6, 128)),
        ("stack/blocks/attn/q_norm", (6, 128)),
        ("stack/blocks/attn/w_k", (6, 8192, 8, 128)),
        ("stack/blocks/attn/w_o", (6, 64, 128, 8192)),
        ("stack/blocks/attn/w_q", (6, 8192, 64, 128)),
        ("stack/blocks/attn/w_v", (6, 8192, 8, 128)),
        ("stack/blocks/mlp/w_down", (6, 22016, 8192)),
        ("stack/blocks/mlp/w_gate", (6, 8192, 22016)),
        ("stack/blocks/mlp/w_up", (6, 8192, 22016)),
        ("stack/blocks/norm1/scale", (6, 8192)),
        ("stack/blocks/norm2/scale", (6, 8192)),
        ("stack/final_norm/scale", (8192,)),
    ],
    "command-r-35b.l5": [
        ("embed/embedding", (256000, 8192)),
        ("stack/blocks/attn/w_k", (5, 8192, 8, 128)),
        ("stack/blocks/attn/w_o", (5, 64, 128, 8192)),
        ("stack/blocks/attn/w_q", (5, 8192, 64, 128)),
        ("stack/blocks/attn/w_v", (5, 8192, 8, 128)),
        ("stack/blocks/mlp/w_down", (5, 22528, 8192)),
        ("stack/blocks/mlp/w_gate", (5, 8192, 22528)),
        ("stack/blocks/mlp/w_up", (5, 8192, 22528)),
        ("stack/blocks/norm1/bias", (5, 8192)),
        ("stack/blocks/norm1/scale", (5, 8192)),
        ("stack/final_norm/bias", (8192,)),
        ("stack/final_norm/scale", (8192,)),
    ],
}

# SHA-256 of every leaf's bytes, in tree order, of the weights from seed 11
# at the size of ``bench_tiny.tiny_cell`` (bf16, CPU)
TINY_WEIGHTS_SHA256 = {
    "chameleon-34b.l6":
        "33b230624bce6f65c6c8550a70aac6c3bc06bebbb243cf9e3f782408e7092ebb",
    "command-r-35b.l5":
        "c039bf770eac6cc3c0e0bf56ab47ac79d0f169bf34dffaa99cfab9f86e5fd94e",
}

LONG = [(4000, 0, 3000, 0, 0), (8192, 4096, 8192, 0, 3)]
# on PROGRESS: served FLOPs, decode work, prefill work in chunks of 4;
# on LONG: prefill work in chunks of 512 (the engine's chunk)
COUNTS = {
    "chameleon-34b.l6": (226_696_953_856, (19_660_800, 4_030_464),
                         (17_104_896, 4_349_952),
                         (5_833_235_890_176, 2_915_893_248)),
    "command-r-35b.l5": (225_180_876_800, (16_384_000, 3_358_720),
                         (14_254_080, 3_624_960),
                         (4_861_029_908_480, 2_429_911_040)),
}


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(str(getattr(k, "key", k)) for k in p), tuple(a.shape))
            for p, a in flat]


@pytest.mark.parametrize("name", NAMES)
def test_model_config_is_pinned(name):
    conf = _conf(name)
    assert spec.load_architecture(conf).model_config(conf) == MODEL[name]


@pytest.mark.parametrize("name", NAMES)
def test_full_size_leaves_are_pinned(name):
    conf = _conf(name)
    cfg = spec.load_architecture(conf).model_config(conf)
    abstract = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    assert _leaves(abstract) == LEAVES[name]
    assert {str(a.dtype) for a in jax.tree.leaves(abstract)} == {"bfloat16"}


@pytest.mark.parametrize("name", NAMES)
def test_tiny_weights_are_bit_identical(name):
    conf = bench_tiny.tiny_cell(CELLS[name]).config
    arch = spec.load_architecture(conf)
    cfg = arch.model_config(conf)
    w = make_weights(build_model(cfg).init, cfg.d_model, 11,
                     dtype=cfg.pdtype, arch_rule=arch.weight_rule)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(w):
        h.update(np.asarray(leaf).view(np.uint8).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256[name]


@pytest.mark.parametrize("name", NAMES)
def test_work_counts_are_pinned(name):
    conf = _conf(name)
    w = spec.load_architecture(conf).work(conf)
    flops, decode, prefill, long_prefill = COUNTS[name]
    assert w.served_flops(PROGRESS) == flops
    assert w.decode_attention_work(PROGRESS) == decode
    assert w.prefill_attention_work(PROGRESS, 4) == prefill
    assert w.prefill_attention_work(LONG, 512) == long_prefill
