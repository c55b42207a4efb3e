"""A dense decoder-only transformer with full attention, for the
configurations in this directory that name it (``"architecture":
"dense_architecture"``): the program's model for a configuration file, its
weight rules and its work counts.

Every leaf of the dense layout has a rule among the shared ones
(``benchlib.weights``), so this module adds none.  Work is counted from
the file's shapes: every token multiplies every weight of a layer, and a
key costs its K and V heads.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Tuple

from benchlib.work import Progress, ctx_sum


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    d, h = conf["hidden_size"], conf["num_attention_heads"]
    if conf["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {conf['hidden_act']!r}")
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=conf["num_hidden_layers"],
        d_model=d, num_heads=h, num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or d // h,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        activation="swiglu", attn_type="full",
        qk_norm=bool(conf.get("qk_layernorm") or conf.get("use_qk_norm")),
        norm=conf["norm"], norm_eps=float(conf["norm_eps"]),
        parallel_block=bool(conf["parallel_block"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"])


def weight_rule(path: str, shape: Tuple[int, ...], d_model: int):
    """No leaf beyond the shared ones."""
    return None


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool            # SwiGLU: three FFN matrices, else two
    tied: bool             # LM head is the embedding's transpose
    qk_norm: bool
    layernorm: bool        # LayerNorm (scale + bias) vs RMSNorm (scale)
    parallel_block: bool   # one norm per block
    weight_bytes: int = 2  # bf16
    kv_bytes: int = 2      # bf16 KV pages
    act_bytes: int = 2     # bf16 activations (q, attention output)


def shapes_of(conf: Dict[str, Any]) -> Shapes:
    """Shapes of a configuration file (``bench/configs/*.json``)."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return Shapes(
        layers=conf["num_hidden_layers"], d_model=d, heads=h,
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or d // h,
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        gated=conf["hidden_act"] == "silu",
        tied=bool(conf["tie_word_embeddings"]),
        qk_norm=bool(conf.get("qk_layernorm") or conf.get("use_qk_norm")),
        layernorm=conf["norm"] == "layernorm",
        parallel_block=bool(conf["parallel_block"]))


def layer_matmul_params(s: Shapes) -> int:
    """Parameters of one layer that a token multiplies: q, k, v, o and
    the FFN."""
    attn = 2 * s.d_model * s.heads * s.head_dim \
        + 2 * s.d_model * s.kv_heads * s.head_dim
    return attn + (3 if s.gated else 2) * s.d_model * s.d_ff


def num_params(s: Shapes) -> int:
    """Every parameter: embedding, untied head, layers, norms."""
    norm = (2 if s.layernorm else 1) * s.d_model
    per_layer = layer_matmul_params(s) \
        + (1 if s.parallel_block else 2) * norm \
        + (2 * s.head_dim if s.qk_norm else 0)
    embed = s.vocab * s.d_model * (1 if s.tied else 2)
    return embed + s.layers * per_layer + norm


def served_flops(s: Shapes, progress: Iterable[Progress]) -> int:
    """Model FLOPs of the work a window served.

    Each prompt token prefilled goes through every layer's matmuls and
    attends causally over its own prefix.  The first generated token costs
    the LM head only (its layers ran with the prompt); each later one goes
    through every layer at its context and the head.
    """
    mm = 2 * s.layers * layer_matmul_params(s)
    att = 4 * s.layers * s.heads * s.head_dim      # per (query, key) pair
    head = 2 * s.d_model * s.vocab
    total = 0
    for plen, p0, p1, g0, g1 in progress:
        total += mm * max(p1 - p0, 0) + att * ctx_sum(p0, p1)
        a = max(g0, 1)
        n = max(g1 - a, 0)
        # token j >= 1 attends over plen + j keys
        total += mm * n + att * ctx_sum(a, g1, offset=plen - 1)
        total += head * max(g1 - g0, 0)
    return total


def decode_attention_work(s: Shapes, progress: Iterable[Progress]
                          ) -> Tuple[int, int]:
    """(FLOPs, bytes) of the paged decode attention the window served:
    each decode token's query against its live context's K and V, over
    every layer.  Pages past the live context are not work."""
    flops = nbytes = 0
    per_key_flops = 4 * s.heads * s.head_dim
    per_key_bytes = 2 * s.kv_heads * s.head_dim * s.kv_bytes
    per_query_bytes = 2 * s.heads * s.head_dim * s.act_bytes
    for plen, _p0, _p1, g0, g1 in progress:
        a = max(g0, 1)
        n = max(g1 - a, 0)
        keys = ctx_sum(a, g1, offset=plen - 1)
        flops += per_key_flops * keys
        nbytes += per_key_bytes * keys + per_query_bytes * n
    return flops * s.layers, nbytes * s.layers


def prefill_attention_work(s: Shapes, progress: Iterable[Progress],
                           chunk: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of the chunked-prefill attention the window served:
    each chunk's queries attend causally over the prompt prefix up to the
    chunk's end, whose K and V are read once per chunk."""
    flops = nbytes = 0
    per_key_bytes = 2 * s.kv_heads * s.head_dim * s.kv_bytes
    per_query_bytes = 2 * s.heads * s.head_dim * s.act_bytes
    for _plen, p0, p1, _g0, _g1 in progress:
        start = p0
        while start < p1:
            end = min(start + chunk, p1)
            flops += 4 * s.heads * s.head_dim * ctx_sum(start, end)
            nbytes += per_key_bytes * end + per_query_bytes * (end - start)
            start = end
    return flops * s.layers, nbytes * s.layers


@dataclasses.dataclass(frozen=True)
class DenseWork:
    """``benchlib.work.Work`` of one configuration."""
    shapes: Shapes

    def num_params(self) -> int:
        return num_params(self.shapes)

    def served_flops(self, progress: Iterable[Progress]) -> int:
        return served_flops(self.shapes, progress)

    def decode_attention_work(self, progress: Iterable[Progress]
                              ) -> Tuple[int, int]:
        return decode_attention_work(self.shapes, progress)

    def prefill_attention_work(self, progress: Iterable[Progress],
                               chunk: int) -> Tuple[int, int]:
        return prefill_attention_work(self.shapes, progress, chunk)


def work(conf: Dict[str, Any]) -> DenseWork:
    return DenseWork(shapes_of(conf))
