"""A routed-expert decoder with full attention, as a configuration file
names it (``"architecture": "moe_architecture"``): the program's model,
the weight rule of the leaf it adds (the router) and its work counts.

Layout: ``first_k_dense_replace`` leading layers with a dense SwiGLU FFN of
``intermediate_size``, then layers whose FFN is ``n_routed_experts``
experts of ``moe_intermediate_size``, ``num_experts_per_tok`` of them
chosen per token by a softmax router (the top-k renormalised), plus
``n_shared_experts`` always-on experts as one FFN of their summed width.
A token's work in an expert layer is the router, its top-k experts and the
shared one; attention is full, counted as the dense architecture counts
it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterable, Tuple

from benchlib import spec
from benchlib.work import Progress, ctx_sum

_DENSE = spec.load_architecture({"architecture": "dense_architecture"})


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig, MoEConfig

    if conf["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {conf['hidden_act']!r}")
    if conf["scoring_func"] != "softmax" or not conf["norm_topk_prob"]:
        raise ValueError("the program's router is a softmax with its "
                         "top-k renormalised")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    e, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    f, ns = conf["moe_intermediate_size"], conf["n_shared_experts"]
    moe = MoEConfig(
        num_experts=e, top_k=k, d_expert=f, num_shared_experts=ns,
        d_shared_expert=ns * f,
        # every expert can take every token of a call, so none is dropped
        # (the published models route without a capacity at inference)
        capacity_factor=e / k,
        first_dense_layers=conf["first_k_dense_replace"],
        first_dense_d_ff=conf["intermediate_size"])
    return ModelConfig(
        name=conf["name"], family="moe", num_layers=conf["num_hidden_layers"],
        d_model=d, num_heads=h, num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or d // h,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        activation="swiglu", attn_type="full", norm=conf["norm"],
        norm_eps=float(conf["norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]), moe=moe,
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"])


def weight_rule(path: str, shape: Tuple[int, ...], d_model: int):
    """The router [L, d, E]: logits of about unit spread per expert."""
    if path.rsplit("/", 1)[-1] == "router":
        return "normal", 1.0 / math.sqrt(d_model)
    return None


@dataclasses.dataclass(frozen=True)
class MoEWork:
    """``benchlib.work.Work`` of one configuration."""
    attn: Any              # the dense architecture's shapes, for attention
    dense_layers: int
    d_ff: int              # the leading dense layers' FFN
    experts: int
    top_k: int
    d_expert: int
    d_shared: int

    def _layer_attn(self) -> int:
        s = self.attn
        return 2 * s.d_model * s.heads * s.head_dim \
            + 2 * s.d_model * s.kv_heads * s.head_dim

    def _layer_mm(self) -> Tuple[int, int]:
        """Parameters a token multiplies in a dense and in an expert
        layer."""
        d = self.attn.d_model
        dense = self._layer_attn() + 3 * d * self.d_ff
        expert = self._layer_attn() + d * self.experts \
            + self.top_k * 3 * d * self.d_expert + 3 * d * self.d_shared
        return dense, expert

    def num_params(self) -> int:
        s = self.attn
        d, norms = s.d_model, 2 * s.d_model
        dense = self._layer_attn() + 3 * d * self.d_ff + norms
        expert = self._layer_attn() + d * self.experts \
            + self.experts * 3 * d * self.d_expert \
            + 3 * d * self.d_shared + norms
        embed = s.vocab * d * (1 if s.tied else 2)
        return embed + self.dense_layers * dense \
            + (s.layers - self.dense_layers) * expert + d

    def served_flops(self, progress: Iterable[Progress]) -> int:
        """As the dense count, with each expert layer's active matmuls."""
        s = self.attn
        dense, expert = self._layer_mm()
        mm = 2 * (self.dense_layers * dense
                  + (s.layers - self.dense_layers) * expert)
        att = 4 * s.layers * s.heads * s.head_dim
        head = 2 * s.d_model * s.vocab
        total = 0
        for plen, p0, p1, g0, g1 in progress:
            total += mm * max(p1 - p0, 0) + att * ctx_sum(p0, p1)
            a = max(g0, 1)
            total += mm * max(g1 - a, 0) \
                + att * ctx_sum(a, g1, offset=plen - 1)
            total += head * max(g1 - g0, 0)
        return total

    def decode_attention_work(self, progress: Iterable[Progress]
                              ) -> Tuple[int, int]:
        return _DENSE.decode_attention_work(self.attn, progress)

    def prefill_attention_work(self, progress: Iterable[Progress],
                               chunk: int) -> Tuple[int, int]:
        return _DENSE.prefill_attention_work(self.attn, progress, chunk)


def work(conf: Dict[str, Any]) -> MoEWork:
    # attention and the model's edges read as a dense model's (every layer
    # has two norms); the FFN is counted here
    attn = _DENSE.shapes_of(dict(conf, parallel_block=False))
    return MoEWork(attn, conf["first_k_dense_replace"],
                   conf["intermediate_size"], conf["n_routed_experts"],
                   conf["num_experts_per_tok"],
                   conf["moe_intermediate_size"],
                   conf["n_shared_experts"] * conf["moe_intermediate_size"])
