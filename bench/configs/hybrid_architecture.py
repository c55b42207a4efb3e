"""A hybrid Mamba-2 / attention decoder laid out by a per-layer pattern, as a
configuration file names it (``"architecture": "hybrid_architecture"``):
Nemotron-H's ``hybrid_override_pattern``, one pre-RMSNorm mixer per layer
with its own weights, added to the residual.  ``M`` is a Mamba-2 mixer,
``-`` a squared-ReLU MLP without a gate, ``*`` grouped-query attention
without a rotary embedding.  The program's model, the weight rules of the
Mamba-2 leaves and the work counts.

Work: every token multiplies every projection of every layer.  A Mamba-2
layer's recurrence costs a token 5·H·P·N (the state decays and takes
dt·x⊗B: three operations an element; the output contracts the state with
C: two), its depthwise conv 2·k a channel; attention is counted as the
dense architecture counts it, for the attention layers alone.  The SSD
kernel's own count (``ssd_prefill_work``) is that of the chunked algorithm
over each engine chunk.
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
    from repro.models.config import ModelConfig, SSMConfig

    pattern = conf["hybrid_override_pattern"]
    if len(pattern) != conf["num_hidden_layers"]:
        raise ValueError("the pattern names one character per layer")
    if conf["mlp_hidden_act"] != "relu2" or conf["mamba_hidden_act"] != "silu":
        raise ValueError("the program's MLP is relu2 and its Mamba-2 silu")
    if conf["attention_bias"] or conf["mlp_bias"] or conf["mamba_proj_bias"] \
            or not conf["use_conv_bias"]:
        raise ValueError("projections without bias, the conv with one")
    if "rope_theta" in conf:
        raise ValueError("the attention layers take no rotary embedding")
    if conf["rms_norm_eps"] != conf["layer_norm_epsilon"]:
        raise ValueError("the program has one norm epsilon")
    if conf["time_step_limit"][0] != 0 or conf["time_step_limit"][1]:
        raise ValueError("dt is not clamped")
    d, h = conf["hidden_size"], conf["mamba_num_heads"]
    if h * conf["mamba_head_dim"] != conf["expand"] * d:
        raise ValueError("Mamba-2 heads times head dim is expand·d")
    ssm = SSMConfig(
        d_state=conf["ssm_state_size"], d_conv=conf["conv_kernel"],
        expand=conf["expand"], head_dim=conf["mamba_head_dim"],
        chunk_size=conf["chunk_size"], n_groups=conf["n_groups"],
        dt_min=conf["time_step_min"], dt_max=conf["time_step_max"])
    return ModelConfig(
        name=conf["name"], family="pattern", layer_pattern=pattern,
        num_layers=len(pattern), d_model=d,
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["attention_head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], activation="relu2", attn_type="full",
        use_rope=False, norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]), ssm=ssm,
        param_dtype=conf["torch_dtype"], compute_dtype=conf["torch_dtype"])


def weight_rule(path: str, shape: Tuple[int, ...], d_model: int):
    """The Mamba-2 leaves.  The shared rules draw normals about 0 or 1
    only, so ``a_log`` and ``dt_bias`` take normals near the published
    initialisation's ranges (the configuration's ``assumed``); ``D`` is
    1."""
    name = path.rsplit("/", 1)[-1]
    if name == "in_proj":                       # [n, d, 2·di + 2·G·N + H]
        return "normal", 1.0 / math.sqrt(shape[-2])
    if name == "out_proj":                      # [n, di, d]
        return "normal", 1.0 / math.sqrt(shape[-2])
    if name == "conv_w":                        # [n, channels, k]
        return "normal", 1.0 / math.sqrt(shape[-1])
    if name == "conv_b":                        # spread of U(±1/√k), k 4
        return "normal", 1.0 / math.sqrt(12.0)
    if name == "a_log":                         # log A, A about 0.4-2.7
        return "normal", 0.5
    if name == "dt_bias":                       # dt from about 0.03 to 3.5
        return "normal", 1.5
    if name == "d_skip":
        return "one_plus", 0.0
    if name == "out_norm":
        return "one_plus", 0.1
    return None


@dataclasses.dataclass(frozen=True)
class HybridWork:
    """``benchlib.work.Work`` of one configuration, with the counts of the
    SSD kernel and of a decode call."""
    attn: Any              # the dense architecture's shapes, attention layers
    pattern: str
    d_model: int
    d_ff: int
    vocab: int
    heads: int             # Mamba-2 heads H
    head_dim: int          # P
    state: int             # N
    groups: int            # G
    conv: int              # k
    weight_bytes: int = 2  # bf16 weights, activations and conv state
    act_bytes: int = 2
    ssm_bytes: int = 4     # float32 SSM state and dt

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def _mamba_mm(self) -> int:
        """Parameters of a Mamba-2 layer that a token multiplies."""
        return self.d_model * (2 * self.d_inner + 2 * self.groups * self.state
                               + self.heads) + self.d_inner * self.d_model

    def _attn_mm(self) -> int:
        s = self.attn
        return 2 * s.d_model * s.heads * s.head_dim \
            + 2 * s.d_model * s.kv_heads * s.head_dim

    def _layer_params(self) -> Dict[str, int]:
        d = self.d_model
        mamba = self._mamba_mm() + (self.conv + 1) * self.conv_dim \
            + 3 * self.heads + self.d_inner
        return {"M": mamba + d, "-": 2 * d * self.d_ff + d,
                "*": self._attn_mm() + d}

    def num_params(self) -> int:
        """Every parameter: embedding, untied head, layers, norms."""
        per = self._layer_params()
        layers = sum(per[k] for k in self.pattern)
        return 2 * self.vocab * self.d_model + layers + self.d_model

    def state_bytes(self) -> int:
        """One slot's recurrent state over every Mamba-2 layer: the conv
        tail (k − 1 entries of the pre-conv stream) and the SSM state."""
        return self.count("M") * (
            (self.conv - 1) * self.conv_dim * self.act_bytes
            + self.heads * self.head_dim * self.state * self.ssm_bytes)

    def _token_flops(self) -> int:
        """Model FLOPs of one token through every layer, attention over
        the context aside."""
        mm = self.count("M") * self._mamba_mm() \
            + self.count("-") * 2 * self.d_model * self.d_ff \
            + self.count("*") * self._attn_mm()
        ssm = self.count("M") * (5 * self.heads * self.head_dim * self.state
                                 + 2 * self.conv * self.conv_dim)
        return 2 * mm + ssm

    def served_flops(self, progress: Iterable[Progress]) -> int:
        """Each prompt token prefilled and each generated token after the
        first goes through every layer (attention at its context); every
        generated token costs the LM head."""
        tok = self._token_flops()
        att = 4 * self.attn.layers * self.attn.heads * self.attn.head_dim
        head = 2 * self.d_model * self.vocab
        total = 0
        for plen, p0, p1, g0, g1 in progress:
            a = max(g0, 1)
            total += tok * (max(p1 - p0, 0) + max(g1 - a, 0))
            total += att * (ctx_sum(p0, p1) + ctx_sum(a, g1, offset=plen - 1))
            total += head * max(g1 - g0, 0)
        return total

    def decode_attention_work(self, progress: Iterable[Progress]
                              ) -> Tuple[int, int]:
        return _DENSE.decode_attention_work(self.attn, progress)

    def prefill_attention_work(self, progress: Iterable[Progress],
                               chunk: int) -> Tuple[int, int]:
        return _DENSE.prefill_attention_work(self.attn, progress, chunk)

    def ssd_prefill_work(self, progress: Iterable[Progress],
                         chunk: int) -> Tuple[int, int]:
        """(FLOPs, bytes) of the chunked SSD algorithm over each engine
        chunk of the prompts served, every Mamba-2 layer.  A chunk of Q
        tokens costs C·Bᵀ (2Q²N a group), the decay mask (Q² a head), the
        masked product with dt·x (2Q²P a head), the carried state's
        contribution and the state's update (2QNP each a head); it moves x,
        dt, B, C, y and the chunk's initial and final state."""
        H, P, N, G = self.heads, self.head_dim, self.state, self.groups
        flops = nbytes = 0
        for _plen, p0, p1, _g0, _g1 in progress:
            start = p0
            while start < p1:
                q = min(start + chunk, p1) - start
                flops += G * 2 * q * q * N \
                    + H * (q * q + 2 * q * q * P + 4 * q * N * P)
                nbytes += q * (2 * H * P * self.act_bytes + H * self.ssm_bytes
                               + 2 * G * N * self.act_bytes) \
                    + 2 * H * P * N * self.ssm_bytes
                start += q
        m = self.count("M")
        return flops * m, nbytes * m

    def decode_step_bytes(self, progress: Iterable[Progress],
                          calls: int) -> Tuple[int, int]:
        """(FLOPs, bytes) of ``calls`` decode calls that produced the
        tokens in ``progress``: every call reads every layer weight and the
        LM head; each decode row (a generated token after the first) reads
        and writes its slot's recurrent state, reads its live KV and its
        embedding row, and writes its new KV."""
        progress = list(progress)
        d = self.d_model
        per = self._layer_params()
        weights = (sum(per[k] for k in self.pattern) + d
                   + self.vocab * d) * self.weight_bytes
        rows = sum(max(g1 - max(g0, 1), 0)
                   for _pl, _p0, _p1, g0, g1 in progress)
        s = self.attn
        kv_token = 2 * s.kv_heads * s.head_dim * s.kv_bytes * s.layers
        att_flops, att_bytes = self.decode_attention_work(progress)
        flops = rows * (self._token_flops() + 2 * d * self.vocab) + att_flops
        nbytes = calls * weights + att_bytes + rows * (
            2 * self.state_bytes() + d * self.weight_bytes + kv_token)
        return flops, nbytes


def work(conf: Dict[str, Any]) -> HybridWork:
    pattern = conf["hybrid_override_pattern"]
    # the attention layers read as a dense model's attention
    attn = _DENSE.Shapes(
        layers=pattern.count("*"), d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf["attention_head_dim"], d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], gated=False, tied=False, qk_norm=False,
        layernorm=False, parallel_block=False)
    return HybridWork(
        attn, pattern, conf["hidden_size"], conf["intermediate_size"],
        conf["vocab_size"], conf["mamba_num_heads"], conf["mamba_head_dim"],
        conf["ssm_state_size"], conf["n_groups"], conf["conv_kernel"])
