"""The ``nemotron_h`` decoder family for the serving path: blocks that
are ONE mixer or ONE feed-forward part each (a Mamba-2 mixer in groups,
GQA attention without a position term, or a routed-expert layer of
two-matrix ``relu^2`` experts of which this chip holds a share), an
untied head.

Reference: the published ``modeling_nemotron_h`` (NVIDIA Nemotron-H /
Nemotron 3 Nano); the mixer is Mamba-2 (Dao & Gu, arXiv:2405.21060),
the router DeepSeek-V3's bias-corrected sigmoid router
(arXiv:2412.19437, section 2.1.2).  Every block ``i`` of
``hybrid_override_pattern`` (``M`` Mamba, ``*`` attention, ``E``
experts)::

    h0     = embed(ids)
    h      = h + Part_i(RMSNorm_i(h))
    logits = RMSNorm(h) @ lm_head

    E: out = sum_e w_e * down_e(relu(up_e u)^2) + down_s(relu(up_s u)^2)

Nothing here is a second copy of a layer body.  The description
(:class:`NemotronHConfig`) carries the names the shared bodies read:
the Mamba blocks run through ``granite_hybrid.mamba_prefill`` /
``mamba_decode`` (``mamba_n_groups`` groups of B and C, the gated norm a
group, ``d_inner`` = heads x head dim), the attention blocks through
``generation.prefill_attention`` / ``decode_attention``
(``position_embedding_type`` "nope": the published ``NemotronHAttention``
applies no rotary embedding, the Mamba blocks carry position), the
expert blocks through ``deepseek_v3.route`` / ``routed_experts``
(``mlp_hidden_act`` "relu2": two matrices an expert, no gate;
``local_experts`` the share held here, the other chips' terms left
out).  ``serving/parallel/recurrent.py`` walks ``blocks``.

Weights are ``[in, out]`` under the published parameter names; the
experts a chip holds are stacked and, where ``moe_intermediate_size`` is
no whole number of 128-lane tiles (1,856 = 14.5), padded with zeros to
the next one (``expert_width``: ``mixer.experts.up_proj.weight``
``[held, hidden, 1920]``, ``down_proj`` ``[held, 1920, hidden]``;
``pad_experts`` does it for a loader, once).  ``relu(0)^2`` is 0 and the
added rows of ``down`` multiply it, so the function is the published
one; without the padding XLA copies each ``[16, 2688, 1856]`` stack into
a tiled layout before every ``grouped_matmul`` call (12.3 ms of a 31 ms
decode step, PERF.md PR 34).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp

from .deepseek_v3 import MOE_COUNTERS, expert_ffn, held_range
from .generation import residual_add
from .granite_hybrid import COUNTERS as SSM_COUNTERS
from .granite_hybrid import RecurrentDescription
from .llama_hybrid import _rms

__all__ = ["NemotronHConfig", "weight_shapes", "layer_weights",
           "block_types", "expert_block", "pad_experts", "COUNTERS"]

EMBED = "backbone.embeddings.weight"
NORM = "backbone.norm_f.weight"
HEAD = "lm_head.weight"
COUNTERS = SSM_COUNTERS + MOE_COUNTERS
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}
LANES = 128


def block_types(pattern: str) -> tuple:
    """``hybrid_override_pattern`` as a block kind a character."""
    unknown = set(pattern) - set(KINDS)
    if unknown or not pattern:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: each block is one of "
            f"{sorted(KINDS)} (a dense '-' MLP block is not implemented)")
    return tuple(KINDS[c] for c in pattern)


@dataclass
class NemotronHConfig(RecurrentDescription):
    """The published keys under the names the shared bodies read
    (:meth:`from_published` maps them)."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_n_heads: int = 64             # mamba_num_heads
    mamba_d_head: int = 64              # mamba_head_dim
    mamba_d_state: int = 128            # ssm_state_size
    mamba_n_groups: int = 8             # n_groups
    mamba_d_conv: int = 4               # conv_kernel
    mamba_chunk_size: int = 128         # chunk_size
    mamba_conv_bias: bool = True        # use_conv_bias
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    mlp_hidden_act: str = "relu2"
    rms_norm_eps: float = 1e-5          # layer_norm_epsilon
    max_position_embeddings: int = 262144
    position_embedding_type: str = "nope"
    # (first, count): the routed experts this chip holds; None = all
    local_experts: tuple | None = None
    dtype: str = "bfloat16"
    family: str = field(default="nemotron_h", init=False)

    def __post_init__(self):
        self.layer_types = block_types(self.hybrid_override_pattern)
        self.local_experts = held_range(self.local_experts,
                                        self.n_routed_experts)
        for name, want in (("mlp_hidden_act", "relu2"),
                           ("n_shared_experts", 1),
                           ("position_embedding_type", "nope")):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"for the nemotron_h family (only {want!r})")
        if (self.n_routed_experts % self.n_group
                or self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError("n_group must divide n_routed_experts and "
                             "mamba_n_groups mamba_n_heads")

    @classmethod
    def from_published(cls, m: dict, **kw):
        """The description of a published ``config.json`` (``m``);
        ``kw``: what the source does not say (``local_experts``,
        ``dtype``, ``position_embedding_type``)."""
        for name, want in (("model_type", "nemotron_h"),
                           ("mamba_hidden_act", "silu"),
                           ("use_bias", False), ("mamba_proj_bias", False),
                           ("mlp_bias", False), ("attention_bias", False),
                           ("tie_word_embeddings", False)):
            if m[name] != want:
                raise ValueError(
                    f"{name}={m[name]!r} is not implemented for the "
                    f"nemotron_h family (only {want!r})")
        if len(m["hybrid_override_pattern"]) != m["num_hidden_layers"]:
            raise ValueError("hybrid_override_pattern must name "
                             "num_hidden_layers blocks")
        return cls(
            mamba_n_heads=m["mamba_num_heads"],
            mamba_d_head=m["mamba_head_dim"],
            mamba_d_state=m["ssm_state_size"], mamba_n_groups=m["n_groups"],
            mamba_d_conv=m["conv_kernel"], mamba_chunk_size=m["chunk_size"],
            mamba_conv_bias=m["use_conv_bias"],
            rms_norm_eps=m["layer_norm_epsilon"],
            **{k: m[k] for k in (
                "vocab_size", "hidden_size", "hybrid_override_pattern",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "n_routed_experts",
                "n_shared_experts", "num_experts_per_tok", "n_group",
                "topk_group", "routed_scaling_factor", "norm_topk_prob",
                "mlp_hidden_act", "max_position_embeddings")}, **kw)

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def expert_width(self) -> int:
        """``moe_intermediate_size`` in whole lane tiles: the held
        experts' stored width."""
        return -(-self.moe_intermediate_size // LANES) * LANES

    @property
    def blocks(self) -> tuple:
        """The parts each block runs, in order: its one part."""
        return tuple((kind,) for kind in self.layer_types)

    @property
    def counters(self) -> tuple:
        """What the decode step counts on the device."""
        return COUNTERS


# ------------------------------------------------------------------ weights
def weight_shapes(cfg: NemotronHConfig) -> dict:
    """{name: shape} of every leaf the serving state holds."""
    h = cfg.hidden_size
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    held, fm = cfg.local_experts[1], cfg.expert_width
    fs = cfg.moe_shared_expert_intermediate_size
    out = {EMBED: (cfg.vocab_size, h), NORM: (h,),
           HEAD: (h, cfg.vocab_size)}
    for n, kind in enumerate(cfg.layer_types):
        p = f"backbone.layers.{n}."
        m = p + "mixer."
        out[p + "norm.weight"] = (h,)
        if kind == "attention":
            out.update({m + "q_proj.weight": (h, q),
                        m + "k_proj.weight": (h, kv),
                        m + "v_proj.weight": (h, kv),
                        m + "o_proj.weight": (q, h)})
        elif kind == "moe":
            out.update({
                m + "gate.weight": (h, cfg.n_routed_experts),
                m + "gate.e_score_correction_bias": (cfg.n_routed_experts,),
                m + "shared_experts.up_proj.weight": (h, fs),
                m + "shared_experts.down_proj.weight": (fs, h),
                m + "experts.up_proj.weight": (held, h, fm),
                m + "experts.down_proj.weight": (held, fm, h)})
        else:
            out.update({
                m + "in_proj.weight": (
                    h, cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads),
                m + "conv1d.weight": (cfg.conv_dim, cfg.mamba_d_conv),
                m + "dt_bias": (cfg.mamba_n_heads,),
                m + "A_log": (cfg.mamba_n_heads,),
                m + "D": (cfg.mamba_n_heads,),
                m + "norm.weight": (cfg.d_inner,),
                m + "out_proj.weight": (cfg.d_inner, h)})
            if cfg.mamba_conv_bias:
                out[m + "conv1d.bias"] = (cfg.conv_dim,)
    return out


def layer_weights(state: dict, cfg: NemotronHConfig, i: int) -> dict:
    """Block ``i``'s leaves under the short names the shared bodies
    read (``ln1`` its one norm)."""
    p = f"backbone.layers.{i}."
    m = p + "mixer."
    w = {"ln1": state[p + "norm.weight"]}
    kind = cfg.layer_types[i]
    if kind == "attention":
        w.update({k: state[m + k + "_proj.weight"] for k in "qkvo"})
    elif kind == "moe":
        w.update(router=state[m + "gate.weight"],
                 router_bias=state[m + "gate.e_score_correction_bias"],
                 up=state[m + "shared_experts.up_proj.weight"],
                 down=state[m + "shared_experts.down_proj.weight"],
                 e_up=state[m + "experts.up_proj.weight"],
                 e_down=state[m + "experts.down_proj.weight"])
    else:
        w.update({"in": state[m + "in_proj.weight"],
                  "conv_w": state[m + "conv1d.weight"],
                  "conv_b": state.get(m + "conv1d.bias"),
                  "dt_bias": state[m + "dt_bias"],
                  "A_log": state[m + "A_log"], "D": state[m + "D"],
                  "norm": state[m + "norm.weight"],
                  "out": state[m + "out_proj.weight"]})
    return w


def pad_experts(cfg: NemotronHConfig, up, down):
    """The held experts' published stacks ``up [held, hidden, F]`` and
    ``down [held, F, hidden]`` with zeros up to ``expert_width``: what a
    loader stores, once."""
    pad = cfg.expert_width - up.shape[-1]
    return (jnp.pad(up, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(down, ((0, 0), (0, pad), (0, 0))))


# ---------------------------------------------------------- the expert block
def expert_block(cfg, w: dict, x, valid, tile: int):
    """x [T, hidden] -> (x + Experts(RMSNorm(x)), MoE counts [3]): the
    routed-expert part with its norm and its residual add; tokens where
    ``valid`` [T] is false choose no expert."""
    h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
    y, counts = expert_ffn(cfg, w, h, valid, tile)
    return residual_add(x, y, cfg), counts
