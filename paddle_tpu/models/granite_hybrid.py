"""The ``granitemoehybrid`` decoder family for the serving path: Mamba-2
(state-space) layers beside GQA attention layers, a shared SwiGLU MLP in
every layer and, where the model has them, routed SwiGLU experts beside
it, Granite's four multipliers, no position encoding, a tied head.

Reference: the published ``modeling_granitemoehybrid`` (IBM Granite 4.0
"H" models); the mixer is Mamba-2 (Dao & Gu, arXiv:2405.21060).  Every
layer, with ``rm = residual_multiplier``::

    h0     = embed(ids) * embedding_multiplier
    h      = h + rm * Mixer(RMSNorm(h))        # Mamba-2 or attention
    h      = h + rm * MLP(RMSNorm(h))          # down(silu(gate) * up)
    logits = (RMSNorm(h) @ embed^T) / logits_scaling

or, in a model with routed experts (``num_local_experts`` > 0: H-small,
72 experts, 10 a token), with ``u = RMSNorm(h)``::

    h      = h + rm * (Experts(u) + MLP(u))
    Experts(u) = sum_{e in top_k(l)} softmax(l[top_k])_e * down_e(silu(gate_e u) * up_e u)

the router's logits ``l = u @ W_router`` in float32 with no bias, the
softmax over the ``num_experts_per_tok`` chosen logits alone, the gates
in the served dtype (the published ``GraniteMoeHybridTopKGating``).

What the serving runner needs of a family is here: the description
(:class:`GraniteHybridConfig`), the names and shapes of the weights
(``weight_shapes``, the published names, ``[in, out]``), and the Mamba
mixer's two bodies: ``mamba_prefill`` (a whole prompt by chunks: inside
a chunk the recurrence in its matmul form, the state carried from chunk
to chunk; returns the final state and the convolution's tail) and
``mamba_decode`` (one token a slot against the per-slot state pools),
each wrapped with its norm and residual add as a block part
(``mamba_prefill_block``, ``mamba_decode_block``).  The attention part
and the MLP part are ``models/generation.py``'s (``decode_attention``,
``prefill_attention``, ``mlp_block``), which read this description's
``position_embedding_type``, ``attention_multiplier`` and
``residual_multiplier``.  ``serving/parallel/recurrent.py`` walks
``blocks``.

The recurrent state ``S`` of a head is ``[P, N]`` (head dim by state
size).  It is held in the dtype the model is served in, as the
published cache allocates it (``HybridMambaAttentionDynamicCache``:
``ssm_states`` and ``conv_states`` in the model's dtype), and updated in
float32: one pool ``[mamba layers, slots, N, H * P]`` for all layers
and slots (``ops/pallas/ssm_update.py`` says why
that layout), with the convolution's last ``d_conv - 1`` inputs in a
second pool ``[mamba layers, slots, (d_conv - 1) * conv_dim]``.  ``B``
and ``C`` come in ``mamba_n_groups`` groups, each shared by
``mamba_n_heads / mamba_n_groups`` consecutive heads, whose channels
the gated norm normalises apart (Granite has one group; the
``nemotron_h`` family, ``models/nemotron_h.py``, whose Mamba blocks run
through these same bodies, has eight).  ``d_inner`` is heads times head
dim, whatever ``mamba_expand`` says of the hidden size.

A description says what its blocks are made of (``blocks``: for each
block the parts it runs in order, each with its own norm and residual
add).  Here every block is a mixer and then the shared MLP:
``("mamba", "mlp")`` or ``("attention", "mlp")``; with routed experts the
second part is ``"moe"``, the experts and the shared MLP under one norm
and one residual add (``expert_block``).  The expert part routes over all
``num_local_experts`` and computes the terms of the experts this chip
holds (``local_experts``: first, count), dropless; the others belong to
other chips and are left out (``deepseek_v3.routed_experts``).  The held
experts are the published stacks, ``[in, out]``:
``block_sparse_moe.input_linear.weight`` ``[held, hidden, 2 F]`` (gate |
up) and ``output_linear.weight`` ``[held, F, hidden]``.
"""
from __future__ import annotations

from dataclasses import InitVar, dataclass, field

import jax
import jax.numpy as jnp

from ..ops.pallas.ssm_update import select_ssm_state_update
from .deepseek_v3 import (MOE_COUNTERS, MOE_ROW_TILES, held_range,
                          routed_experts)
from .generation import _ffn, _mm, residual_add
from .llama_hybrid import _rms

__all__ = ["GraniteHybridConfig", "weight_shapes", "layer_weights",
           "mamba_prefill", "mamba_decode", "mamba_prefill_block",
           "mamba_decode_block", "expert_block", "state_shapes",
           "COUNTERS"]

HI = jax.lax.Precision.HIGHEST
EMBED = "model.embed_tokens.weight"
COUNTERS = ("ssm_rows_live",)
MOE = "model.layers.{}.block_sparse_moe."


class RecurrentDescription:
    """What a description of this family's kind derives from its
    ``layer_types`` (a kind a block) and its Mamba sizes."""

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @property
    def mamba_layers(self) -> tuple:
        return self.layers_of("mamba")

    @property
    def attention_layers(self) -> tuple:
        return self.layers_of("attention")

    def ordinal(self, i: int) -> int:
        """Block ``i``'s place among the blocks of its own kind: its
        row in that kind's pools."""
        return self.layer_types[:i].count(self.layer_types[i])


@dataclass
class GraniteHybridConfig(RecurrentDescription):
    """The published keys under the names the bodies read
    (:meth:`from_published` maps them).  ``intermediate_size`` is taken
    as a constructor keyword for the shared MLP's width, the name older
    calls gave it (the Granite-micro cell's driver; the keyword can go
    once that call names ``shared_intermediate_size``), and is no
    attribute: the published ``intermediate_size`` is one routed expert's
    width (``expert_intermediate_size``)."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    # the shared MLP's width; None: 8192, Granite-micro's
    shared_intermediate_size: int | None = None
    num_hidden_layers: int = 40
    # "mamba" or "attention" a layer; None: attention at 5, 15, 25, ...
    layer_types: tuple | None = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    position_embedding_type: str = "nope"
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    # routed experts: how many the router scores (0: H-micro's dense
    # layer), how many a token takes, one expert's width
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    expert_intermediate_size: int = 0
    # (first, count): the routed experts this chip holds; None = all
    local_experts: tuple | None = None
    dtype: str = "bfloat16"
    family: str = field(default="granitemoehybrid", init=False)
    # the router's rule (``deepseek_v3.route``)
    scoring_func: str = field(default="softmax_topk", init=False)
    intermediate_size: InitVar[int | None] = None

    def __post_init__(self, intermediate_size):
        if intermediate_size is not None:
            if self.shared_intermediate_size is not None:
                raise ValueError(
                    "the shared MLP's width is given twice: name it "
                    "shared_intermediate_size alone")
            self.shared_intermediate_size = intermediate_size
        elif self.shared_intermediate_size is None:
            self.shared_intermediate_size = 8192
        if self.layer_types is None:
            self.layer_types = tuple(
                "attention" if i % 10 == 5 else "mamba"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {"mamba", "attention"}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each 'mamba' or 'attention': {self.layer_types}")
        for name, want in (("mamba_proj_bias", False),
                           ("tie_word_embeddings", True),
                           ("position_embedding_type", "nope")):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"for the granitemoehybrid family (only {want!r})")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        if self.num_local_experts:
            if not (0 < self.num_experts_per_tok <= self.num_local_experts
                    and self.expert_intermediate_size > 0):
                raise ValueError(
                    "routed experts need 0 < num_experts_per_tok <= "
                    "num_local_experts and an expert_intermediate_size")
            self.local_experts = held_range(self.local_experts,
                                            self.num_local_experts)
        elif self.local_experts is not None:
            raise ValueError("local_experts names experts of a model "
                             "without routed experts")

    @classmethod
    def from_published(cls, m: dict, **kw):
        """The description of a published ``config.json`` (``m``);
        ``kw``: what the source does not say (``local_experts``,
        ``dtype``)."""
        for name, want in (("model_type", "granitemoehybrid"),
                           ("hidden_act", "silu"),
                           ("normalization_function", "rmsnorm"),
                           ("attention_bias", False)):
            if m[name] != want:
                raise ValueError(
                    f"{name}={m[name]!r} is not implemented for the "
                    f"granitemoehybrid family (only {want!r})")
        experts = int(m["num_local_experts"])
        return cls(
            shared_intermediate_size=m["shared_intermediate_size"],
            expert_intermediate_size=m["intermediate_size"] if experts else 0,
            layer_types=tuple(m["layer_types"]),
            **{k: m[k] for k in (
                "vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_expand",
                "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
                "embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling",
                "position_embedding_type", "rms_norm_eps",
                "max_position_embeddings", "tie_word_embeddings",
                "num_local_experts", "num_experts_per_tok")}, **kw)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def blocks(self) -> tuple:
        """The parts each block runs, in order: a mixer, then the MLP, or
        the experts and the MLP where the model has routed experts."""
        second = "moe" if self.num_local_experts else "mlp"
        return tuple((kind, second) for kind in self.layer_types)

    @property
    def counters(self) -> tuple:
        """What the decode step counts on the device."""
        if not self.num_local_experts:
            return COUNTERS
        return COUNTERS + MOE_COUNTERS + (MOE_ROW_TILES,)


# the width's older name is a constructor keyword alone: without this the
# InitVar's default would answer ``cfg.intermediate_size`` with None (so
# ``dataclasses.replace`` wants ``intermediate_size=None`` named)
del GraniteHybridConfig.intermediate_size

def kv_pack(cfg) -> int:
    """KV heads that share one row of the K/V pools: a head dim under
    the TPU's 128 lanes is stored two (or more) heads to a row, so the
    paged kernel's page copies are whole lane rows and no lane is
    padding (``generation.decode_layer`` reads the factor off the pool)."""
    pack = max(1, 128 // cfg.head_dim)
    while cfg.num_key_value_heads % pack:
        pack //= 2
    return pack


def state_shapes(cfg, slots: int) -> dict:
    """The two per-slot pools: {name: (shape, dtype)}."""
    n = len(cfg.mamba_layers)
    return {"ssm": ((n, slots, cfg.mamba_d_state, cfg.d_inner),
                    jnp.dtype(cfg.dtype)),
            "conv": ((n, slots, (cfg.mamba_d_conv - 1) * cfg.conv_dim),
                     jnp.dtype(cfg.dtype))}


# ------------------------------------------------------------------ weights
def weight_shapes(cfg: GraniteHybridConfig) -> dict:
    """{name: shape} of every leaf the serving state holds: one
    embedding matrix (the head is its transpose) and no ``lm_head``."""
    h, i = cfg.hidden_size, cfg.shared_intermediate_size
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    out = {EMBED: (cfg.vocab_size, h), "model.norm.weight": (h,)}
    for n, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{n}."
        out.update({
            p + "input_layernorm.weight": (h,),
            p + "post_attention_layernorm.weight": (h,),
            p + "shared_mlp.input_linear.weight": (h, 2 * i),
            p + "shared_mlp.output_linear.weight": (i, h)})
        if cfg.num_local_experts:
            e, f = MOE.format(n), cfg.expert_intermediate_size
            held = cfg.local_experts[1]
            out.update({
                e + "router.layer.weight": (h, cfg.num_local_experts),
                e + "input_linear.weight": (held, h, 2 * f),
                e + "output_linear.weight": (held, f, h)})
        if kind == "attention":
            a = p + "self_attn."
            out.update({a + "q_proj.weight": (h, q),
                        a + "k_proj.weight": (h, kv),
                        a + "v_proj.weight": (h, kv),
                        a + "o_proj.weight": (q, h)})
            continue
        m = p + "mamba."
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


def layer_weights(state: dict, cfg: GraniteHybridConfig, i: int) -> dict:
    """Layer ``i``'s leaves under the short names the bodies read; the
    MLP's and the attention's are ``generation.py``'s names."""
    p = f"model.layers.{i}."
    w = {"ln1": state[p + "input_layernorm.weight"],
         "ln2": state[p + "post_attention_layernorm.weight"],
         "gateup": state[p + "shared_mlp.input_linear.weight"],
         "down": state[p + "shared_mlp.output_linear.weight"]}
    if cfg.num_local_experts:
        e = MOE.format(i)
        w.update(router=state[e + "router.layer.weight"],
                 e_gateup=state[e + "input_linear.weight"],
                 e_down=state[e + "output_linear.weight"])
    if cfg.layer_types[i] == "attention":
        a = p + "self_attn."
        w.update({k: state[a + k + "_proj.weight"] for k in "qkvo"})
        return w
    m = p + "mamba."
    w.update({"in": state[m + "in_proj.weight"],
              "conv_w": state[m + "conv1d.weight"],
              "conv_b": state.get(m + "conv1d.bias"),
              "dt_bias": state[m + "dt_bias"], "A_log": state[m + "A_log"],
              "D": state[m + "D"], "norm": state[m + "norm.weight"],
              "out": state[m + "out_proj.weight"]})
    return w


# ------------------------------------------------------------ the mixer
def _split(cfg, zxbcdt):
    """``in_proj``'s output, last axis: [gate z | conv input xBC | dt]."""
    d, c = cfg.d_inner, cfg.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + c], zxbcdt[..., d + c:]


def _x_b_c(cfg, act):
    """The convolution's output, last axis [x | B | C], as (x [.., H *
    P], B [.., G, N], C [.., G, N])."""
    d, gn = cfg.d_inner, cfg.mamba_n_groups * cfg.mamba_d_state
    by_group = act.shape[:-1] + (cfg.mamba_n_groups, cfg.mamba_d_state)
    return (act[..., :d], act[..., d:d + gn].reshape(by_group),
            act[..., d + gn:].reshape(by_group))


def _conv_taps(w):
    """The depthwise convolution's (taps [conv_dim, d_conv], bias),
    float32."""
    f32 = jnp.float32
    bias = (0.0 if w["conv_b"] is None else w["conv_b"].astype(f32))
    return w["conv_w"].astype(f32), bias


def _dt_and_a(w, dt):
    """(softplus(dt + dt_bias) [.., H], A = -exp(A_log) [H]), float32."""
    f32 = jnp.float32
    return (jax.nn.softplus(dt.astype(f32) + w["dt_bias"].astype(f32)),
            -jnp.exp(w["A_log"].astype(f32)))


def _gate_and_out(cfg, w, y, z, dtype):
    """``out_proj(RMSNorm(y * silu(z)))``: the norm over each group's
    ``d_inner / G`` channels apart, computed in float32."""
    with jax.named_scope("ssm.gate"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        by_group = g.reshape(g.shape[:-1] + (cfg.mamba_n_groups, -1))
        var = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
        g = (by_group * jax.lax.rsqrt(var + cfg.rms_norm_eps)).reshape(
            g.shape).astype(dtype)
        g = g * w["norm"]
    with jax.named_scope("ssm.out"):
        return _mm(g, w["out"])


def _dot(a, b, dims, dtype):
    """A product of the chunked scan: operands in the model's dtype
    (float32 at the highest precision), float32 out."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=HI if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _chunk_scan(xdt, da, b, c, q: int, dtype):
    """The recurrence over ``S`` tokens by chunks of ``q`` (SSD, section
    6 of arXiv:2405.21060).  xdt [S, H, P] = dt * x; da [S, H] = dt * A
    (0 on padding: the state passes it unchanged); b, c [S, G, N], group
    ``g`` shared by heads ``g * H / G`` onwards.  Returns (y [S, H, P],
    final state [N, H * P]), float32."""
    s, h, p = xdt.shape
    g, n = b.shape[1:]
    hg = h // g                                 # heads a group
    nc = s // q
    f32 = jnp.float32
    causal = jnp.tril(jnp.ones((q, q), bool))

    def chunk(state, part):                     # state [G, N, hg * P]
        xdt_c, da_c, b_c, c_c = part
        cs = jnp.cumsum(da_c, axis=0)                       # [q, H]
        # within the chunk: y[t] = sum_{u<=t} (c_t.b_u) e^{cs_t-cs_u} xdt_u
        cb = _dot(c_c, b_c, (((2,), (2,)), ((1,), (1,))), dtype)  # [G,t,u]
        diff = cs[:, None, :] - cs[None, :, :]              # [t, u, H]
        decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
        m = (cb[:, None] * decay.transpose(2, 0, 1).reshape(g, hg, q, q)
             ).reshape(h, q, q)                             # [H, t, u]
        y = _dot(m, xdt_c.transpose(1, 0, 2),
                 (((2,), (1,)), ((0,), (0,))), dtype)       # [H, t, P]
        y = y.transpose(1, 0, 2)
        # what the carried state adds: (c_t . S) e^{cs_t}
        carried = _dot(c_c, state, (((2,), (1,)), ((1,), (0,))), dtype)
        y = y + (carried.transpose(1, 0, 2).reshape(q, h, p)
                 * jnp.exp(cs)[:, :, None])
        # the state after the chunk
        to_end = jnp.exp(cs[-1][None, :] - cs)              # [q, H]
        add = _dot(b_c, (xdt_c * to_end[:, :, None]).reshape(q, g, hg * p),
                   (((0,), (0,)), ((1,), (1,))), dtype)     # [G, N, hg*P]
        state = (state * jnp.repeat(jnp.exp(cs[-1]), p).reshape(
            g, 1, hg * p) + add)
        return state, y

    parts = (xdt.reshape(nc, q, h, p), da.reshape(nc, q, h),
             b.reshape(nc, q, g, n), c.reshape(nc, q, g, n))
    state, y = jax.lax.scan(chunk, jnp.zeros((g, n, hg * p), f32), parts)
    return y.reshape(s, h, p), state.transpose(1, 0, 2).reshape(n, h * p)


def mamba_prefill(cfg: GraniteHybridConfig, w: dict, h, length):
    """The mixer over one right-padded prompt: ``h`` [S, hidden] the
    normed input, ``length`` (traced scalar) its real tokens.  Returns
    (out [S, hidden], state [N, H * P] float32 after token ``length -
    1``, tail [(d_conv - 1) * conv_dim]: the convolution's last inputs,
    oldest first, zeros where the prompt is shorter)."""
    s = h.shape[0]
    f32 = jnp.float32
    nh, p, k = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_conv
    with jax.named_scope("ssm.in_proj"):
        z, xbc, dt = _split(cfg, _mm(h, w["in"]))
    with jax.named_scope("ssm.conv"):
        taps, bias = _conv_taps(w)
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        acc = bias + sum(padded[j:j + s].astype(f32) * taps[:, j]
                         for j in range(k))
        act = jax.nn.silu(acc)
        tail = jax.lax.dynamic_slice(
            padded, (length.astype(jnp.int32), jnp.int32(0)),
            (k - 1, cfg.conv_dim)).reshape(-1)
    with jax.named_scope("ssm.scan"):
        x, b, c = _x_b_c(cfg, act)
        x = x.reshape(s, nh, p)
        dtv, a = _dt_and_a(w, dt)
        dtv = jnp.where((jnp.arange(s) < length)[:, None], dtv, 0.0)
        q = min(cfg.mamba_chunk_size, s)
        pad = -s % q
        xdt = x * dtv[:, :, None]
        parts = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                 for v in (xdt, dtv * a, b, c)]
        y, state = _chunk_scan(*parts, q, jnp.dtype(h.dtype))
        y = y[:s] + w["D"].astype(f32)[None, :, None] * x
    out = _gate_and_out(cfg, w, y.reshape(s, cfg.d_inner), z, h.dtype)
    return out, state, tail


def mamba_decode(cfg: GraniteHybridConfig, w: dict, h, ssm, conv, lm,
                 active):
    """The mixer for one token a slot: ``h`` [slots, hidden] the normed
    input; ``ssm`` / ``conv`` the pools of every Mamba layer, passed
    whole and returned whole; ``lm`` this layer's row in them.  A
    parked slot's ``ssm`` row is left alone (``ssm_state_update``); its
    ``conv`` row is rewritten with what nothing reads, since an
    admission's prefill writes a slot's whole state."""
    f32 = jnp.float32
    p, k, cd = cfg.mamba_d_head, cfg.mamba_d_conv, cfg.conv_dim
    with jax.named_scope("ssm.in_proj"):
        z, xbc, dt = _split(cfg, _mm(h, w["in"]))
    with jax.named_scope("ssm.conv"):
        taps, bias = _conv_taps(w)
        tail = conv[lm]
        window = [tail[:, j * cd:(j + 1) * cd] for j in range(k - 1)]
        window.append(xbc.astype(conv.dtype))
        act = jax.nn.silu(bias + sum(
            window[j].astype(f32) * taps[:, j] for j in range(k)))
        conv = conv.at[lm].set(jnp.concatenate(window[1:], axis=-1))
    with jax.named_scope("ssm.update"):
        x, b, c = _x_b_c(cfg, act)
        dtv, a = _dt_and_a(w, dt)
        ssm, y = select_ssm_state_update()(
            ssm, lm, jnp.repeat(jnp.exp(dtv * a), p, axis=1),
            jnp.repeat(dtv, p, axis=1) * x, b, c, active)
        y = y + jnp.repeat(w["D"].astype(f32), p)[None, :] * x
    return _gate_and_out(cfg, w, y, z, h.dtype), ssm, conv


# ----------------------------------------------------------- block parts
def mamba_prefill_block(cfg, w, x, length):
    """x [1, S, hidden] -> (x, state, tail): the Mamba mixer over a
    prompt, with its norm and its residual add."""
    h = _rms(x, w["ln1"], cfg.rms_norm_eps)[0]
    out, state, tail = mamba_prefill(cfg, w, h, length)
    return residual_add(x, out[None], cfg), state, tail


def mamba_decode_block(cfg, w, lm, x, ssm, conv, active):
    """x [slots, hidden] -> (x, ssm, conv): the Mamba mixer for one
    token a slot, with its norm and its residual add; ``lm`` its row in
    the pools."""
    h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
    out, ssm, conv = mamba_decode(cfg, w, h, ssm, conv, lm, active)
    return residual_add(x, out, cfg), ssm, conv


def expert_block(cfg, w, x, valid, tile: int):
    """x [T, hidden] -> (x + rm * (Experts(u) + MLP(u)), MoE counts):
    ``u = RMSNorm_2(x)``; the held experts' terms and the shared MLP,
    whole, under the block's second norm and one residual add; tokens
    where ``valid`` [T] is false choose no expert."""
    h = _rms(x[:, None], w["ln2"], cfg.rms_norm_eps)[:, 0]
    routed, counts = routed_experts(cfg, w, h, valid, tile)
    with jax.named_scope("moe.shared"):
        y = (_ffn(w, h).astype(jnp.float32) + routed).astype(h.dtype)
    return residual_add(x, y, cfg), counts
