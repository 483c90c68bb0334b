"""Llama-family decoder LM, TPU-first.

Reference: the in-tree auto-parallel Llama test model
(test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py:93,121,195
— LlamaAttention/LlamaMLP/LlamaDecoderLayer built from dist.shard_tensor)
and the fused transformer ops it exercises
(python/paddle/incubate/nn/functional/fused_rms_norm.py, flash attention
paddle/phi/kernels/gpu/flash_attn_kernel.cu).

TPU design choices:
  * attention runs through ops.pallas.flash_attention.sdpa (Pallas blockwise
    kernel on TPU, flash-reference XLA fallback elsewhere); GQA native.
  * rotary embedding precomputed once per forward in fp32, applied in
    input dtype — keeps the MXU in bf16.
  * weights are plain nn.Linear ([in, out]); tensor parallelism is applied
    as GSPMD shardings via `llama_tp_shard_fn` (the reference's colwise /
    rowwise placements), NOT via distinct layer classes — the same model
    object runs 1-chip or N-D-mesh unchanged.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..framework.tensor import Tensor
from ..ops.pallas.flash_attention import _xla_sdpa, sdpa


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False
    dtype: str = "float32"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def llama3_8b() -> LlamaConfig:
    return LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=8192, rope_theta=500000.0, dtype="bfloat16")


def llama_tiny(**kw) -> LlamaConfig:
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=256)
    cfg.update(kw)
    return LlamaConfig(**cfg)


class LlamaRMSNorm(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.hidden_size = config.hidden_size
        self.weight = self.create_parameter(
            [config.hidden_size],
            default_initializer=nn.initializer.Constant(1.0))
        self.variance_epsilon = config.rms_norm_eps

    def forward(self, x):
        return F.rms_norm(x, self.weight, epsilon=self.variance_epsilon)


def _rope_tables(seq_len, head_dim, theta, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                       # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [S, D]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def apply_rotary_pos_emb(q, k, cos, sin):
    """q,k: [B, S, H, D]; cos,sin: [S, D] (fp32 tables, applied in dtype)."""
    cos = cos[None, :, None, :].astype(q.dtype)
    sin = sin[None, :, None, :].astype(q.dtype)
    return q * cos + _rotate_half(q) * sin, k * cos + _rotate_half(k) * sin


class LlamaAttention(nn.Layer):
    """GQA attention (reference test model LlamaAttention:93)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        kvh = config.num_key_value_heads
        self.num_heads = config.num_attention_heads
        self.num_key_value_heads = kvh
        self.head_dim = hd
        self.q_proj = nn.Linear(h, self.num_heads * hd, bias_attr=False)
        self.k_proj = nn.Linear(h, kvh * hd, bias_attr=False)
        self.v_proj = nn.Linear(h, kvh * hd, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * hd, h, bias_attr=False)

    def forward(self, hidden_states, attn_mask=None, cos=None, sin=None):
        b, s, _ = hidden_states.shape
        q = self.q_proj(hidden_states).reshape(
            [b, s, self.num_heads, self.head_dim])
        k = self.k_proj(hidden_states).reshape(
            [b, s, self.num_key_value_heads, self.head_dim])
        v = self.v_proj(hidden_states).reshape(
            [b, s, self.num_key_value_heads, self.head_dim])
        if cos is None:
            cos, sin = _rope_tables(s, self.head_dim, self.config.rope_theta)
            cos, sin = Tensor(cos), Tensor(sin)
        q, k = rope_op(q, k, cos, sin)
        # causal always: attn_mask (e.g. padding) composes with, never
        # replaces, the causal structure of the LM
        out = flash_attention(q, k, v, attn_mask, is_causal=True,
                              kernel=self.config.use_flash_attention)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    """SwiGLU FFN (reference test model LlamaMLP:121)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, i, bias_attr=False)
        self.up_proj = nn.Linear(h, i, bias_attr=False)
        self.down_proj = nn.Linear(i, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def forward(self, hidden_states, attn_mask=None, cos=None, sin=None):
        residual = hidden_states
        h = self.input_layernorm(hidden_states)
        h = self.self_attn(h, attn_mask=attn_mask, cos=cos, sin=sin)
        h = residual + h
        residual = h
        h = self.post_attention_layernorm(h)
        h = self.mlp(h)
        return residual + h


def _built(layer, config: LlamaConfig):
    """Cast a sublayer to the config dtype as soon as it is built: the
    float32 initialisers then never hold more than one sublayer, where
    casting the finished model held all of it twice over (11 GB before
    the first bf16 byte at Llama-3-8B widths x 8 layers)."""
    return layer.bfloat16() if config.dtype == "bfloat16" else layer


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _built(
            nn.Embedding(config.vocab_size, config.hidden_size), config)
        self.layers = nn.LayerList(
            [_built(LlamaDecoderLayer(config), config)
             for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config)

    def forward(self, input_ids, attn_mask=None):
        h = self.embed_tokens(input_ids)
        if self.config.dtype == "bfloat16":
            h = h.astype("bfloat16")
        s = input_ids.shape[1]
        cos, sin = _rope_tables(s, self.config.head_dim,
                                self.config.rope_theta)
        cos, sin = Tensor(cos), Tensor(sin)
        from ..distributed.fleet import recompute as _rc
        for layer in self.layers:
            if self.config.recompute and self.training:
                h = _rc.recompute(layer, h, attn_mask, cos, sin)
            else:
                h = layer(h, attn_mask=attn_mask, cos=cos, sin=sin)
        return self.norm(h)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = _built(
                nn.Linear(config.hidden_size, config.vocab_size,
                          bias_attr=False), config)

        if config.dtype == "bfloat16":
            self.bfloat16()

    def forward(self, input_ids, attn_mask=None):
        h = self.llama(input_ids, attn_mask=attn_mask)
        if self.lm_head is None:
            w = self.llama.embed_tokens.weight
            return h.matmul(w, transpose_y=True)
        return self.lm_head(h)

    def generate(self, input_ids, **kwargs):
        """KV-cache autoregressive decoding (models/generation.py)."""
        from .generation import generate as _generate
        return _generate(self, input_ids, **kwargs)


class LlamaPretrainingCriterion(nn.Layer):
    """Shifted next-token cross entropy in fp32 (reference test model's
    criterion; loss math must leave bf16)."""

    def forward(self, logits, labels):
        logits = logits.astype("float32")
        return F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]),
            labels.reshape([-1]), reduction="mean")


# ---------------------------------------------------------------- sharding
def llama_tp_shard_fn(mesh, tp_axis="tp", dp_axis=None):
    """shard_fn for dist.shard_layer implementing the reference's TP plan
    (semi_auto_parallel_llama_model.py: colwise q/k/v/gate/up Shard(1),
    rowwise o/down Shard(0), embedding Shard(1) on its hidden dim;
    everything else replicated).  Returns (name, layer, mesh) -> None."""
    from ..distributed.placement import Shard, Replicate
    from ..distributed.auto_parallel.api import shard_tensor

    col = ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj", "lm_head")
    row = ("o_proj", "down_proj")

    def placements_for(layer_name, pname, p):
        base = [Replicate() for _ in mesh.dim_names]
        if tp_axis not in mesh.dim_names:
            return base
        ax = mesh.dim_names.index(tp_axis)
        leaf = layer_name.rsplit(".", 1)[-1]
        if leaf in col and pname == "weight":
            base[ax] = Shard(1)
        elif leaf in row and pname == "weight":
            base[ax] = Shard(0)
        elif leaf == "embed_tokens" and pname == "weight":
            base[ax] = Shard(1)
        return base

    def fn(name, sub, m):
        for pname, p in list(sub._parameters.items()):
            if p is None:
                continue
            sharded = shard_tensor(p, m, placements_for(name, pname, p))
            p._data = sharded._data
    return fn


# --- fused ops (registered so autograd tape + AMP see them) ---------------
from ..ops.registry import op as _op


@_op(name="llama_rope")
def rope_op(q, k, cos, sin):
    return apply_rotary_pos_emb(q, k, cos, sin)


@_op(name="flash_attention")
def flash_attention(q, k, v, attn_mask=None, is_causal=False, kernel=True):
    """``kernel=False`` (``LlamaConfig.use_flash_attention``) takes the
    plain XLA formulation on every backend — the reference the Pallas
    kernels are compared against."""
    if not kernel:
        return _xla_sdpa(q, k, v, attn_mask=attn_mask, is_causal=is_causal)
    return sdpa(q, k, v, attn_mask=attn_mask, is_causal=is_causal)
