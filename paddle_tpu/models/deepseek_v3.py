"""The ``deepseek_v3`` decoder family for the serving path: multi-head
latent attention (MLA) over a latent cache, a routed-expert layer that
holds a share of the experts, YaRN rotary tables.

Reference: DeepSeek-V3 (arXiv:2412.19437) as the published
``modeling_deepseek_v3`` computes it; MLA is DeepSeek-V2's
(arXiv:2405.04434, section 2.1), the bias-corrected sigmoid router is
section 2.1.2 of the V3 report, YaRN is arXiv:2309.00071.

What the serving runner needs of a family is here: the model
description (:class:`DeepseekV3Config`: sizes, the layer pattern, the
experts this chip holds, the cache's row), the names and shapes of the
weights (``weight_shapes``), the rotary tables, and one set of layer
bodies for one chip: ``decode_layer`` (absorbed attention over the paged
latent pool) and ``prefill_layer`` (expanded attention: a whole prompt
through the flash kernel, or a suffix against resident latent rows).
Weights are ``[in, out]`` under the published parameter names; the
experts a chip holds are stacked: ``mlp.experts.gate_proj.weight``
``[held, hidden, moe_intermediate]``.

The cache holds ONE row a token a layer, ``[c | rope(k_r)]``: the
normalized compressed latent (``kv_lora_rank`` values) and the rotary
key all heads share (``qk_rope_head_dim``).  Prefill expands it
(``[k_nope | v] = c . W_kv_b``); decode never does: ``W_kv_b``'s key half
is absorbed into the query and its value half into the output.

An expert layer is told which experts it holds (``local_experts =
(first, count)``): it routes over all ``n_routed_experts`` and computes
the terms of the experts it holds; the other terms belong to other
chips and are left out (on one chip the layer runs without its
exchange).  The shared expert is whole on every chip.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .llama import _rotate_half
from .llama_hybrid import _rms

__all__ = ["DeepseekV3Config", "weight_shapes", "rope_tables",
           "softmax_scale", "route", "routed_experts", "expert_ffn",
           "decode_layer", "prefill_layer", "layer_weights"]

HI = jax.lax.Precision.HIGHEST
# rows of one tile of the sorted expert buffer: an expert sees a few
# rows a decode step and some tens of a prefill
DECODE_TILE, PREFILL_TILE = 16, 128
MOE_COUNTERS = ("moe_routed_pairs", "moe_local_pairs", "moe_experts_live")
# the live row tiles of the sorted buffer, counted where a description
# names it among its ``counters``: a touched expert's weights are fetched
# once a row tile, so this over ``moe_experts_live`` is how many times
MOE_ROW_TILES = "moe_row_tiles_live"


def held_range(local_experts, n_routed: int) -> tuple:
    """``local_experts`` as (first, count) of the ``n_routed`` routed
    experts; None = all of them."""
    first, count = (0, n_routed) if local_experts is None else (
        int(x) for x in local_experts)
    if not (0 <= first and count >= 1 and first + count <= n_routed):
        raise ValueError(
            f"local_experts={(first, count)} is no range of the "
            f"{n_routed} routed experts")
    return first, count


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # {"factor", "original_max_position_embeddings", "beta_fast",
    #  "beta_slow", "mscale", "mscale_all_dim"} or None (plain rotary)
    rope_scaling: dict | None = None
    # (first, count): the routed experts this chip holds; None = all
    local_experts: tuple | None = None
    dtype: str = "bfloat16"
    family: str = field(default="deepseek_v3", init=False)

    def __post_init__(self):
        self.local_experts = held_range(self.local_experts,
                                        self.n_routed_experts)
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """Values one token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_expert_layer(self, i: int) -> bool:
        return i >= self.first_k_dense_replace


# ------------------------------------------------------------------ weights
def weight_shapes(cfg: DeepseekV3Config) -> dict:
    """{name: shape} of every leaf the serving state holds."""
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    held = cfg.local_experts[1]
    fm = cfg.moe_intermediate_size
    out = {"model.embed_tokens.weight": (cfg.vocab_size, h),
           "model.norm.weight": (h,),
           "lm_head.weight": (h, cfg.vocab_size)}
    for n in range(cfg.num_hidden_layers):
        p = f"model.layers.{n}."
        a = p + "self_attn."
        out.update({
            p + "input_layernorm.weight": (h,),
            p + "post_attention_layernorm.weight": (h,),
            a + "q_a_proj.weight": (h, cfg.q_lora_rank),
            a + "q_a_layernorm.weight": (cfg.q_lora_rank,),
            a + "q_b_proj.weight": (cfg.q_lora_rank, nh * cfg.qk_head_dim),
            a + "kv_a_proj_with_mqa.weight": (h, cfg.cache_row),
            a + "kv_a_layernorm.weight": (cfg.kv_lora_rank,),
            a + "kv_b_proj.weight": (
                cfg.kv_lora_rank,
                nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            a + "o_proj.weight": (nh * cfg.v_head_dim, h)})
        m = p + "mlp."
        if not cfg.is_expert_layer(n):
            i = cfg.intermediate_size
            out.update({m + "gate_proj.weight": (h, i),
                        m + "up_proj.weight": (h, i),
                        m + "down_proj.weight": (i, h)})
            continue
        fs = fm * cfg.n_shared_experts
        out.update({
            m + "gate.weight": (h, cfg.n_routed_experts),
            m + "gate.e_score_correction_bias": (cfg.n_routed_experts,),
            m + "shared_experts.gate_proj.weight": (h, fs),
            m + "shared_experts.up_proj.weight": (h, fs),
            m + "shared_experts.down_proj.weight": (fs, h),
            m + "experts.gate_proj.weight": (held, h, fm),
            m + "experts.up_proj.weight": (held, h, fm),
            m + "experts.down_proj.weight": (held, fm, h)})
    return out


def layer_weights(state: dict, cfg: DeepseekV3Config, i: int) -> dict:
    """Layer ``i``'s leaves under short names."""
    p = f"model.layers.{i}."
    a = p + "self_attn."
    w = {"ln1": state[p + "input_layernorm.weight"],
         "ln2": state[p + "post_attention_layernorm.weight"],
         "q_a": state[a + "q_a_proj.weight"],
         "q_ln": state[a + "q_a_layernorm.weight"],
         "q_b": state[a + "q_b_proj.weight"],
         "kv_a": state[a + "kv_a_proj_with_mqa.weight"],
         "kv_ln": state[a + "kv_a_layernorm.weight"],
         "kv_b": state[a + "kv_b_proj.weight"],
         "o": state[a + "o_proj.weight"]}
    m = p + "mlp."
    if not cfg.is_expert_layer(i):
        w.update(gate=state[m + "gate_proj.weight"],
                 up=state[m + "up_proj.weight"],
                 down=state[m + "down_proj.weight"])
        return w
    w.update(router=state[m + "gate.weight"],
             router_bias=state[m + "gate.e_score_correction_bias"],
             gate=state[m + "shared_experts.gate_proj.weight"],
             up=state[m + "shared_experts.up_proj.weight"],
             down=state[m + "shared_experts.down_proj.weight"],
             e_gate=state[m + "experts.gate_proj.weight"],
             e_up=state[m + "experts.up_proj.weight"],
             e_down=state[m + "experts.down_proj.weight"])
    return w


# ------------------------------------------------------------------- rotary
def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_inv_freq(cfg: DeepseekV3Config):
    """The rotary frequencies [rope/2]: plain, or YaRN's blend of the
    plain ones and the same over ``factor``, by a linear ramp between
    the correction dims of ``beta_fast`` and ``beta_slow``."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    plain = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    rs = cfg.rope_scaling
    if not rs:
        return plain
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rs.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rs.get("beta_slow", 1)))),
               d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_tables(cfg: DeepseekV3Config, length: int):
    """(cos, sin) [length, rope] float32, halves repeated (the
    rotate-half layout), times mscale / mscale_all_dim."""
    inv = yarn_inv_freq(cfg)
    ang = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv)
    emb = jnp.concatenate([ang, ang], axis=-1)
    rs = cfg.rope_scaling
    scale = 1.0
    if rs and rs.get("mscale") and rs.get("mscale_all_dim"):
        scale = (_yarn_mscale(float(rs["factor"]), float(rs["mscale"]))
                 / _yarn_mscale(float(rs["factor"]),
                                float(rs["mscale_all_dim"])))
    elif rs:
        scale = _yarn_mscale(float(rs["factor"]), 1.0)
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def softmax_scale(cfg: DeepseekV3Config) -> float:
    """qk_head_dim^-0.5, times YaRN's mscale(all dims) squared."""
    s = cfg.qk_head_dim ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        s *= m * m
    return s


def _rope(x, cos, sin):
    """The published interleaved pairs: de-interleave, then rotate
    half.  x [..., rope] float32; cos/sin broadcastable."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return x * cos + _rotate_half(x) * sin


# ------------------------------------------------------------------- router
def route(cfg, x, w_gate, bias):
    """x [T, H] -> (experts [T, K] int32, weights [T, K] float32), over
    all ``n_routed_experts``, in float32, by the description's
    ``scoring_func``: "sigmoid" (where it does not say; DeepSeek-V3's
    bias-corrected sigmoid in groups: the bias moves the choice, never
    the weight) or "softmax_topk" (Granite's: the top ``num_experts_per_tok``
    logits, a softmax over those alone, no bias, no groups, no scale)."""
    logits = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=HI)
    if getattr(cfg, "scoring_func", "sigmoid") == "softmax_topk":
        top, idx = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        gates = jax.nn.softmax(top, axis=-1).astype(x.dtype)
        return idx.astype(jnp.int32), gates.astype(jnp.float32)
    t = x.shape[0]
    e, g = cfg.n_routed_experts, cfg.n_group
    sc = jax.nn.sigmoid(logits)
    ch = sc + bias.astype(jnp.float32)[None, :]
    grouped = ch.reshape(t, g, e // g)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)     # [T, g]
    kept = jax.lax.top_k(group_score, cfg.topk_group)[1]        # [T, kg]
    group_ok = jnp.any(kept[:, :, None] == jnp.arange(g)[None, None, :],
                       axis=1)                                   # [T, g]
    among = jnp.where(jnp.repeat(group_ok, e // g, axis=1), ch, -jnp.inf)
    idx = jax.lax.top_k(among, cfg.num_experts_per_tok)[1].astype(jnp.int32)
    w = jnp.take_along_axis(sc, idx, axis=1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx, w * jnp.float32(cfg.routed_scaling_factor)


def _sorted_rows(cfg: DeepseekV3Config, idx, valid, tile: int):
    """The tile-aligned order of the (token, choice) pairs this chip
    computes: pairs sorted by held expert, each expert's rows padded up
    to ``tile`` so that a row tile belongs to one expert.

    Returns (row_pair [R]: the pair a buffer row holds, or T*K for a
    padding row; pair_row [T*K]: the buffer row of a pair, or R where
    the pair is not computed here; emap [R/tile]; n_live: the live row
    tiles; sizes [held]: rows per held expert)."""
    first, held = cfg.local_experts
    t, k = idx.shape
    n = t * k
    rows = -(-n // tile) * tile + held * tile
    local = (idx >= first) & (idx < first + held) & valid[:, None]
    flat = jnp.where(local, idx - first, held).reshape(n).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sorted_e = flat[order]
    sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
    padded = -(-sizes // tile) * tile
    start = jnp.cumsum(sizes) - sizes
    pstart = jnp.cumsum(padded) - padded
    e_c = jnp.minimum(sorted_e, held - 1)
    dest = jnp.where(sorted_e < held,
                     pstart[e_c] + jnp.arange(n, dtype=jnp.int32)
                     - start[e_c], rows).astype(jnp.int32)
    row_pair = jnp.full((rows,), n, jnp.int32).at[dest].set(
        order, mode="drop")
    pair_row = jnp.zeros((n,), jnp.int32).at[order].set(dest)
    tiles_end = jnp.cumsum(padded) // tile                      # [held]
    # tile t belongs to the first expert whose tiles end past it
    tiles = jnp.arange(rows // tile, dtype=jnp.int32)
    emap = jnp.minimum(
        jnp.sum((tiles_end[None, :] <= tiles[:, None]).astype(jnp.int32),
                axis=1), held - 1).astype(jnp.int32)
    return row_pair, pair_row, emap, tiles_end[-1].astype(jnp.int32), sizes


def _gated(cfg) -> bool:
    """Whether the description's experts are the gated three-matrix
    ``down(silu(gate x) . up x)`` (``mlp_hidden_act`` "silu", and where
    the description does not say) or the two-matrix ``down(relu(up
    x)^2)`` ("relu2")."""
    act = getattr(cfg, "mlp_hidden_act", "silu")
    if act not in ("silu", "relu2"):
        raise ValueError(f"mlp_hidden_act={act!r} is not implemented "
                         "(only 'silu' and 'relu2')")
    return act == "silu"


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def routed_experts(cfg, w: dict, x, valid, tile: int):
    """Σ over the chosen experts this chip holds of ``w_e . expert_e(x)``
    for x [T, H], an expert being ``down_e(silu(gate_e x) . up_e x)`` or
    ``down_e(relu(up_e x)^2)`` as the description says (``_gated``);
    gate and up are two stacks, or one published stack ``e_gateup``
    [held, H, 2F] (gate | up) computed by one grouped product 2F wide.
    Tokens where ``valid`` is false choose nothing.  Dropless: every
    pair of a held expert is computed.  Returns (y [T, H] float32,
    counts: ``MOE_COUNTERS``, and ``MOE_ROW_TILES`` after them where the
    description's ``counters`` name it)."""
    from ..ops.pallas.grouped_ffn import select_grouped_matmul
    t = x.shape[0]
    k = cfg.num_experts_per_tok
    with jax.named_scope("moe.route"):
        idx, wts = route(cfg, x, w["router"], w.get("router_bias"))
        row_pair, pair_row, emap, n_live, sizes = _sorted_rows(
            cfg, idx, valid, tile)
        x_buf = jnp.take(x, row_pair // k, axis=0, mode="fill",
                         fill_value=0)
    with jax.named_scope("moe.experts"):
        gmm = select_grouped_matmul()
        if "e_gateup" in w:
            gu = gmm(x_buf, w["e_gateup"], emap, n_live, tile_m=tile)
            f = gu.shape[-1] // 2
            act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
                   * gu[:, f:].astype(jnp.float32)).astype(x.dtype)
        elif _gated(cfg):
            g = gmm(x_buf, w["e_gate"], emap, n_live, tile_m=tile)
            u = gmm(x_buf, w["e_up"], emap, n_live, tile_m=tile)
            act = (jax.nn.silu(g.astype(jnp.float32))
                   * u.astype(jnp.float32)).astype(x.dtype)
        else:
            u = gmm(x_buf, w["e_up"], emap, n_live, tile_m=tile)
            act = _relu2(u.astype(jnp.float32)).astype(x.dtype)
        y_buf = gmm(act, w["e_down"], emap, n_live, tile_m=tile)
        # rows of tiles that hold no pair were never written: a pair that
        # is not computed here points past the buffer and reads as zero
        y = jnp.take(y_buf, pair_row, axis=0, mode="fill", fill_value=0)
        y = jnp.sum(y.reshape(t, k, -1).astype(jnp.float32)
                    * wts[:, :, None], axis=1)
    counts = [jnp.sum(valid.astype(jnp.int32)) * k, jnp.sum(sizes),
              jnp.sum((sizes > 0).astype(jnp.int32))]
    if MOE_ROW_TILES in getattr(cfg, "counters", ()):
        counts.append(n_live)
    return y, jnp.stack(counts).astype(jnp.int32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def expert_ffn(cfg, w: dict, x, valid, tile: int):
    """The expert layer's feed-forward part on x [T, H] (normed): the
    held routed experts' terms and the shared expert, whole.  Returns
    (y [T, H], MoE counts)."""
    routed, counts = routed_experts(cfg, w, x, valid, tile)
    with jax.named_scope("moe.shared"):
        if _gated(cfg):
            shared = _swiglu(x, w["gate"], w["up"], w["down"])
        else:
            shared = _relu2(x @ w["up"]) @ w["down"]
        return (shared.astype(jnp.float32) + routed).astype(x.dtype), counts


def ffn(cfg: DeepseekV3Config, w: dict, li: int, x, valid, tile: int):
    """x [T, H] (normed) -> (ffn(x) [T, H], MoE counts [3] or None)."""
    if not cfg.is_expert_layer(li):
        with jax.named_scope("mlp"):
            return _swiglu(x, w["gate"], w["up"], w["down"]), None
    return expert_ffn(cfg, w, x, valid, tile)


# ---------------------------------------------------------------- attention
def _queries(cfg, w, h, cos, sin):
    """h [T, H] -> (q_nope [T, nh, nope] f32, q_rope [T, nh, rope] f32,
    rotated)."""
    nh = cfg.num_attention_heads
    cq = _rms(h @ w["q_a"], w["q_ln"], cfg.rms_norm_eps)
    q = jnp.dot(cq, w["q_b"], preferred_element_type=jnp.float32)
    q = q.reshape(-1, nh, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = _rope(q[..., cfg.qk_nope_head_dim:], cos[:, None, :],
                   sin[:, None, :])
    return q_nope, q_rope


def _latent_rows(cfg, w, h, cos, sin, dtype):
    """h [T, H] -> the cache's rows [T, rank + rope]: the normalized
    latent and the rotated shared key."""
    ckv = jnp.dot(h, w["kv_a"], preferred_element_type=jnp.float32)
    c = _rms(ckv[:, :cfg.kv_lora_rank], w["kv_ln"].astype(jnp.float32),
             cfg.rms_norm_eps)
    k_r = _rope(ckv[:, cfg.kv_lora_rank:], cos, sin)
    return jnp.concatenate([c, k_r], axis=-1).astype(dtype)


def _kv_b(cfg, w):
    """W_kv_b as (W_UK [rank, nh, nope], W_UV [rank, nh, v])."""
    nh = cfg.num_attention_heads
    kv_b = w["kv_b"].reshape(cfg.kv_lora_rank, nh,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)
    return kv_b[..., :cfg.qk_nope_head_dim], kv_b[..., cfg.qk_nope_head_dim:]


def _expanded_attention(cfg, w, q_nope, q_rope, rows, mask, causal):
    """Attention of queries [S, nh, .] over cached ``rows`` [T, width]
    in the expanded form: keys and values per head from the latent.
    ``mask``: [1, 1, 1, T] key padding (with ``causal``) or [1, 1, S, T]
    bool.  Returns [S, nh * v]."""
    from ..ops.pallas.flash_attention import sdpa
    nh = cfg.num_attention_heads
    dt = rows.dtype
    c = rows[:, :cfg.kv_lora_rank]
    k_r = rows[:, cfg.kv_lora_rank:cfg.cache_row]
    w_uk, w_uv = _kv_b(cfg, w)
    k_nope = jnp.einsum("tc,chd->thd", c, w_uk)
    v = jnp.einsum("tc,chd->thd", c, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None, :],
                                  (k_r.shape[0], nh, k_r.shape[1]))], -1)
    # sdpa scales by head_dim^-0.5 itself; YaRN's mscale^2 rides on q
    extra = softmax_scale(cfg) * math.sqrt(cfg.qk_head_dim)
    q = (jnp.concatenate([q_nope, q_rope], -1) * extra).astype(dt)
    if cfg.v_head_dim != cfg.qk_head_dim:
        # one head dim through the kernel: pad v, cut the output
        v = jnp.pad(v, ((0, 0), (0, 0),
                        (0, cfg.qk_head_dim - cfg.v_head_dim)))
    out = sdpa(q[None], k[None], v[None], attn_mask=mask,
               is_causal=causal)[0]
    return out[..., :cfg.v_head_dim].reshape(q.shape[0],
                                             nh * cfg.v_head_dim)


def prefill_layer(cfg, w, li, x, cos, sin, valid, pre_rows=None,
                  mask=None):
    """One layer over a (right-padded) run of tokens: x [1, S, H],
    ``valid`` [S] the real positions.  A whole prompt attends itself
    causally (the flash kernel); a suffix whose prefix is resident is
    given ``pre_rows`` [Tpre, width], the table's rows of this layer,
    and ``mask`` [1, 1, S, Tpre + S].  Returns (x, rows [S, row], MoE
    counts or None)."""
    with jax.named_scope("attn.mla.q"):
        h = _rms(x, w["ln1"], cfg.rms_norm_eps)[0]
        q_nope, q_rope = _queries(cfg, w, h, cos, sin)
    with jax.named_scope("attn.mla.kv"):
        rows = _latent_rows(cfg, w, h, cos, sin, x.dtype)
    with jax.named_scope("attn.prefill"):
        if pre_rows is None:
            attn = _expanded_attention(cfg, w, q_nope, q_rope, rows,
                                       valid[None, None, None, :], True)
        else:
            both = jnp.concatenate([pre_rows[:, :cfg.cache_row], rows], 0)
            attn = _expanded_attention(cfg, w, q_nope, q_rope, both, mask,
                                       False)
    with jax.named_scope("attn.out"):
        x = x + (attn @ w["o"])[None]
    h = _rms(x, w["ln2"], cfg.rms_norm_eps)[0]
    y, counts = ffn(cfg, w, li, h, valid, PREFILL_TILE)
    return x + y[None], rows, counts


def decode_layer(cfg, w, li, x, pool, table, cos1, sin1, pos, active):
    """One token a slot: x [B, H]; pool [L, P, page, width] (every
    layer's; this layer's new rows are scattered into it in place);
    ``pos`` [B] the current token's position.  Returns (x, pool, MoE
    counts or None)."""
    from ..ops.pallas.mla_paged_attention import (
        select_mla_paged_attention, select_write_rows)
    ps = pool.shape[2]
    dt = x.dtype
    with jax.named_scope("attn.mla.q"):
        h = _rms(x[:, None], w["ln1"], cfg.rms_norm_eps)[:, 0]
        q_nope, q_rope = _queries(cfg, w, h, cos1, sin1)
        w_uk, w_uv = _kv_b(cfg, w)
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope.astype(dt), w_uk)
    with jax.named_scope("attn.mla.kv"):
        rows = _latent_rows(cfg, w, h, cos1, sin1, pool.dtype)
    with jax.named_scope("kv.write"):
        page = jnp.take_along_axis(table, (pos // ps)[:, None], axis=1)[:, 0]
        pool = select_write_rows()(pool, li, page, pos % ps, rows)
    with jax.named_scope("attn.decode"):
        o_lat = select_mla_paged_attention()(
            q_lat, q_rope.astype(dt), pool, li, table, pos + 1,
            sm_scale=softmax_scale(cfg))
    with jax.named_scope("attn.out"):
        o = jnp.einsum("bhc,chd->bhd", o_lat, w_uv)
        x = x + o.reshape(o.shape[0], -1) @ w["o"]
    h = _rms(x[:, None], w["ln2"], cfg.rms_norm_eps)[:, 0]
    y, counts = ffn(cfg, w, li, h, active.astype(bool), DECODE_TILE)
    return x + y, pool, counts
