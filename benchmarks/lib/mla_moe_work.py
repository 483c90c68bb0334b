"""Operations and bytes a ``deepseek_v3`` configuration's algorithms
need, from shapes, traffic and the routing the window saw.  Nothing here
knows which kernel ran.  ``cfg`` is the configuration file (see
``mla_moe_state``); ``seen`` is what the driver saw of the window.
"""
from __future__ import annotations

from benchmarks.lib.mla_moe_state import local_experts, router_width


def layer_params(cfg: dict) -> dict:
    """Matrix parameters of the parts of one layer (norms apart)."""
    m = cfg["model"]
    h, nh = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * qk
                 + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
                 + m["kv_lora_rank"] * nh
                 * (m["qk_nope_head_dim"] + m["v_head_dim"])
                 + nh * m["v_head_dim"] * h)
    expert = 3 * h * m["moe_intermediate_size"]
    return {"attention": attention,
            "dense_mlp": 3 * h * m["intermediate_size"],
            "shared": expert * m["n_shared_experts"],
            "router": h * router_width(cfg), "expert": expert}


def expert_layers(cfg: dict) -> int:
    m = cfg["model"]
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def params(cfg: dict) -> dict:
    """Parameters this chip holds (norm vectors and the router's bias
    apart): the whole attention, router and shared expert of every
    layer, its share of the routed experts, its slice of the
    vocabulary."""
    m, p = cfg["model"], layer_params(cfg)
    dense = m["first_k_dense_replace"] * (p["attention"] + p["dense_mlp"])
    moe = expert_layers(cfg) * (p["attention"] + p["shared"] + p["router"]
                                + local_experts(cfg)[1] * p["expert"])
    vocab = 2 * m["vocab_size"] * m["hidden_size"]
    return {"dense_layers": dense, "expert_layers": moe,
            "embedding_and_head": vocab, "total": dense + moe + vocab}


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return params(cfg)["total"] * itemsize


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One latent row a layer: the compressed latent and the rotary key."""
    m = cfg["model"]
    return ((m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize
            * m["num_hidden_layers"])


def mla_decode_bytes(cfg: dict, seen: dict) -> float:
    """Bytes of latent rows decode had to read: a decode token at
    context c reads c rows of every layer, once for all heads."""
    return float(seen["decode_context_sum"]) * cache_bytes_per_token(cfg)


def moe_expert_bytes(cfg: dict, seen: dict, itemsize: int = 2) -> float:
    """Bytes of held experts' weights the window's decode routing
    touched: every (step, layer, held expert) with at least one row
    reads that expert's three matrices once."""
    return (float(seen["moe_experts_live"]) * layer_params(cfg)["expert"]
            * itemsize)


def serve_flops(cfg: dict, seen: dict) -> float:
    """Forward operations of the window's tokens on this chip: two a
    matrix parameter a token for what every token passes (attention's
    matrices, the dense MLP or the router and shared expert, the head's
    slice), two a parameter for each (token, expert) pair computed
    here (decode: counted by the program; prefill: the held share of
    its pairs), and attention as QK^T and PV over the published head
    sizes (the expanded form, whatever form ran)."""
    m, p = cfg["model"], layer_params(cfg)
    tokens = seen["decode_tokens"] + seen["prompt_tokens"]
    per_token = (m["num_hidden_layers"] * p["attention"]
                 + m["first_k_dense_replace"] * p["dense_mlp"]
                 + expert_layers(cfg) * (p["shared"] + p["router"])
                 + m["vocab_size"] * m["hidden_size"])
    pairs = seen["moe_local_pairs"] + (
        seen["prompt_tokens"] * m["num_experts_per_tok"]
        * expert_layers(cfg) * local_experts(cfg)[1] / router_width(cfg))
    context = seen["decode_context_sum"] + seen["prefill_context_sum"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = (2.0 * context * m["num_attention_heads"]
                 * (qk + m["v_head_dim"]) * m["num_hidden_layers"])
    return 2.0 * tokens * per_token + 2.0 * pairs * p["expert"] + attention


WORK = {"mla_decode_bytes": mla_decode_bytes,
        "moe_expert_bytes": moe_expert_bytes,
        "mla_moe_serve": serve_flops}
