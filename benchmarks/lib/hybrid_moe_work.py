"""Operations and bytes a ``nemotron_h`` configuration's algorithms
need, from shapes, traffic and the routing the window saw.  Nothing here
knows which kernel ran.  ``cfg`` is the configuration file (see
``hybrid_moe_state``); ``seen`` is what the driver saw of the window.
"""
from __future__ import annotations

from benchmarks.lib.hybrid_moe_state import (dims, local_experts,
                                             router_width)

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def block_params(cfg: dict) -> dict:
    """Matrix parameters of the parts a block is made of (norms,
    convolution, the per-head vectors and the router's bias apart)."""
    m, d = cfg["model"], dims(cfg)
    h = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return {"mamba": h * d["in_proj"] + d["d_inner"] * h,
            "attention": 2 * h * q + 2 * h * kv,
            "expert": 2 * h * m["moe_intermediate_size"],
            "shared": 2 * h * m["moe_shared_expert_intermediate_size"],
            "router": h * router_width(cfg)}


def params(cfg: dict) -> dict:
    """Matrix parameters this chip holds, at the published widths (the
    zeros that pad a held expert to whole lane tiles are no parameters:
    ``padding_bytes``): every Mamba and attention block, every expert
    block's router, shared expert and share of the routed experts, its
    slice of the vocabulary twice (embedding and head)."""
    m, d, p = cfg["model"], dims(cfg), block_params(cfg)
    held = local_experts(cfg)[1]
    blocks = (d["mamba_layers"] * p["mamba"]
              + d["attention_layers"] * p["attention"]
              + d["expert_layers"] * (p["router"] + p["shared"]
                                      + held * p["expert"]))
    vocab = 2 * m["vocab_size"] * m["hidden_size"]
    return {"blocks": blocks, "embedding_and_head": vocab,
            "total": blocks + vocab}


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return params(cfg)["total"] * itemsize


def padding_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What storing the held experts in whole lane tiles adds."""
    m, d = cfg["model"], dims(cfg)
    return (d["expert_layers"] * local_experts(cfg)[1] * 2
            * m["hidden_size"]
            * (d["expert_width"] - m["moe_intermediate_size"]) * itemsize)


def state_elements_per_slot(cfg: dict) -> int:
    """One slot's recurrent state of one Mamba block: H x P x N values
    (the convolution's tail apart)."""
    m = cfg["model"]
    return m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]


def state_bytes_per_slot(cfg: dict) -> int:
    """Those values in the dtype the configuration states for the state
    (``assumed.ssm_state_dtype``)."""
    return (state_elements_per_slot(cfg)
            * ITEMSIZE[cfg["assumed"]["ssm_state_dtype"]])


def recurrent_state_bytes(cfg: dict, slots: int) -> int:
    """What ``slots`` slots keep beside their pages: every Mamba block's
    state in ``assumed.ssm_state_dtype`` and the convolution's last
    ``conv_kernel - 1`` inputs in the served dtype."""
    m, d, a = cfg["model"], dims(cfg), cfg["assumed"]
    tail = (m["conv_kernel"] - 1) * d["conv_dim"] * ITEMSIZE[
        a["torch_dtype"]]
    return slots * d["mamba_layers"] * (state_bytes_per_slot(cfg) + tail)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of the attention blocks alone."""
    m, d = cfg["model"], dims(cfg)
    return (2 * m["num_key_value_heads"] * m["head_dim"] * itemsize
            * d["attention_layers"])


def ssm_update_bytes(cfg: dict, seen: dict) -> float:
    """Bytes of recurrent state decode had to move: a decode token reads
    its slot's state of every Mamba block once and writes it once,
    whatever implements the update."""
    return (float(seen["decode_tokens"]) * dims(cfg)["mamba_layers"]
            * 2 * state_bytes_per_slot(cfg))


def paged_decode_bytes(cfg: dict, seen: dict) -> float:
    """K/V bytes the window's decode tokens had to read: a token reads
    the K and V of every token of its context in the attention blocks."""
    return float(seen["decode_context_sum"]) * kv_bytes_per_token(cfg)


def moe_expert_bytes(cfg: dict, seen: dict, itemsize: int = 2) -> float:
    """Bytes of held experts' weights the window's decode routing
    touched: every (step, block, held expert) with at least one row
    reads that expert's two matrices once."""
    return (float(seen["moe_experts_live"]) * block_params(cfg)["expert"]
            * itemsize)


def serve_flops(cfg: dict, seen: dict) -> float:
    """Forward operations of the window's tokens on this chip: two a
    matrix parameter a token for what every token passes (the Mamba and
    attention blocks' matrices, the expert blocks' router and shared
    expert, the head's slice), two a parameter for each (token, expert)
    pair computed here (decode: counted by the program; prefill: the
    held share of its pairs), the recurrence as five an element of
    state a token a Mamba block (decay, input, sum; the read-out's
    product and sum), in whatever form it ran, and attention as QK^T and
    PV over the context in the attention blocks."""
    m, d, p = cfg["model"], dims(cfg), block_params(cfg)
    tokens = seen["decode_tokens"] + seen["prompt_tokens"]
    per_token = (d["mamba_layers"] * p["mamba"]
                 + d["attention_layers"] * p["attention"]
                 + d["expert_layers"] * (p["shared"] + p["router"])
                 + m["vocab_size"] * m["hidden_size"])
    pairs = seen["moe_local_pairs"] + (
        seen["prompt_tokens"] * m["num_experts_per_tok"]
        * d["expert_layers"] * local_experts(cfg)[1] / router_width(cfg))
    recurrence = 5.0 * state_elements_per_slot(cfg) * d["mamba_layers"]
    context = seen["decode_context_sum"] + seen["prefill_context_sum"]
    attention = (4.0 * context * m["num_attention_heads"] * m["head_dim"]
                 * d["attention_layers"])
    return (tokens * (2.0 * per_token + recurrence)
            + 2.0 * pairs * p["expert"] + attention)


WORK = {"ssm_update_bytes": ssm_update_bytes,
        "paged_decode_bytes": paged_decode_bytes,
        "moe_expert_bytes": moe_expert_bytes,
        "hybrid_moe_serve": serve_flops}
