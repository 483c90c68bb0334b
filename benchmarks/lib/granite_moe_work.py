"""Operations and bytes a ``granitemoehybrid`` configuration with routed
experts needs, from shapes, traffic and the routing the window saw.
Nothing here knows which kernel ran.  ``cfg`` is the configuration file
(see ``granite_moe_state``); ``seen`` is what the driver saw of the
window.
"""
from __future__ import annotations

from benchmarks.lib import hybrid_work
from benchmarks.lib.granite_moe_state import (dims, local_experts,
                                              router_width, served)

ITEMSIZE = hybrid_work.ITEMSIZE


def layer_params(cfg: dict) -> dict:
    """Matrix parameters of the parts of a layer (norms, convolution and
    the per-head vectors apart): the mixers and the shared MLP as
    ``hybrid_work`` counts them, one routed expert (gate, up, down) and
    the router."""
    m = cfg["model"]
    p = hybrid_work.layer_params(served(cfg))
    return {"mamba": p["mamba"], "attention": p["attention"],
            "shared": p["mlp"],
            "expert": 3 * m["hidden_size"] * m["intermediate_size"],
            "router": m["hidden_size"] * router_width(cfg)}


def params(cfg: dict) -> dict:
    """Matrix parameters this chip holds: every layer's mixer, router,
    shared MLP and share of the routed experts, and its slice of the one
    embedding matrix, which is the head too."""
    m, d, p = cfg["model"], dims(cfg), layer_params(cfg)
    held = local_experts(cfg)[1]
    layers = (d["mamba_layers"] * p["mamba"]
              + d["attention_layers"] * p["attention"]
              + d["layers"] * (p["router"] + p["shared"]
                               + held * p["expert"]))
    embed = m["vocab_size"] * m["hidden_size"]
    return {"layers": layers, "embedding": embed, "total": layers + embed}


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return params(cfg)["total"] * itemsize


def state_bytes_per_slot(cfg: dict) -> int:
    """One slot's recurrent state of one Mamba layer, H x P x N values in
    ``assumed.ssm_state_dtype``."""
    return hybrid_work.state_bytes_per_slot(cfg)


def recurrent_state_bytes(cfg: dict, slots: int) -> int:
    return hybrid_work.recurrent_state_bytes(served(cfg), slots)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return hybrid_work.kv_bytes_per_token(served(cfg), itemsize)


def ssm_update_bytes(cfg: dict, seen: dict) -> float:
    """Bytes of recurrent state decode had to move: a decode token reads
    its slot's state of every Mamba layer once and writes it once,
    whatever implements the update."""
    return hybrid_work.ssm_update_bytes(served(cfg), seen)


def paged_decode_bytes(cfg: dict, seen: dict) -> float:
    """K/V bytes the window's decode tokens had to read: K and V of
    every token of a decode token's context in the attention layers."""
    return hybrid_work.paged_decode_bytes(served(cfg), seen)


def moe_expert_bytes(cfg: dict, seen: dict, itemsize: int = 2) -> float:
    """Bytes of held experts' weights the window's decode routing
    touched: every (step, layer, held expert) with at least one row
    reads that expert's three matrices once."""
    return (float(seen["moe_experts_live"]) * layer_params(cfg)["expert"]
            * itemsize)


def serve_flops(cfg: dict, seen: dict) -> float:
    """Forward operations of the window's tokens on this chip: two a
    matrix parameter a token for what every token passes (the mixers,
    every layer's router and shared MLP, the head's slice), two a
    parameter for each (token, expert) pair computed here (decode:
    counted by the program; prefill: the held share of its pairs), the
    recurrence as five an element of state a token a Mamba layer, and
    attention as QK^T and PV over the context in the attention layers."""
    m, d, p = cfg["model"], dims(cfg), layer_params(cfg)
    tokens = seen["decode_tokens"] + seen["prompt_tokens"]
    per_token = (d["mamba_layers"] * p["mamba"]
                 + d["attention_layers"] * p["attention"]
                 + d["layers"] * (p["shared"] + p["router"])
                 + m["vocab_size"] * m["hidden_size"])
    pairs = seen["moe_local_pairs"] + (
        seen["prompt_tokens"] * m["num_experts_per_tok"] * d["layers"]
        * local_experts(cfg)[1] / router_width(cfg))
    recurrence = (5.0 * hybrid_work.state_elements_per_slot(cfg)
                  * d["mamba_layers"])
    context = seen["decode_context_sum"] + seen["prefill_context_sum"]
    attention = (4.0 * context * m["num_attention_heads"] * d["head_dim"]
                 * d["attention_layers"])
    return (tokens * (2.0 * per_token + recurrence)
            + 2.0 * pairs * p["expert"] + attention)


WORK = {"ssm_update_bytes": ssm_update_bytes,
        "paged_decode_bytes": paged_decode_bytes,
        "moe_expert_bytes": moe_expert_bytes,
        "granite_moe_serve": serve_flops}
