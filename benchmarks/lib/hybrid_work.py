"""Operations and bytes a ``granitemoehybrid`` configuration's
algorithms need, from shapes and traffic.  Nothing here knows which
kernel ran.  ``cfg`` is the configuration file (see ``hybrid_state``);
``seen`` is what the driver saw of the window.
"""
from __future__ import annotations

from benchmarks.lib.hybrid_state import dims


def layer_params(cfg: dict) -> dict:
    """Matrix parameters of the parts of a layer (norms, convolution and
    the per-head vectors apart)."""
    m, d = cfg["model"], dims(cfg)
    h = m["hidden_size"]
    q = m["num_attention_heads"] * d["head_dim"]
    kv = m["num_key_value_heads"] * d["head_dim"]
    return {"mamba": h * d["in_proj"] + d["d_inner"] * h,
            "attention": 2 * h * q + 2 * h * kv,
            "mlp": 3 * h * m["shared_intermediate_size"]}


def params(cfg: dict) -> dict:
    """Matrix parameters this chip holds: every layer and the one
    embedding matrix, which is the head too."""
    m, d, p = cfg["model"], dims(cfg), layer_params(cfg)
    layers = (d["mamba_layers"] * (p["mamba"] + p["mlp"])
              + d["attention_layers"] * (p["attention"] + p["mlp"]))
    embed = m["vocab_size"] * m["hidden_size"]
    return {"layers": layers, "embedding": embed, "total": layers + embed}


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    return params(cfg)["total"] * itemsize


ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def state_elements_per_slot(cfg: dict) -> int:
    """One slot's recurrent state of one Mamba layer: H x P x N values
    (the convolution's tail apart)."""
    m = cfg["model"]
    return m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]


def state_bytes_per_slot(cfg: dict) -> int:
    """Those values in the dtype the configuration states for the state
    (``assumed.ssm_state_dtype``)."""
    return (state_elements_per_slot(cfg)
            * ITEMSIZE[cfg["assumed"]["ssm_state_dtype"]])


def recurrent_state_bytes(cfg: dict, slots: int) -> int:
    """What ``slots`` slots keep beside their pages: every Mamba layer's
    state in ``assumed.ssm_state_dtype`` and the convolution's last
    ``d_conv - 1`` inputs in the served dtype."""
    m, d, a = cfg["model"], dims(cfg), cfg["assumed"]
    tail = (m["mamba_d_conv"] - 1) * d["conv_dim"] * ITEMSIZE[
        a["torch_dtype"]]
    return slots * d["mamba_layers"] * (state_bytes_per_slot(cfg) + tail)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of the attention layers alone."""
    m, d = cfg["model"], dims(cfg)
    return (2 * m["num_key_value_heads"] * d["head_dim"] * itemsize
            * d["attention_layers"])


def ssm_update_bytes(cfg: dict, seen: dict) -> float:
    """Bytes of recurrent state decode had to move: a decode token reads
    its slot's state of every Mamba layer once and writes it once,
    whatever implements the update."""
    return (float(seen["decode_tokens"]) * dims(cfg)["mamba_layers"]
            * 2 * state_bytes_per_slot(cfg))


def paged_decode_bytes(cfg: dict, seen: dict) -> float:
    """K/V bytes the window's decode tokens had to read: a token reads
    the K and V of every token of its context in the attention layers
    (``work.paged_decode_bytes`` for this family's four layers)."""
    return float(seen["decode_context_sum"]) * kv_bytes_per_token(cfg)


def serve_flops(cfg: dict, seen: dict) -> float:
    """Forward operations of the window's prompt and decode tokens: two
    a matrix parameter a token (the head's product counted, the
    embedding's lookup being that same matrix); the recurrence as five
    an element of state a token a Mamba layer (decay, input, sum; the
    read-out's product and sum), in whatever form it ran; attention as
    QK^T and PV over the context in the attention layers."""
    m, d = cfg["model"], dims(cfg)
    tokens = seen["decode_tokens"] + seen["prompt_tokens"]
    recurrence = 5.0 * state_elements_per_slot(cfg) * d["mamba_layers"]
    context = seen["decode_context_sum"] + seen["prefill_context_sum"]
    attention = (4.0 * context * m["num_attention_heads"] * d["head_dim"]
                 * d["attention_layers"])
    return tokens * (2.0 * params(cfg)["total"] + recurrence) + attention


WORK = {"ssm_update_bytes": ssm_update_bytes,
        "paged_decode_bytes": paged_decode_bytes,
        "hybrid_serve": serve_flops}
