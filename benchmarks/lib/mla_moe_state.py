"""Weight shapes of a ``deepseek_v3`` configuration, for
``state.seeded``: {key: (shape, kind)} under the program's parameter
names (``paddle_tpu/models/deepseek_v3.py``: the published names,
``[in, out]``, the held experts stacked).

``cfg`` is the configuration file: ``model`` holds the published keys
(``n_routed_experts`` there counts the experts held here), ``published``
the source's values of what was cut, ``expert_parallel`` this chip's
rank among the chips that share a layer.  Every matrix is seeded normal; so is the
router's ``e_score_correction_bias`` (a trained model's is not zero, and
a zero bias would leave that path unexercised), at the same deviation
and in the served dtype.
"""
from __future__ import annotations


def router_width(cfg: dict) -> int:
    """The router scores every published expert, held here or not."""
    return int(cfg.get("published", {}).get(
        "n_routed_experts", cfg["model"]["n_routed_experts"]))


def local_experts(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    held = int(cfg["model"]["n_routed_experts"])
    rank = int(cfg.get("expert_parallel", {}).get("rank", 0))
    return rank * held, held


def shapes(cfg: dict) -> dict:
    m = cfg["model"]
    h, nh, v = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    fm, held = m["moe_intermediate_size"], local_experts(cfg)[1]
    out = {"model.embed_tokens.weight": ((v, h), "w"),
           "model.norm.weight": ((h,), "one"),
           "lm_head.weight": ((h, v), "w")}
    for n in range(m["num_hidden_layers"]):
        p = f"model.layers.{n}."
        a = p + "self_attn."
        out.update({
            p + "input_layernorm.weight": ((h,), "one"),
            p + "post_attention_layernorm.weight": ((h,), "one"),
            a + "q_a_proj.weight": ((h, m["q_lora_rank"]), "w"),
            a + "q_a_layernorm.weight": ((m["q_lora_rank"],), "one"),
            a + "q_b_proj.weight": ((m["q_lora_rank"], nh * qk), "w"),
            a + "kv_a_proj_with_mqa.weight": ((h, row), "w"),
            a + "kv_a_layernorm.weight": ((m["kv_lora_rank"],), "one"),
            a + "kv_b_proj.weight": (
                (m["kv_lora_rank"],
                 nh * (m["qk_nope_head_dim"] + m["v_head_dim"])), "w"),
            a + "o_proj.weight": ((nh * m["v_head_dim"], h), "w")})
        f = p + "mlp."
        if n < m["first_k_dense_replace"]:
            i = m["intermediate_size"]
            out.update({f + "gate_proj.weight": ((h, i), "w"),
                        f + "up_proj.weight": ((h, i), "w"),
                        f + "down_proj.weight": ((i, h), "w")})
            continue
        fs = fm * m["n_shared_experts"]
        out.update({
            f + "gate.weight": ((h, router_width(cfg)), "w"),
            f + "gate.e_score_correction_bias": ((router_width(cfg),), "w"),
            f + "shared_experts.gate_proj.weight": ((h, fs), "w"),
            f + "shared_experts.up_proj.weight": ((h, fs), "w"),
            f + "shared_experts.down_proj.weight": ((fs, h), "w"),
            f + "experts.gate_proj.weight": ((held, h, fm), "w"),
            f + "experts.up_proj.weight": ((held, h, fm), "w"),
            f + "experts.down_proj.weight": ((held, fm, h), "w")})
    return out
