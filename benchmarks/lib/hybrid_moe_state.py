"""Weight shapes and seeded weights of a ``nemotron_h`` configuration:
{key: (shape, kind)} under the program's parameter names
(``paddle_tpu/models/nemotron_h.py``: the published names, ``[in,
out]``, the held experts stacked, an untied ``lm_head``).

``cfg`` is the configuration file: ``model`` holds the published keys
(``n_routed_experts`` there counts the experts held here), ``published``
the source's values of what was cut, ``expert_parallel`` this chip's
rank among the chips that share a block.  Kinds and their seeding are
``hybrid_state``'s (``w`` a matrix, normal at
``assumed.initializer_range``; ``one``; the Mamba-2 parameters as the
published implementation initialises them: ``a_log``, ``dt_bias``,
``conv``), one jitted call; the routers' ``e_score_correction_bias`` is
seeded normal and scaled to ``assumed.e_score_correction_bias_std``
after it, as ``engine_closed_loop_mla`` does (a trained model's is not
zero, and a zero bias would leave that path unexercised).

The held experts' stacks are stored ``expert_width`` wide: the
published ``moe_intermediate_size`` in whole 128-lane tiles (1,856 ->
1,920), as the program stores them (the configuration's
``assumed.expert_matrices`` says why).  The added columns of ``up_proj``
and rows of ``down_proj`` are zeros, set once here, so every expert is
the published function: ``relu(0)^2 = 0``, times rows of zeros.
"""
from __future__ import annotations

import math

from benchmarks.lib import state
from benchmarks.lib.hybrid_state import _maker
from benchmarks.lib.mla_moe_state import (local_experts,  # noqa: F401
                                          router_width)

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}
LANES = 128


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    d_inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv = d_inner + 2 * m["n_groups"] * m["ssm_state_size"]
    kinds = [KINDS[c] for c in m["hybrid_override_pattern"]]
    return {"d_inner": d_inner, "conv_dim": conv,
            "in_proj": d_inner + conv + m["mamba_num_heads"],
            "expert_width": -(-m["moe_intermediate_size"] // LANES) * LANES,
            "kinds": kinds, "mamba_layers": kinds.count("mamba"),
            "attention_layers": kinds.count("attention"),
            "expert_layers": kinds.count("moe")}


def shapes(cfg: dict) -> dict:
    m, d = cfg["model"], dims(cfg)
    h, v = m["hidden_size"], m["vocab_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    nh, held = m["mamba_num_heads"], local_experts(cfg)[1]
    fm, fs = d["expert_width"], m["moe_shared_expert_intermediate_size"]
    out = {"backbone.embeddings.weight": ((v, h), "w"),
           "backbone.norm_f.weight": ((h,), "one"),
           "lm_head.weight": ((h, v), "w")}
    for n, kind in enumerate(d["kinds"]):
        p = f"backbone.layers.{n}."
        s = p + "mixer."
        out[p + "norm.weight"] = ((h,), "one")
        if kind == "attention":
            out.update({s + "q_proj.weight": ((h, q), "w"),
                        s + "k_proj.weight": ((h, kv), "w"),
                        s + "v_proj.weight": ((h, kv), "w"),
                        s + "o_proj.weight": ((q, h), "w")})
        elif kind == "moe":
            e = router_width(cfg)
            out.update({
                s + "gate.weight": ((h, e), "w"),
                s + "gate.e_score_correction_bias": ((e,), "w"),
                s + "shared_experts.up_proj.weight": ((h, fs), "w"),
                s + "shared_experts.down_proj.weight": ((fs, h), "w"),
                s + "experts.up_proj.weight": ((held, h, fm), "w"),
                s + "experts.down_proj.weight": ((held, fm, h), "w")})
        else:
            out.update({
                s + "in_proj.weight": ((h, d["in_proj"]), "w"),
                s + "conv1d.weight": ((d["conv_dim"], m["conv_kernel"]),
                                      "conv"),
                s + "dt_bias": ((nh,), "dt_bias"),
                s + "A_log": ((nh,), "a_log"),
                s + "D": ((nh,), "one"),
                s + "norm.weight": ((d["d_inner"],), "one"),
                s + "out_proj.weight": ((d["d_inner"], h), "w")})
            if m["use_conv_bias"]:
                out[s + "conv1d.bias"] = ((d["conv_dim"],), "conv")
    return out


def seeded(cfg: dict, seed: int) -> dict:
    """Every leaf of ``shapes(cfg)`` from ``seed``, on the device."""
    a, m = cfg["assumed"], cfg["model"]
    std = float(a["initializer_range"])
    spec = tuple((k, tuple(s), kind) for k, (s, kind) in sorted(
        shapes(cfg).items()))
    make = _maker(spec, std, std, a["torch_dtype"],
                  1.0 / math.sqrt(m["conv_kernel"]),
                  (float(m["time_step_min"]), float(m["time_step_max"])),
                  tuple(float(x) for x in a["A_init_range"]))
    weights = make(state.key_of(seed))
    fm = m["moe_intermediate_size"]
    for key in [k for k in weights if ".experts." in k]:
        at = ((slice(None), slice(None), slice(fm, None))
              if key.endswith("up_proj.weight")
              else (slice(None), slice(fm, None)))
        weights[key] = weights[key].at[at].set(0)
    shrink = float(a.get("e_score_correction_bias_std", std)) / std
    for key in [k for k in weights if k.endswith("e_score_correction_bias")]:
        weights[key] = (weights[key].astype("float32")
                        * shrink).astype(a["torch_dtype"])
    return weights
