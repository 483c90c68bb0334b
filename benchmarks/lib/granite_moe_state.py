"""Weight shapes and seeded weights of a ``granitemoehybrid``
configuration with routed experts: {key: (shape, kind)} under the
program's parameter names (``paddle_tpu/models/granite_hybrid.py``: the
published names, ``[in, out]``, the held experts stacked, one embedding
matrix and no ``lm_head``).

``cfg`` is the configuration file: ``model`` holds the published keys
(``num_local_experts`` there counts the experts held here,
``num_hidden_layers`` the layers served: the first ones of the published
``layer_types``, which is kept whole), ``published`` the source's values
of what was cut, ``expert_parallel`` this chip's rank among the chips
that share a layer.  A layer is ``hybrid_state``'s (its mixer, its two
norms, its shared MLP) with the expert part's three leaves beside it;
kinds and their seeding are ``hybrid_state``'s, the router and the
experts ``w`` (normal at ``assumed.initializer_range``), one jitted
call.
"""
from __future__ import annotations

import math

from benchmarks.lib import hybrid_state, state

MOE = "model.layers.{}.block_sparse_moe."


def layer_types(cfg: dict) -> list:
    """The served layers' kinds."""
    m = cfg["model"]
    return list(m["layer_types"][:m["num_hidden_layers"]])


def served(cfg: dict) -> dict:
    """``cfg`` with ``model.layer_types`` cut to the served layers: what
    ``hybrid_state`` reads."""
    return dict(cfg, model=dict(cfg["model"], layer_types=layer_types(cfg)))


def router_width(cfg: dict) -> int:
    """The router scores every published expert, held here or not."""
    return int(cfg.get("published", {}).get(
        "num_local_experts", cfg["model"]["num_local_experts"]))


def local_experts(cfg: dict) -> tuple:
    """(first, count) of the routed experts this chip holds."""
    held = int(cfg["model"]["num_local_experts"])
    rank = int(cfg.get("expert_parallel", {}).get("rank", 0))
    return rank * held, held


def dims(cfg: dict) -> dict:
    d = hybrid_state.dims(served(cfg))
    d["layers"] = len(layer_types(cfg))
    return d


def shapes(cfg: dict) -> dict:
    m = cfg["model"]
    h, f = m["hidden_size"], m["intermediate_size"]
    held = local_experts(cfg)[1]
    out = hybrid_state.shapes(served(cfg))
    for n in range(len(layer_types(cfg))):
        e = MOE.format(n)
        out.update({
            e + "router.layer.weight": ((h, router_width(cfg)), "w"),
            e + "input_linear.weight": ((held, h, 2 * f), "w"),
            e + "output_linear.weight": ((held, f, h), "w")})
    return out


def seeded(cfg: dict, seed: int) -> dict:
    """Every leaf of ``shapes(cfg)`` from ``seed``, on the device."""
    a = cfg["assumed"]
    spec = tuple((k, tuple(s), kind) for k, (s, kind) in sorted(
        shapes(cfg).items()))
    make = hybrid_state._maker(
        spec, float(a["initializer_range"]),
        float(a.get("embedding_std", a["initializer_range"])),
        a["torch_dtype"], 1.0 / math.sqrt(cfg["model"]["mamba_d_conv"]),
        (float(a["dt_min"]), float(a["dt_max"])),
        tuple(float(x) for x in a["A_init_range"]))
    return make(state.key_of(seed))
