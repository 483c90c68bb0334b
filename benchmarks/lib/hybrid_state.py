"""Weight shapes and seeded weights of a ``granitemoehybrid``
configuration: {key: (shape, kind)} under the program's parameter names
(``paddle_tpu/models/granite_hybrid.py``: the published names,
``[in, out]``, one embedding matrix and no ``lm_head``).

``cfg`` is the configuration file; ``model`` holds the published keys.
Kinds: ``w`` a matrix, normal at ``assumed.initializer_range``;
``embed`` the one embedding matrix, normal at ``assumed.embedding_std``
(``initializer_range`` where the file gives none); ``one`` a norm weight
or ``D``; and the Mamba-2 parameters as the published
implementation initialises them (the configuration file says why):
``a_log`` = log of uniform over ``assumed.A_init_range`` a head,
``dt_bias`` = the inverse softplus of a log-uniform dt on
[``dt_min``, ``dt_max``], ``conv`` = uniform(-b, b), b = 1 /
sqrt(``mamba_d_conv``), the convolution's weight and bias alike.  Every
leaf is in the served dtype and has its own key folded from its place in
the sorted key list, as ``state.seeded`` does it; the seed comes in as
the key, so every seed runs the one compiled program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib import state


def dims(cfg: dict) -> dict:
    m = cfg["model"]
    d_inner = m["mamba_n_heads"] * m["mamba_d_head"]
    conv = d_inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    return {"d_inner": d_inner, "conv_dim": conv,
            "in_proj": 2 * d_inner + 2 * m["mamba_n_groups"]
            * m["mamba_d_state"] + m["mamba_n_heads"],
            "head_dim": m["hidden_size"] // m["num_attention_heads"],
            "mamba_layers": m["layer_types"].count("mamba"),
            "attention_layers": m["layer_types"].count("attention")}


def shapes(cfg: dict) -> dict:
    m, d = cfg["model"], dims(cfg)
    h, i = m["hidden_size"], m["shared_intermediate_size"]
    q = m["num_attention_heads"] * d["head_dim"]
    kv = m["num_key_value_heads"] * d["head_dim"]
    nh = m["mamba_n_heads"]
    out = {"model.embed_tokens.weight": ((m["vocab_size"], h), "embed"),
           "model.norm.weight": ((h,), "one")}
    for n, kind in enumerate(m["layer_types"]):
        p = f"model.layers.{n}."
        out.update({
            p + "input_layernorm.weight": ((h,), "one"),
            p + "post_attention_layernorm.weight": ((h,), "one"),
            p + "shared_mlp.input_linear.weight": ((h, 2 * i), "w"),
            p + "shared_mlp.output_linear.weight": ((i, h), "w")})
        if kind == "attention":
            a = p + "self_attn."
            out.update({a + "q_proj.weight": ((h, q), "w"),
                        a + "k_proj.weight": ((h, kv), "w"),
                        a + "v_proj.weight": ((h, kv), "w"),
                        a + "o_proj.weight": ((q, h), "w")})
            continue
        s = p + "mamba."
        out.update({
            s + "in_proj.weight": ((h, d["in_proj"]), "w"),
            s + "conv1d.weight": ((d["conv_dim"], m["mamba_d_conv"]),
                                  "conv"),
            s + "dt_bias": ((nh,), "dt_bias"),
            s + "A_log": ((nh,), "a_log"),
            s + "D": ((nh,), "one"),
            s + "norm.weight": ((d["d_inner"],), "one"),
            s + "out_proj.weight": ((d["d_inner"], h), "w")})
        if m["mamba_conv_bias"]:
            out[s + "conv1d.bias"] = ((d["conv_dim"],), "conv")
    return out


@functools.lru_cache(maxsize=4)
def _maker(spec: tuple, std: float, embed_std: float, dtype: str,
           conv_bound: float, dt_range: tuple, a_range: tuple):
    dt_ = jnp.dtype(dtype)
    f32 = jnp.float32

    def make(key):
        out = {}
        for n, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, n)
            if kind in ("w", "embed"):
                v = (std if kind == "w" else embed_std) * jax.random.normal(
                    k, shape, f32)
            elif kind == "one":
                v = jnp.ones(shape, f32)
            elif kind == "conv":
                v = jax.random.uniform(k, shape, f32, -conv_bound,
                                       conv_bound)
            elif kind == "a_log":
                v = jnp.log(jax.random.uniform(k, shape, f32, *a_range))
            else:                       # dt_bias
                lo, hi = (math.log(x) for x in dt_range)
                dt = jnp.exp(jax.random.uniform(k, shape, f32, lo, hi))
                v = dt + jnp.log(-jnp.expm1(-dt))
            out[name] = v.astype(dt_)
        return out

    return jax.jit(make)


def seeded(cfg: dict, seed: int) -> dict:
    """Every leaf of ``shapes(cfg)`` from ``seed``, on the device."""
    a = cfg["assumed"]
    spec = tuple((k, tuple(s), kind) for k, (s, kind) in sorted(
        shapes(cfg).items()))
    make = _maker(spec, float(a["initializer_range"]),
                  float(a.get("embedding_std", a["initializer_range"])),
                  a["torch_dtype"],
                  1.0 / math.sqrt(cfg["model"]["mamba_d_conv"]),
                  (float(a["dt_min"]), float(a["dt_max"])),
                  tuple(float(x) for x in a["A_init_range"]))
    return make(state.key_of(seed))
