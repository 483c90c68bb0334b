"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the same function
feeds the system under test and, after the window, the plain reference,
which therefore takes nothing the program has made.  Matrix leaves are
normal with the published initializer range as standard deviation (a
uniform draw has no tails, and would flatter any per-channel quantizer
that ``correct``'s control stands for), norm weights 1, biases and norm
biases 0; every leaf has its own key folded from its position in the
sorted key list.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_of(seed: int):
    # seeds run a little past 2**31; fold the high bits in
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              seed >> 31)


def decoder_shapes(m: dict) -> dict:
    """{key: (shape, kind)} under the program's Llama key names."""
    h, i, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = m.get("head_dim") or h // m["num_attention_heads"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    out = {"llama.embed_tokens.weight": ((v, h), "w"),
           "llama.norm.weight": ((h,), "one")}
    if not m.get("tie_word_embeddings"):
        out["lm_head.weight"] = ((h, v), "w")
    for n in range(m["num_hidden_layers"]):
        p = f"llama.layers.{n}."
        out.update({
            p + "self_attn.q_proj.weight": ((h, q), "w"),
            p + "self_attn.k_proj.weight": ((h, kv), "w"),
            p + "self_attn.v_proj.weight": ((h, kv), "w"),
            p + "self_attn.o_proj.weight": ((q, h), "w"),
            p + "mlp.gate_proj.weight": ((h, i), "w"),
            p + "mlp.up_proj.weight": ((h, i), "w"),
            p + "mlp.down_proj.weight": ((i, h), "w"),
            p + "input_layernorm.weight": ((h,), "one"),
            p + "post_attention_layernorm.weight": ((h,), "one")})
    return out


def bert_shapes(m: dict, num_labels: int) -> dict:
    """{key: (shape, kind)} under the program's BERT parameter names."""
    h, i = m["hidden_size"], m["intermediate_size"]
    e = "bert.embeddings."
    out = {e + "word_embeddings.weight": ((m["vocab_size"], h), "w"),
           e + "position_embeddings.weight":
               ((m["max_position_embeddings"], h), "w"),
           e + "token_type_embeddings.weight":
               ((m["type_vocab_size"], h), "w"),
           e + "layer_norm.weight": ((h,), "one"),
           e + "layer_norm.bias": ((h,), "zero"),
           "bert.pooler.weight": ((h, h), "w"),
           "bert.pooler.bias": ((h,), "zero"),
           "classifier.weight": ((h, num_labels), "w"),
           "classifier.bias": ((num_labels,), "zero")}
    for n in range(m["num_hidden_layers"]):
        p = f"bert.encoder.{n}."
        for name, (a, b) in (("attention.query", (h, h)),
                             ("attention.key", (h, h)),
                             ("attention.value", (h, h)),
                             ("attention.dense", (h, h)),
                             ("intermediate", (h, i)),
                             ("output", (i, h))):
            out[p + name + ".weight"] = ((a, b), "w")
            out[p + name + ".bias"] = ((b,), "zero")
        for name in ("attention.layer_norm", "layer_norm"):
            out[p + name + ".weight"] = ((h,), "one")
            out[p + name + ".bias"] = ((h,), "zero")
    return out


@functools.lru_cache(maxsize=8)
def _maker(spec: tuple, std: float, dtype: str):
    """One jitted program that makes every leaf of ``spec`` from a key."""
    dt = jnp.dtype(dtype)

    def make(key):
        out = {}
        for n, (name, shape, kind) in enumerate(spec):
            if kind == "w":
                out[name] = (std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
                ).astype(dt)
            else:
                out[name] = jnp.full(shape, 1.0 if kind == "one" else 0.0,
                                     dt)
        return out

    return jax.jit(make)


def maker(shapes: dict, *, std: float, dtype: str):
    """The jitted ``key -> {name: leaf}`` for ``shapes``.  The seed is
    never part of a program: it comes in as the key (``key_of``), so
    every seed runs the one compiled program."""
    spec = tuple((k, tuple(s), kind) for k, (s, kind) in sorted(
        shapes.items()))
    return _maker(spec, float(std), str(dtype))


def seeded(shapes: dict, seed: int, *, std: float, dtype: str) -> dict:
    return maker(shapes, std=std, dtype=dtype)(key_of(seed))


def decoder_state(m: dict, seed: int, *, std: float = 0.02,
                  dtype: str = "bfloat16") -> dict:
    return seeded(decoder_shapes(m), seed, std=std, dtype=dtype)


def bert_state(m: dict, seed: int, *, num_labels: int = 2,
               std: float = 0.02, dtype: str = "float32") -> dict:
    return seeded(bert_shapes(m, num_labels), seed, std=std, dtype=dtype)
