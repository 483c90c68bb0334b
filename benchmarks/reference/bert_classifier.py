"""BERT for sequence classification, its loss, gradients and AdamW: the
plain reference (Devlin et al., arXiv:1810.04805; Loshchilov & Hutter,
arXiv:1711.05101).

``jax.numpy``, float32, matmul precision ``highest``; no kernel, no
autocast, nothing imported from ``paddle_tpu``.  Parameters are a flat
dict under the names of ``benchmarks/lib/state.bert_shapes``; weights are
[in, out].  Departures from the published model, both stated in the
configuration's file: no dropout (the program's BERT has none), and the
classifier head on the pooled first token with ``num_labels`` outputs.

Layers run under ``lax.scan`` with ``jax.checkpoint``, so the backward
pass holds one layer's activations at a time and the reference fits
beside nothing else on a 16 GB chip at BERT-large, batch 16 x 384.

``fp8=True`` is the control of ``correct``, the step below bf16 that
would tempt a later PR, in the usual recipe: wherever the configuration's
autocast computes in bf16 (every linear layer, and attention's two
products) the operands are rounded to ``float8_e4m3fn`` under a
per-tensor scale on the way forward (a straight-through estimator
carries the gradient), and the gradient that arrives at the product's
output is rounded to ``float8_e5m2`` on the way back.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = (
    "attention.query.weight", "attention.query.bias",
    "attention.key.weight", "attention.key.bias",
    "attention.value.weight", "attention.value.bias",
    "attention.dense.weight", "attention.dense.bias",
    "attention.layer_norm.weight", "attention.layer_norm.bias",
    "intermediate.weight", "intermediate.bias",
    "output.weight", "output.bias",
    "layer_norm.weight", "layer_norm.bias")

HI = jax.lax.Precision.HIGHEST


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_back(y):
    """The identity, whose gradient is rounded to float8_e5m2."""
    return y


def _fp8_back_bwd(_, g):
    scale = jnp.max(jnp.abs(g)) / 57344.0 + 1e-30
    return ((g / scale).astype(jnp.float8_e5m2).astype(jnp.float32)
            * scale,)


_fp8_back.defvjp(lambda y: (y, None), _fp8_back_bwd)


def _linear(x, w, b, fp8):
    if not fp8:
        return jnp.matmul(x, w, precision=HI) + b
    return _fp8_back(jnp.matmul(_fp8(x), _fp8(w), precision=HI)) + b


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _layer(x, w, *, heads, eps, fp8):
    b, s, h = x.shape
    hd = h // heads

    def split(t):
        return t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)

    q = split(_linear(x, w["attention.query.weight"],
                      w["attention.query.bias"], fp8))
    k = split(_linear(x, w["attention.key.weight"],
                      w["attention.key.bias"], fp8))
    v = split(_linear(x, w["attention.value.weight"],
                      w["attention.value.bias"], fp8))
    low = (lambda t: _fp8(t)) if fp8 else (lambda t: t)
    back = _fp8_back if fp8 else (lambda t: t)
    scores = back(jnp.einsum("bhqd,bhkd->bhqk", low(q), low(k),
                             precision=HI)) / math.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = back(jnp.einsum("bhqk,bhkd->bhqd", low(probs), low(v),
                          precision=HI))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    out = _linear(ctx, w["attention.dense.weight"],
                  w["attention.dense.bias"], fp8)
    x = _layer_norm(x + out, w["attention.layer_norm.weight"],
                    w["attention.layer_norm.bias"], eps)
    y = jax.nn.gelu(_linear(x, w["intermediate.weight"],
                            w["intermediate.bias"], fp8),
                    approximate=False)
    y = _linear(y, w["output.weight"], w["output.bias"], fp8)
    return _layer_norm(x + y, w["layer_norm.weight"],
                       w["layer_norm.bias"], eps)


def logits_of(params, ids, token_types, *, layers, heads, eps, fp8=False):
    e = "bert.embeddings."
    s = ids.shape[1]
    x = (jnp.take(params[e + "word_embeddings.weight"], ids, axis=0)
         + params[e + "position_embeddings.weight"][None, :s]
         + jnp.take(params[e + "token_type_embeddings.weight"],
                    token_types, axis=0))
    x = _layer_norm(x, params[e + "layer_norm.weight"],
                    params[e + "layer_norm.bias"], eps)
    stacked = {leaf: jnp.stack([params[f"bert.encoder.{n}.{leaf}"]
                                for n in range(layers)])
               for leaf in LAYER_LEAVES}
    body = jax.checkpoint(functools.partial(
        _layer, heads=heads, eps=eps, fp8=fp8))
    x, _ = jax.lax.scan(lambda c, w: (body(c, w), None), x, stacked)
    pooled = jnp.tanh(_linear(x[:, 0], params["bert.pooler.weight"],
                              params["bert.pooler.bias"], fp8))
    return _linear(pooled, params["classifier.weight"],
                   params["classifier.bias"], fp8)


def loss_of(params, ids, token_types, labels, **kw):
    logits = logits_of(params, ids, token_types, **kw)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _strided(leaf, n):          # benchmarks/lib/stats.strided, kept apart:
    flat = leaf.reshape(-1)     # the reference imports nothing but jax
    return flat[::max(1, flat.shape[0] // n)][:n]


@functools.partial(jax.jit, static_argnames=(
    "layers", "heads", "eps", "fp8", "lr", "beta1", "beta2", "adam_eps",
    "weight_decay", "sample"), donate_argnums=(0, 1, 2))
def adamw_step(params, m, v, t, ids, token_types, labels, *, layers, heads,
               eps, fp8, lr, beta1, beta2, adam_eps, weight_decay,
               sample=4096):
    """One step of decoupled AdamW on the mean cross entropy.  ``t`` is
    the step's number from 1.  Returns the loss, the norm of every
    leaf's gradient with ``sample`` of its elements spread evenly over
    the leaf, and the new params, m, v."""
    loss, grads = jax.value_and_grad(loss_of)(
        params, ids, token_types, labels, layers=layers, heads=heads,
        eps=eps, fp8=fp8)
    new_p, new_m, new_v = {}, {}, {}
    for k, g in grads.items():
        x = params[k] * (1.0 - lr * weight_decay)
        new_m[k] = beta1 * m[k] + (1.0 - beta1) * g
        new_v[k] = beta2 * v[k] + (1.0 - beta2) * jnp.square(g)
        mhat = new_m[k] / (1.0 - beta1 ** t)
        vhat = new_v[k] / (1.0 - beta2 ** t)
        new_p[k] = x - lr * mhat / (jnp.sqrt(vhat) + adam_eps)
    seen = (leaf_norms(grads),
            {k: _strided(g, sample) for k, g in grads.items()})
    return loss, seen, new_p, new_m, new_v


def follow(params, batches, *, model: dict, optimizer: dict,
           fp8: bool = False, sample: int = 4096) -> dict:
    """Drive ``len(batches)`` AdamW steps from ``params`` (consumed) on
    ``batches`` [(ids, token types, labels), ...].  Returns the losses,
    the first step's gradient norm (with a sample of its elements) and
    the whole change's norm of every leaf."""
    start = {k: jnp.array(v, copy=True) for k, v in params.items()}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(p) for k, p in params.items()}
    kw = dict(layers=int(model["num_hidden_layers"]),
              heads=int(model["num_attention_heads"]),
              eps=float(model["layer_norm_eps"]), fp8=bool(fp8),
              lr=float(optimizer["learning_rate"]),
              beta1=float(optimizer["beta1"]),
              beta2=float(optimizer["beta2"]),
              adam_eps=float(optimizer["epsilon"]),
              weight_decay=float(optimizer["weight_decay"]),
              sample=int(sample))
    losses, first = [], None
    for t, (ids, tts, labels) in enumerate(batches, 1):
        loss, (gnorm, some), params, m, v = adamw_step(
            params, m, v, jnp.float32(t), ids, tts, labels, **kw)
        losses.append(float(loss))
        if first is None:
            first = {k: float(x) for k, x in gnorm.items()}
            sampled = {k: np.asarray(x) for k, x in some.items()}
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(params, start)
    return {"losses": losses, "grad_norm": first, "grad_sample": sampled,
            "change_norm": {k: float(x) for k, x in change.items()}}
