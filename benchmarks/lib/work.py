"""Operations and bytes the algorithms need, from shapes and traffic.

Nothing here knows which kernel ran: a later PR that replaces a kernel
changes the time under the same work.  A configuration is the ``model``
object of its file under ``benchmarks/configs/`` (the published keys).
Recomputed operations are never counted, nor are embedding lookups.
"""
from __future__ import annotations


# ---------------------------------------------------------------- decoder LM
def decoder_head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def decoder_layer_params(m: dict) -> dict:
    """Matrix parameters of one decoder layer (norm vectors apart)."""
    h, i = m["hidden_size"], m["intermediate_size"]
    hd = decoder_head_dim(m)
    q = h * m["num_attention_heads"] * hd
    kv = h * m["num_key_value_heads"] * hd
    attn = 2 * q + 2 * kv                      # q, o and k, v projections
    mlp = 3 * h * i                            # gate, up, down
    return {"attention": attn, "mlp": mlp, "total": attn + mlp}


def decoder_params(m: dict) -> dict:
    layers = m["num_hidden_layers"] * (
        decoder_layer_params(m)["total"] + 2 * m["hidden_size"])
    embed = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else embed
    return {"layers": layers, "embedding": embed, "head": head,
            "total": layers + embed + head + m["hidden_size"]}


def decoder_weight_bytes(m: dict, itemsize: int = 2) -> int:
    return decoder_params(m)["total"] * itemsize


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """K and V of one position, all layers."""
    return (2 * m["num_key_value_heads"] * decoder_head_dim(m) * itemsize
            * m["num_hidden_layers"])


def decoder_flops(m: dict, tokens: int, context_sum: int) -> float:
    """Forward operations for ``tokens`` positions whose attention spans
    ``context_sum`` keys in total (a decode token at position p spans p+1
    keys; a prefill of n tokens spans n(n+1)/2).  Matrix products are
    two operations a parameter a token, the head included; attention is
    QK^T and PV, two operations each for every query head, key and
    head-dim element."""
    matrix = 2.0 * tokens * (
        m["num_hidden_layers"] * decoder_layer_params(m)["total"]
        + m["vocab_size"] * m["hidden_size"])
    attn = (4.0 * context_sum * m["num_attention_heads"]
            * decoder_head_dim(m) * m["num_hidden_layers"])
    return matrix + attn


def prefill_context_sum(n: int) -> int:
    return n * (n + 1) // 2


def paged_decode_bytes(m: dict, context_sum: int, itemsize: int = 2) -> float:
    """Bytes of K/V that decode steps had to read: every decode token at
    context length c reads c positions of every layer's K and V."""
    return float(context_sum) * kv_bytes_per_token(m, itemsize)


# --------------------------------------------------------------------- BERT
def bert_matrix_params(m: dict) -> int:
    """Parameters in per-token matrix products: four H x H projections
    and the two MLP matrices of every layer.  Embedding tables (lookups),
    biases, norms, and the pooler and classifier (one row a sequence) are
    not matrix work per token."""
    h, i = m["hidden_size"], m["intermediate_size"]
    return m["num_hidden_layers"] * (4 * h * h + 2 * h * i)


def bert_params(m: dict, num_labels: int = 2) -> int:
    h, i, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    emb = (m["vocab_size"] + m["max_position_embeddings"]
           + m["type_vocab_size"]) * h + 2 * h
    layer = 4 * (h * h + h) + (h * i + i) + (i * h + h) + 4 * h
    return emb + L * layer + (h * h + h) + (h * num_labels + num_labels)


def bert_attention_flops(m: dict, batch: int, seq: int,
                         backward: bool) -> float:
    """QK^T and PV over the whole square (no mask): 4 S^2 H a sequence a
    layer forward; the backward pass needs dV, dP, dQ and dK, twice as
    much again.  The kernels' recomputation of QK^T is not counted."""
    fwd = 4.0 * seq * seq * m["hidden_size"] * m["num_hidden_layers"] * batch
    return fwd * 3.0 if backward else fwd


def bert_train_flops_per_step(m: dict, batch: int, seq: int) -> float:
    """Forward plus backward: 6 operations a matrix parameter a token,
    and attention."""
    return (6.0 * bert_matrix_params(m) * batch * seq
            + bert_attention_flops(m, batch, seq, backward=True))


# ------------------------------------------------------- work of a window
def window_work(name: str, model: dict, seen: dict) -> float:
    """Operations or bytes that the window's traffic needed, from what
    the driver saw of it (``seen``: steps and shapes, tokens and context
    sums).  One name per kind of work; a metric's file names the one it
    is a share of."""
    if name == "bert_train":
        return seen["steps"] * bert_train_flops_per_step(
            model, seen["batch"], seen["seq"])
    if name == "bert_attention_train":
        return seen["steps"] * bert_attention_flops(
            model, seen["batch"], seen["seq"], backward=True)
    if name == "decoder_serve":
        return decoder_flops(
            model, seen["decode_tokens"] + seen["prompt_tokens"],
            seen["decode_context_sum"] + seen["prefill_context_sum"])
    if name == "paged_decode_bytes":
        return paged_decode_bytes(model, seen["decode_context_sum"])
    raise KeyError(f"no work function called {name!r}")
