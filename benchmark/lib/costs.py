"""Operations and bytes the algorithms need, computed from shapes.  These
are the numerators of every roofline and utilisation share the benchmark
reports, kept here so that no PR that claims a gain can change them.  All
take the configuration file's published keys."""

from __future__ import annotations


def _dims(doc: dict):
    h = doc["hidden_size"]
    nh, nkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    return h, nh, nkv, h // nh, doc["intermediate_size"], \
        doc["num_hidden_layers"], doc["vocab_size"]


def layer_params(doc: dict) -> int:
    """Matrix parameters of one block: q and o, k and v, gate, up, down."""
    h, nh, nkv, hd, m, _, _ = _dims(doc)
    return 2 * h * nh * hd + 2 * h * nkv * hd + 3 * h * m


def num_params(doc: dict) -> int:
    h, _, _, _, _, L, v = _dims(doc)
    emb = v * h * (1 if doc.get("tie_word_embeddings") else 2)
    return L * layer_params(doc) + emb


def train_flops_per_token(doc: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token that training requires: 6 per
    active matrix parameter (the input embedding is a gather, the head a
    matmul) plus the attention score and value matmuls.  Recomputation does
    not count.  Copied from ``TransformerConfig.flops_per_token`` (which
    counts the quadratic term unhalved by causality; kept so that MFU here
    equals the number ``bench.py`` printed)."""
    h, _, _, _, _, L, v = _dims(doc)
    n_active = L * layer_params(doc) + v * h
    return 6.0 * n_active + 6.0 * L * 2 * seq_len * h


def kv_bytes_per_token(doc: dict, dtype_bytes: int = 2) -> int:
    """Bytes of K and V one token holds over all layers."""
    _, _, nkv, hd, _, L, _ = _dims(doc)
    return 2 * nkv * hd * dtype_bytes * L


def decode_step_bytes(doc: dict, live_kv_tokens: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read from HBM: every block matrix and
    the head once (the embedding is a gather of a few rows), plus the keys
    and values of the tokens that are live in the batch."""
    h, _, _, _, _, L, v = _dims(doc)
    weights = (L * layer_params(doc) + v * h) * dtype_bytes
    return weights + live_kv_tokens * kv_bytes_per_token(doc, dtype_bytes)


def decode_step_flops(doc: dict, active_slots: float,
                      live_kv_tokens: float) -> float:
    """FLOPs of one decode step: 2 per matrix parameter per active slot and
    the score and value products over the live context."""
    h, nh, _, hd, _, L, v = _dims(doc)
    return (2.0 * (L * layer_params(doc) + v * h) * active_slots
            + 4.0 * L * nh * hd * live_kv_tokens)


def flash_attention_flops(doc: dict, batch: int, seq_len: int,
                          backward: bool) -> float:
    """FLOPs causal flash attention needs for ``batch`` sequences in every
    layer: 2 matmuls forward (QK^T, PV) and 5 backward (S recomputed once,
    dP, dV, dQ, dK: the published algorithm), each 2*S*S*D per head, halved
    by causality."""
    _, nh, _, hd, _, L, _ = _dims(doc)
    one = 2.0 * seq_len * seq_len * hd * nh / 2
    return L * batch * one * (2 + (5 if backward else 0))


def flash_attention_bytes(doc: dict, batch: int, seq_len: int,
                          backward: bool, dtype_bytes: int = 2) -> float:
    """Bytes the same calls have to move: forward reads Q, K, V and writes
    O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    _, nh, nkv, hd, _, L, _ = _dims(doc)
    q = seq_len * nh * hd * dtype_bytes
    kv = seq_len * nkv * hd * dtype_bytes
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return L * batch * (fwd + (bwd if backward else 0))
