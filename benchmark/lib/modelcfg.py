"""From a configuration file's published keys to the program's
``TransformerConfig``.  The file carries the model's ``config.json`` keys
verbatim at its top level; this is the only place that maps them onto the
repo's names, and it refuses what the repo's dense block cannot express
instead of running something else under the model's name."""

from __future__ import annotations

#: published key -> TransformerConfig field
_KEYS = {
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_layers",
    "hidden_size": "hidden_size",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "mlp_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tied_embeddings",
}


def transformer_kwargs(doc: dict) -> dict:
    """Keyword arguments of ``TransformerConfig`` for a configuration file."""
    missing = [k for k in _KEYS if k not in doc]
    if missing:
        raise ValueError(f"configuration lacks published keys {missing}")
    if doc.get("sliding_window") is not None:
        raise ValueError("sliding_window is set: the dense block attends "
                         "over the whole context")
    if doc.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {doc['hidden_act']!r}: the block's "
                         "gated MLP is SiLU (SwiGLU)")
    head_dim = doc["hidden_size"] // doc["num_attention_heads"]
    if doc.get("head_dim", head_dim) != head_dim:
        raise ValueError(f"head_dim {doc['head_dim']} is not hidden_size / "
                         f"num_attention_heads = {head_dim}, which is what "
                         "TransformerConfig derives")
    kw = {field: doc[key] for key, field in _KEYS.items()}
    kw.update(use_rope=True, use_rmsnorm=True, use_swiglu=True,
              use_qkv_bias=False, num_experts=1, attention_impl="auto")
    return kw


def transformer_config(doc: dict):
    from ray_tpu.models.config import TransformerConfig
    return TransformerConfig(**transformer_kwargs(doc))
