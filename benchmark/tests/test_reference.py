"""The plain reference against the program's forward pass at a tiny size:
float32 on both sides, so they agree to rounding."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.manifest import load_model

#: the block kind's file, loaded the way ``lib/manifest.Cell`` loads it
M = load_model(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "models", "mistral.py"))

DOC = {"hidden_act": "silu", "hidden_size": 64, "intermediate_size": 192,
       "max_position_embeddings": 256, "num_attention_heads": 4,
       "num_hidden_layers": 3, "num_key_value_heads": 2,
       "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
       "sliding_window": None, "tie_word_embeddings": False,
       "vocab_size": 128}


def test_reference_agrees_with_transformer_apply():
    import dataclasses

    from ray_tpu.models import transformer
    cfg = dataclasses.replace(M.program_config(DOC),
                              attention_impl="plain")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.random.default_rng(0).integers(0, 128, size=(2, 33))
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.apply(params, jnp.asarray(toks[:, :-1]), cfg,
                                   compute_dtype=jnp.float32)
        loss, _ = transformer.causal_lm_loss(
            params, {"tokens": jnp.asarray(toks)}, cfg,
            compute_dtype=jnp.float32)
    want = np.stack([M.logits(params, toks[b, :-1], DOC)
                     for b in range(2)])
    assert np.abs(np.asarray(got) - want).max() < 2e-4
    ref_loss = np.mean([M.loss(params, toks[b], DOC)
                        for b in range(2)])
    assert abs(float(loss) - ref_loss) < 1e-5
    # a bf16 forward in the reference's place would be seen
    low, _ = transformer.apply(params, jnp.asarray(toks[:, :-1]), cfg,
                               compute_dtype=jnp.bfloat16)
    assert np.abs(np.asarray(low) - want).max() > 2e-3


def test_costs_match_the_programs_own_counts():
    cfg = M.program_config(DOC)
    assert M.num_params(DOC) == cfg.num_params()
    assert M.train_flops_per_token(DOC, 64) == cfg.flops_per_token(64)
    from ray_tpu.models import decode
    assert M.kv_bytes_per_token(DOC) * 5 * 16 == \
        decode.cache_bytes(cfg, 5, 16)
    # one decode step reads every matrix once plus the live keys and values
    assert M.decode_step_bytes(DOC, 0, 0) == \
        2 * (M.num_params(DOC) - 128 * 64)
    real = {"hidden_size": 4096, "intermediate_size": 14336,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "num_hidden_layers": 16, "vocab_size": 32768}
    assert M.layer_params(real) == 218_103_808
    assert abs(M.decode_step_bytes(real, 0, 0) / 1e9 - 7.25) < 0.01
    assert M.kv_bytes_per_token(real) == 65536
