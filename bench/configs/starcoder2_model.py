"""How a starcoder2 configuration file becomes the program's model and
its weights: the program's ``ModelConfig`` for the file's sizes, and
random weights in the program's parameter layout, made from a key in the
dtype they are served in.

A configuration names its model file under the key ``model``; the
serving driver calls :func:`model_config` and, under one ``jax.jit``,
:func:`init_params`.
"""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    if cfg["hidden_act"] != "gelu_pytorch_tanh" or \
            cfg["norm_type"] != "layer_norm":
        raise ValueError("a starcoder2 file has a tanh GELU and LayerNorm")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm="layernorm", act="gelu", rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"], source=cfg["source"])


def init_params(cfg: dict, key):
    """Random weights in the program's parameter layout, made in the
    dtype they are served in. Call under ``jax.jit``."""
    import jax
    import jax.numpy as jnp
    w = cfg["weights"]
    dt = jnp.dtype(cfg["torch_dtype"])
    f32 = jnp.float32
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, F = cfg["head_dim"], cfg["intermediate_size"]
    V = -(-cfg["vocab_size"] // 256) * 256
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std, dtype=dt):
        return (jax.random.normal(next(keys), shape, dtype) * std
                ).astype(dtype)

    def norm(shape):
        return {"ln_scale": normal(shape, w["norm_scale_std"], f32),
                "ln_bias": normal(shape, w["norm_bias_std"], f32)}

    attn = {"wq": normal((L, D, H * dh), D ** -.5),
            "wk": normal((L, D, Hkv * dh), D ** -.5),
            "wv": normal((L, D, Hkv * dh), D ** -.5),
            "wo": normal((L, H * dh, D), (2 * H * dh * L) ** -.5),
            **norm((L, D))}
    ffn = {"w_in": normal((L, D, F), D ** -.5),
           "b_in": normal((L, F), w["mlp_bias_std"]),
           "w_out": normal((L, F, D), (2 * F * L) ** -.5),
           "b_out": normal((L, D), w["mlp_bias_std"]),
           **norm((L, D))}
    return {"embed": normal((V, D), w["embedding_std"]),
            "body": [{"attn": attn, "ffn": ffn}], "tail": [],
            "final_scale": normal((D,), w["norm_scale_std"], f32),
            "final_bias": normal((D,), w["norm_bias_std"], f32),
            "lm_head": normal((D, V), D ** -.5)}
