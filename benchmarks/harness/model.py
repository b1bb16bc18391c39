"""A configuration file's sizes (the source's own key names) as the
program's `LlamaConfig`.  Both configurations run through
`ray_tpu/models/llama.py`, which is the shared code."""

from __future__ import annotations

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings",
              "torch_dtype")


def model_sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model."""
    return {k: config[k] for k in MODEL_KEYS}


def llama_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if sizes["head_dim"] * sizes["num_attention_heads"] != \
            sizes["hidden_size"]:
        raise ValueError("models/llama.py derives head_dim as hidden_size / "
                         "num_attention_heads; this configuration differs")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return LlamaConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"],
        max_seq_len=sizes["max_position_embeddings"],
        rope_theta=float(sizes["rope_theta"]),
        rms_eps=float(sizes["rms_norm_eps"]), dtype=dtype,
        tie_embeddings=bool(sizes["tie_word_embeddings"]),
        **{"attention": "reference", "remat": False, **overrides})
