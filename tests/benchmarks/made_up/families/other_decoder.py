"""A made-up family, for the tests alone: the same decoder under other key
names, with heads of 64.  It shows what a later PR's family file holds
and that the harness finds it, its configuration, its reference and its
mix from files alone (`benchmarks/families/dense_decoder.py` lists the
names a family gives)."""

from __future__ import annotations

from benchmarks.harness import loader

reference = loader.beside(__file__, "reference", "other_decoder_ref.py")

REDUCIBLE = {"n_layer"}
DEPTH_KEY = "n_layer"       # (`num_hidden_layers` where a family names none)


def sizes(config: dict) -> dict:
    return {k: config[k] for k in (
        "d_model", "d_ff", "n_layer", "n_head", "n_kv_head", "d_head",
        "vocab_size", "rope_base", "norm_eps", "n_positions", "tied_head",
        "dtype")}


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["d_model"],
        n_layers=sizes["n_layer"], n_heads=sizes["n_head"],
        n_kv_heads=sizes["n_kv_head"], d_ff=sizes["d_ff"],
        max_seq_len=sizes["n_positions"],
        rope_theta=float(sizes["rope_base"]),
        rms_eps=float(sizes["norm_eps"]),
        dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[sizes["dtype"]],
        tie_embeddings=bool(sizes["tied_head"]),
        **{"attention": "reference", "remat": False, **overrides})


def model(cfg):
    from ray_tpu.models.llama import LlamaModel

    return LlamaModel(cfg)


def loss(logits, targets):
    from ray_tpu.models.llama import cross_entropy_loss

    return cross_entropy_loss(logits, targets)


def check_file(conf: dict) -> None:
    if conf["d_head"] * conf["n_head"] != conf["d_model"] or \
            conf["d_head"] != 64:
        raise ValueError("this family has heads of 64 that make up d_model")
