"""The family of the three configurations the benchmark started with: a
pre-norm decoder with rotary grouped-query attention and a gated SiLU
feed-forward, run through `ray_tpu/models/llama.py` (the shared code) and
held to `reference/dense_decoder.py`.

A family is what the harness needs to know about a kind of model and
nothing else; `loader.load_cell` finds it by the configuration file's
`family` (this one where the file names none).  A later family is a new
file `families/<name>.py` in any directory of `paths` with these names:

    sizes(config)            the model part of the file, always with
                             `vocab_size` (the traffic draws ids from it)
    program_config(sizes, **overrides)   the program's own configuration
    model(cfg)               the program's module: `init(key, tokens)`,
                             `apply(params, tokens)` -> float32 logits
    loss(logits, targets)    the program's mean next-token loss
    reference                the plain reference: `logits(params, sizes,
                             tokens, rows, rounded=0)`, `ROUNDINGS`,
                             `mean_token_loss(params, sizes, inputs, targets)`
    REDUCIBLE                the keys a configuration of it may cut
    check_file(conf)         what must hold of a file of this family
"""

from __future__ import annotations

from benchmarks.reference import dense_decoder as reference  # noqa: F401

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings",
              "torch_dtype")
REDUCIBLE = {"num_hidden_layers"}


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model."""
    return {k: config[k] for k in MODEL_KEYS}


def program_config(sizes: dict, **overrides):
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


def model(cfg):
    from ray_tpu.models.llama import LlamaModel

    return LlamaModel(cfg)


def loss(logits, targets):
    from ray_tpu.models.llama import cross_entropy_loss

    return cross_entropy_loss(logits, targets)


def check_file(conf: dict) -> None:
    cfg = program_config(sizes(conf))
    if not cfg.head_dim == conf["head_dim"] == 128:
        raise ValueError(f"heads of {conf['head_dim']}: the kernels and "
                         "the cost functions here are for heads of 128")
