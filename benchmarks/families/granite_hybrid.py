"""The `granite_hybrid` family: Granite-4.0-H's decoder (`granitemoehybrid`
with no routed experts: Mamba-2 layers, a few plain grouped-query
attention layers with no position term among them, a gated feed-forward
after every mixer, four multipliers), run through
`ray_tpu/models/granite_hybrid.py` and held to `reference/granite_hybrid.py`.
`families/dense_decoder.py` lists the names a family gives.

A configuration file holds the published `config.json` keys verbatim.
"""

from __future__ import annotations

import os

from benchmarks.harness import loader
from benchmarks.reference import granite_hybrid as reference  # noqa: F401

# A checkout whose program has no such model (any commit before PR 36, with
# these benchmark files laid over it) is told so here, as the cell is loaded
# and before any cluster or replica is started: the command exits 1 at once.
if not os.path.isfile(os.path.join(loader.REPO_ROOT, "ray_tpu", "models",
                                   "granite_hybrid.py")):
    raise loader.BenchmarkError(
        "this checkout's program cannot run the `granite_hybrid` family: it "
        "has no ray_tpu/models/granite_hybrid.py (the engine serves it "
        "since PR 36)")

MODEL_KEYS = (
    "attention_bias", "attention_multiplier", "embedding_multiplier",
    "hidden_act", "hidden_size", "intermediate_size", "layer_types",
    "logits_scaling", "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv",
    "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_groups",
    "mamba_n_heads", "mamba_proj_bias", "max_position_embeddings",
    "normalization_function", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_local_experts",
    "position_embedding_type", "residual_multiplier", "rms_norm_eps",
    "rope_theta", "shared_intermediate_size", "tie_word_embeddings",
    "vocab_size", "torch_dtype")
REDUCIBLE = {"num_hidden_layers"}
HEAD_DIM = 64

# The seeded weights of a run (`assumed.weights` of the configuration file
# says why each): standard deviations of the embedding, of the matrices
# that read the stream (the attention layers' apart), of those that write
# into it, the final norm's scale, and the ranges each Mamba-2 head's step
# size dt and decay |A| are drawn from, log-uniformly: a head forgets in
# 1 / (dt |A|) steps, 160 to 100,000 here, so that the state carries as
# much of a layer's output as the skip term and what it summed over a
# whole stream is still in it.
WEIGHTS = dict(embed_std=0.02, in_std=0.02, qkv_std=0.09, out_std=0.05,
               final_norm=8.0, step_size=(0.01, 0.1),
               decay=(2.0 ** -10, 2.0 ** -4))


def layer_pattern(conf: dict) -> tuple:
    """(leading dense layers, period): five Mamba-2 layers, an attention
    layer, four Mamba-2 layers."""
    return 0, 10


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model."""
    return {k: config[k] for k in MODEL_KEYS}


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return GraniteHybridConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        layer_types=tuple(sizes["layer_types"]),
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["shared_intermediate_size"],
        mamba_heads=sizes["mamba_n_heads"],
        mamba_head_dim=sizes["mamba_d_head"],
        d_state=sizes["mamba_d_state"], d_conv=sizes["mamba_d_conv"],
        chunk=sizes["mamba_chunk_size"],
        embedding_multiplier=float(sizes["embedding_multiplier"]),
        residual_multiplier=float(sizes["residual_multiplier"]),
        attention_multiplier=float(sizes["attention_multiplier"]),
        logits_scaling=float(sizes["logits_scaling"]),
        max_positions=sizes["max_position_embeddings"],
        norm_eps=float(sizes["rms_norm_eps"]), dtype=dtype, **overrides)


class _Seeded:
    """The program's module with the family's initialiser (WEIGHTS) as
    its `init`."""

    def __init__(self, cfg):
        from ray_tpu.models.granite_hybrid import GraniteHybridModel

        self.cfg, self.module = cfg, GraniteHybridModel(cfg)

    def init(self, key, tokens):
        from ray_tpu.models.granite_hybrid import init_params

        return init_params(self.cfg, key, **WEIGHTS)

    def apply(self, params, tokens):
        return self.module.apply(params, tokens)


def model(cfg):
    return _Seeded(cfg)


def loss(logits, targets):
    from ray_tpu.models.llama import cross_entropy_loss

    return cross_entropy_loss(logits, targets)


def check_file(conf: dict) -> None:
    cfg = program_config(sizes(conf))
    if conf["position_embedding_type"] != "nope":
        raise ValueError("the family has no position term "
                         "(position_embedding_type \"nope\"; rope_theta is "
                         "kept as published and unread)")
    if cfg.head_dim != HEAD_DIM or \
            cfg.head_dim * cfg.n_heads != conf["hidden_size"]:
        raise ValueError(
            f"heads of {cfg.head_dim}: the family runs heads of {HEAD_DIM} "
            "that make up the hidden size, two KV heads to a kernel's head "
            "of 128")
    if cfg.n_kv_heads % 2 or cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("KV heads pair up, and whole groups of query heads "
                         "share a KV head")
    if cfg.d_inner != conf["mamba_expand"] * conf["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    if conf["mamba_n_groups"] != 1:
        raise ValueError("models/granite_hybrid.py runs one group of B and "
                         "C (mamba_n_groups 1)")
    if len(conf["layer_types"]) != conf["num_hidden_layers"] or \
            set(conf["layer_types"]) - {"mamba", "attention"}:
        raise ValueError("layer_types names a 'mamba' or an 'attention' "
                         "mixer for each of num_hidden_layers layers")
    if not conf["tie_word_embeddings"]:
        raise ValueError("the family ties its head to the embedding")
    if conf["num_local_experts"] or conf["num_experts_per_tok"]:
        raise ValueError("routed experts: this family is the dense one "
                         "(num_local_experts 0); the feed-forward is the "
                         "shared one, of shared_intermediate_size")
    if conf["shared_intermediate_size"] != conf["intermediate_size"]:
        raise ValueError("with no routed experts intermediate_size is the "
                         "shared feed-forward's")
    if conf["attention_bias"] or conf["mamba_proj_bias"] or \
            not conf["mamba_conv_bias"]:
        raise ValueError("no biases but the conv's")
    if conf["hidden_act"] != "silu" or \
            conf["normalization_function"] != "rmsnorm":
        raise ValueError("silu and RMSNorm")
