"""The `lfm2_moe` family: LFM2-24B-A2B's decoder (gated short convolutions
with a few grouped-query attention layers among them, whose q and k are
normed a head before the rotary term; a dense feed-forward in the leading
layers and 64 routed experts, four a token and none dropped, in the rest),
run through `ray_tpu/models/lfm2_moe.py` and held to
`reference/lfm2_moe.py`.  `families/dense_decoder.py` lists the names a
family gives.

A configuration file holds the published `config.json` keys verbatim.
"""

from __future__ import annotations

import os

from benchmarks.harness import loader
from benchmarks.reference import lfm2_moe as reference  # noqa: F401

# A checkout whose program has no such model (any commit before PR 42, with
# these benchmark files laid over it) is told so here, as the cell is loaded
# and before any cluster or replica is started: the command exits 1 at once.
if not os.path.isfile(os.path.join(loader.REPO_ROOT, "ray_tpu", "models",
                                   "lfm2_moe.py")):
    raise loader.BenchmarkError(
        "this checkout's program cannot run the `lfm2_moe` family: it has "
        "no ray_tpu/models/lfm2_moe.py (the engine serves it since PR 42)")

MODEL_KEYS = (
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
    "layer_types", "max_position_embeddings", "moe_intermediate_size",
    "norm_eps", "norm_topk_prob", "num_attention_heads", "num_dense_layers",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rope_parameters", "routed_scaling_factor",
    "use_expert_bias", "vocab_size", "torch_dtype")
REDUCIBLE = {"num_hidden_layers", "num_dense_layers"}
DEPTH_KEY = "num_hidden_layers"
EXPERTS_KEY = "num_experts"
HEAD_DIM = 64
PERIOD = ("full_attention", "conv", "conv", "conv")

# The seeded weights of a run (`assumed.weights` of the configuration file
# says why each): standard deviations of the embedding, of the matrices
# that read the stream (q, k and v apart), of those that write into it, of
# the router's rows and of the selection bias, and the final norm's scale.
WEIGHTS = dict(embed_std=0.02, in_std=0.02, qkv_std=0.02, out_std=0.001,
               ffn_out_std=0.02, expert_out_std=0.06, router_std=0.02,
               bias_std=0.01, final_norm=1.0)


def layer_pattern(conf: dict) -> tuple:
    """(leading dense layers, period): the layers whose feed-forward is
    dense, then `full_attention, conv, conv, conv` over and over."""
    return conf["num_dense_layers"], len(PERIOD)


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model."""
    return {k: config[k] for k in MODEL_KEYS}


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return Lfm2MoeConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        layer_types=tuple(sizes["layer_types"]),
        n_dense_layers=sizes["num_dense_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"],
        d_expert=sizes["moe_intermediate_size"],
        n_experts=sizes["num_experts"], top_k=sizes["num_experts_per_tok"],
        conv_L=sizes["conv_L_cache"],
        rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
        max_positions=sizes["max_position_embeddings"],
        norm_eps=float(sizes["norm_eps"]),
        routed_scaling=float(sizes["routed_scaling_factor"]),
        dtype=dtype, **overrides)


class _Seeded:
    """The program's module with the family's initialiser (WEIGHTS) as
    its `init`."""

    def __init__(self, cfg):
        from ray_tpu.models.lfm2_moe import Lfm2MoeModel

        self.cfg, self.module = cfg, Lfm2MoeModel(cfg)

    def init(self, key, tokens):
        from ray_tpu.models.lfm2_moe import init_params

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
    if cfg.head_dim != HEAD_DIM or \
            cfg.head_dim * cfg.n_heads != conf["hidden_size"]:
        raise ValueError(
            f"heads of {cfg.head_dim}: the family runs heads of {HEAD_DIM} "
            "that make up the hidden size, two KV heads to a kernel's head "
            "of 128")
    if cfg.n_kv_heads % 2 or cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("KV heads pair up, and whole groups of query heads "
                         "share a KV head")
    kept = list(conf["layer_types"])
    if len(kept) != conf["num_hidden_layers"] or \
            set(kept) - {"conv", "full_attention"}:
        raise ValueError("layer_types names a 'conv' or a 'full_attention' "
                         "operator for each of num_hidden_layers layers")
    if not 0 < conf["num_dense_layers"] < len(kept):
        raise ValueError("the leading dense layers are some and not all")
    # A cut keeps a run of the published list that ends its dense layers
    # where the published model ends its own: the last dense layers, then
    # whole periods of the routed ones.
    published = conf.get("published", {})
    if set(published) - REDUCIBLE - {"layer_types"}:
        raise ValueError(
            f"`published` states {sorted(set(published) - REDUCIBLE)}: only "
            "the depth is cut, every width is the published one")
    whole = list(published.get("layer_types", kept))
    first = published.get("num_dense_layers", conf["num_dense_layers"]) \
        - conf["num_dense_layers"]
    if first < 0 or whole[first: first + len(kept)] != kept:
        raise ValueError(
            f"layer_types is not entries {first}-{first + len(kept) - 1} "
            "of the published list: a cut keeps the last dense layers and "
            "the layers that follow them, in the published order")
    if conf["conv_bias"]:
        raise ValueError("the short conv has no bias (conv_bias false)")
    if not conf["norm_topk_prob"] or not conf["use_expert_bias"]:
        raise ValueError("the router renormalises the chosen scores and "
                         "chooses by score + expert_bias")
    if conf["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("plain rotary embedding (rope_type default)")
    if conf["num_experts_per_tok"] > conf["num_experts"]:
        raise ValueError("more experts a token than experts")
