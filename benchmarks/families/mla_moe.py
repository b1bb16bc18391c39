"""The `mla_moe` family: the DeepSeek-V3 decoder as Kimi-VL-A3B-Instruct's
language model has it (latent attention over a latent cache: 16 heads, a
latent of 512 and one rotated key of 64 a token, interleaved partial
rotation; a leading dense layer, then 64 routed experts, six a token and
none dropped, beside two shared experts), run through
`ray_tpu/models/mla_moe.py` and held to `reference/mla_moe.py`.
`families/dense_decoder.py` lists the names a family gives.

A configuration file holds the published `config.json` keys verbatim.
"""

from __future__ import annotations

import os

from benchmarks.harness import loader
from benchmarks.reference import mla_moe as reference  # noqa: F401

# A checkout whose program has no such model (any commit before PR 45, with
# these benchmark files laid over it) is told so here, as the cell is loaded
# and before any cluster or replica is started: the command exits 1 at once.
if not os.path.isfile(os.path.join(loader.REPO_ROOT, "ray_tpu", "models",
                                   "mla_moe.py")):
    raise loader.BenchmarkError(
        "this checkout's program cannot run the `mla_moe` family: it has "
        "no ray_tpu/models/mla_moe.py (the engine serves it since PR 45)")

MODEL_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_attention_heads", "n_shared_experts", "n_routed_experts",
    "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "topk_method",
    "n_group", "topk_group", "num_experts_per_tok", "moe_layer_freq",
    "first_k_dense_replace", "norm_topk_prob", "scoring_func",
    "num_key_value_heads", "hidden_act", "rms_norm_eps", "rope_theta",
    "rope_scaling", "attention_bias", "tie_word_embeddings", "torch_dtype")
REDUCIBLE = {"num_hidden_layers"}
DEPTH_KEY = "num_hidden_layers"
EXPERTS_KEY = "n_routed_experts"

# The seeded weights of a run (`assumed.weights` of the configuration file
# says why each): standard deviations, by what the matrix does.
WEIGHTS = dict(embed_std=1.0, in_std=0.02, q_std=0.04, kv_a_std=0.01,
               kv_b_std=0.044, out_std=0.002, ffn_out_std=0.02,
               expert_out_std=0.04, shared_out_std=0.02, router_std=0.02,
               bias_std=0.03, head_std=0.02)


def layer_pattern(conf: dict) -> tuple:
    """(leading dense layers, period): `first_k_dense_replace` dense
    layers, then a routed layer every `moe_layer_freq`."""
    return conf["first_k_dense_replace"], conf["moe_layer_freq"]


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model."""
    return {k: config[k] for k in MODEL_KEYS}


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.mla_moe import MlaMoeConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return MlaMoeConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_dense_layers=sizes["first_k_dense_replace"],
        n_heads=sizes["num_attention_heads"],
        d_ff=sizes["intermediate_size"],
        d_expert=sizes["moe_intermediate_size"],
        n_experts=sizes["n_routed_experts"],
        top_k=sizes["num_experts_per_tok"],
        n_shared=sizes["n_shared_experts"],
        kv_rank=sizes["kv_lora_rank"], d_nope=sizes["qk_nope_head_dim"],
        d_rope=sizes["qk_rope_head_dim"], d_v=sizes["v_head_dim"],
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        routed_scaling=float(sizes["routed_scaling_factor"]),
        dtype=dtype, **overrides)


class _Seeded:
    """The program's module with the family's initialiser (WEIGHTS) as
    its `init`."""

    def __init__(self, cfg):
        from ray_tpu.models.mla_moe import MlaMoeModel

        self.cfg, self.module = cfg, MlaMoeModel(cfg)

    def init(self, key, tokens):
        from ray_tpu.models.mla_moe import init_params

        return init_params(self.cfg, key, **WEIGHTS)

    def apply(self, params, tokens):
        return self.module.apply(params, tokens)


def model(cfg):
    return _Seeded(cfg)


def loss(logits, targets):
    from ray_tpu.models.llama import cross_entropy_loss

    return cross_entropy_loss(logits, targets)


def check_file(conf: dict) -> None:
    program_config(sizes(conf))
    published = conf.get("published", {})
    if set(published) - REDUCIBLE:
        raise ValueError(
            f"`published` states {sorted(set(published) - REDUCIBLE)}: only "
            "the depth is cut, every width is the published one")
    if conf["q_lora_rank"] is not None:
        raise ValueError("the family has no query compression "
                         "(q_lora_rank null)")
    if conf["rope_scaling"] is not None:
        raise ValueError("plain rotary embedding (rope_scaling null)")
    if conf["scoring_func"] != "sigmoid" or conf["topk_method"] != "noaux_tc" \
            or not conf["norm_topk_prob"]:
        raise ValueError("the router scores by sigmoid, chooses by score + "
                         "selection bias (noaux_tc) and renormalises the "
                         "chosen scores")
    if conf["n_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("no group-limited routing (n_group 1, topk_group 1)")
    if conf["moe_layer_freq"] != 1 or \
            not 0 < conf["first_k_dense_replace"] < conf["num_hidden_layers"]:
        raise ValueError("some leading dense layers, then every layer routed")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError("latent attention decompresses a key and a value "
                         "for every head")
    if conf["attention_bias"] or conf["tie_word_embeddings"] or \
            conf["hidden_act"] != "silu":
        raise ValueError("no biases, a head of its own, SiLU gates")
    if conf["num_experts_per_tok"] > conf["n_routed_experts"]:
        raise ValueError("more experts a token than experts")
