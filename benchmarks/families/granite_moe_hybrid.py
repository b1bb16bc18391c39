"""The `granite_moe_hybrid` family: Granite-4.0-H's decoder WITH its routed
experts (`granitemoehybrid` as Granite-4.0-H-Small has it: Mamba-2 layers,
a few grouped-query attention layers of heads of 128 with no position term,
and after every mixer a shared feed-forward beside 72 routed experts, ten a
token, the gates a softmax over the ten chosen logits), run through
`ray_tpu/models/granite_hybrid.py` and held to
`reference/granite_moe_hybrid.py`.  `families/granite_hybrid.py` is the
dense member's; `families/dense_decoder.py` lists the names a family gives.

A configuration file holds the published `config.json` keys verbatim.  One
that holds a chip's SHARE of each layer's experts (`num_local_experts` under
`published.num_local_experts`, `deployment.experts_held` = [first, count])
keeps the router whole: every one of the published experts is routed over,
the held ones are computed.
"""

from __future__ import annotations

import os

from benchmarks.harness import loader

reference = loader.beside(__file__, "reference", "granite_moe_hybrid.py")

# A checkout whose program cannot run the family is told so here, as the
# cell is loaded and before any cluster or replica is started: the command
# exits 1 at once.  Every checkout since PR 36 HAS models/granite_hybrid.py
# (the dense member), so the probe is for the routed configuration's own
# field, which PR 52 brought.
_PROGRAM = os.path.join(loader.REPO_ROOT, "ray_tpu", "models",
                        "granite_hybrid.py")
try:
    with open(_PROGRAM) as _f:
        _routed = "experts_held" in _f.read()
except OSError:
    _routed = False
if not _routed:
    raise loader.BenchmarkError(
        "this checkout's program cannot run the `granite_moe_hybrid` family: "
        "ray_tpu/models/granite_hybrid.py has no routed experts "
        "(`GraniteHybridConfig.experts_held`; the engine serves them since "
        "PR 52)")

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
REDUCIBLE = {"num_hidden_layers", "num_local_experts"}
EXPERTS_KEY = "num_local_experts"
HEAD_DIM = 128

# The seeded weights of a run (`assumed.weights` of the configuration file
# says why each): standard deviations of the embedding, of the matrices
# that read the stream (q, k and v apart), of the mixers' matrices that
# write into it, of the shared expert's and of every routed expert's, of
# the router's rows; the final norm's scale; and the ranges each Mamba-2
# head's step size dt and decay |A| are drawn from (as the dense member's).
WEIGHTS = dict(embed_std=0.006, in_std=0.014, qkv_std=0.074, out_std=0.035,
               ffn_out_std=0.08, expert_out_std=0.07, router_std=0.02,
               final_norm=37.7, step_size=(0.01, 0.1),
               decay=(2.0 ** -10, 2.0 ** -4))


def layer_pattern(conf: dict) -> tuple:
    """(leading dense layers, period): five Mamba-2 layers, an attention
    layer, four Mamba-2 layers."""
    return 0, 10


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model, and
    beside the published keys the two that a share adds: `router_experts`
    (the experts routed over: the published count where the file holds
    fewer) and `experts_held` ([first, count], None: all)."""
    out = {k: config[k] for k in MODEL_KEYS}
    out["router_experts"] = config.get("published", {}).get(
        EXPERTS_KEY, config[EXPERTS_KEY])
    held = config["deployment"].get("experts_held") \
        if isinstance(config.get("deployment"), dict) else None
    out["experts_held"] = None if held is None else [int(v) for v in held]
    return out


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    held = sizes.get("experts_held")
    return GraniteHybridConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        layer_types=tuple(sizes["layer_types"]),
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["shared_intermediate_size"],
        n_experts=sizes.get("router_experts", sizes["num_local_experts"]),
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["intermediate_size"],
        experts_held=None if held is None else tuple(held),
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
    model_sizes = sizes(conf)
    cfg = program_config(model_sizes)
    if conf["position_embedding_type"] != "nope":
        raise ValueError("the family has no position term "
                         "(position_embedding_type \"nope\"; rope_theta is "
                         "kept as published and unread)")
    if cfg.head_dim != HEAD_DIM or \
            cfg.head_dim * cfg.n_heads != conf["hidden_size"]:
        raise ValueError(
            f"heads of {cfg.head_dim}: the family runs heads of {HEAD_DIM} "
            "that make up the hidden size, the kernels' own width (heads of "
            "64 are the `granite_hybrid` family's)")
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("whole groups of query heads share a KV head")
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
    published = conf.get("published", {})
    if set(published) - REDUCIBLE - {"layer_types"}:
        raise ValueError(
            f"`published` states {sorted(set(published) - REDUCIBLE)}: only "
            "the depth and the experts held are cut, every width is the "
            "published one")
    whole = list(published.get("layer_types", conf["layer_types"]))
    if whole[: len(conf["layer_types"])] != list(conf["layer_types"]):
        raise ValueError("layer_types is not the leading run of the "
                         "published list: a cut keeps the first layers, "
                         "whole periods of them")
    if not conf["tie_word_embeddings"]:
        raise ValueError("the family ties its head to the embedding")
    if not 0 < conf["num_experts_per_tok"] <= model_sizes["router_experts"]:
        raise ValueError("the routed member: some experts a token, and no "
                         "more than are routed over (the dense member is the "
                         "`granite_hybrid` family's)")
    held = model_sizes["experts_held"]
    cut = EXPERTS_KEY in conf["reduced"]
    if cut != (held is not None):
        raise ValueError(
            "`deployment.experts_held` = [first, count] goes with "
            f"{EXPERTS_KEY!r} in `reduced`, and only with it")
    if cut:
        first, count = held
        if count != conf[EXPERTS_KEY] or first < 0 or \
                first + count > model_sizes["router_experts"]:
            raise ValueError(
                f"experts_held {held}: a run of {conf[EXPERTS_KEY]} "
                f"({EXPERTS_KEY}) of the {model_sizes['router_experts']} "
                "experts routed over")
        if model_sizes["router_experts"] % count:
            raise ValueError("the chips that share a layer hold equal "
                             "shares of its experts")
        if conf["deployment"]["chips_sharing_a_layer"] * count != \
                model_sizes["router_experts"]:
            raise ValueError("chips_sharing_a_layer shares of "
                             f"{count} experts do not make up the "
                             f"{model_sizes['router_experts']} routed over")
    if conf["attention_bias"] or conf["mamba_proj_bias"] or \
            not conf["mamba_conv_bias"]:
        raise ValueError("no biases but the conv's")
    if conf["hidden_act"] != "silu" or \
            conf["normalization_function"] != "rmsnorm":
        raise ValueError("silu and RMSNorm")
