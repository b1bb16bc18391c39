"""The `minicpm_sala` family: MiniCPM-SALA's decoder (block-sparse attention
layers that choose their pages through a cache of compressed keys, among
lightning linear-attention layers with a constant-size state; a gated
feed-forward after every mixer; muP scalings), run through
`ray_tpu/models/minicpm_sala.py` and held to `reference/minicpm_sala.py`.
`families/dense_decoder.py` lists the names a family gives.

A configuration file holds the published `config.json` keys verbatim and,
as `sparse_config`, the sizes of the selection the catalog's copy lacks
(`assumed.sparse_config` says where each is from).
"""

from __future__ import annotations

import os

from benchmarks.harness import loader

# A checkout whose program has no such model (any commit before PR 49, with
# these benchmark files laid over it) is told so here, as the cell is loaded
# and before any cluster or replica is started: the command exits 1 at once.
if not os.path.isfile(os.path.join(loader.REPO_ROOT, "ray_tpu", "models",
                                   "minicpm_sala.py")):
    raise loader.BenchmarkError(
        "this checkout's program cannot run the `minicpm_sala` family: it "
        "has no ray_tpu/models/minicpm_sala.py (the engine serves it since "
        "PR 49)")

reference = loader.beside(__file__, "reference", "minicpm_sala.py")

MODEL_KEYS = (
    "attention_bias", "attn_use_rope", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "lightning_head_dim", "lightning_nh",
    "lightning_nkv", "lightning_scale", "lightning_use_rope",
    "max_position_embeddings", "mixer_types", "num_attention_heads",
    "num_hidden_layers", "num_key_value_heads", "qk_norm", "rms_norm_eps",
    "vocab_size", "rope_theta", "scale_emb", "scale_depth", "dim_model_base",
    "tie_word_embeddings", "use_output_gate", "use_output_norm",
    "attn_use_output_gate", "torch_dtype", "sparse_config")
REDUCIBLE = {"num_hidden_layers"}
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# The seeded weights of a run (`assumed.weights` of the configuration file
# says why each): standard deviations, by what the matrix does, and the
# scale of the sparse layers' q-norm, which sharpens their softmax.  A
# sparse layer's W_o is the stage's first layer's, then its last's: a block
# exchanged at a near-tie in the FIRST moves everything the seven layers
# after it read, and float32 itself exchanges one about once a request
# (README-minicpm-sala.md, "The refusal on seed 411897652").
WEIGHTS = dict(embed_std=0.08, in_std=0.02, ffn_out_std=0.02,
               sparse_out_std=(0.0025, 0.04), lightning_out_std=0.04,
               head_std=0.32, query_scale=4.0)


def layer_pattern(conf: dict) -> tuple:
    """(leading dense layers, period): a sparse layer in four."""
    return 0, 4


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model, and the
    published depth (it stands under the root of the residual's scale)."""
    return dict({k: config[k] for k in MODEL_KEYS},
                published_layers=config.get("published", {}).get(
                    "num_hidden_layers", config["num_hidden_layers"]))


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCpmSalaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    s = sizes["sparse_config"]
    return MiniCpmSalaConfig(**{**dict(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        mixer_types=tuple(sizes["mixer_types"]),
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        d_ff=sizes["intermediate_size"], scale_emb=float(sizes["scale_emb"]),
        scale_depth=float(sizes["scale_depth"]),
        depth_layers=sizes["published_layers"],
        dim_model_base=sizes["dim_model_base"],
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        kernel_size=s["kernel_size"], kernel_stride=s["kernel_stride"],
        block_size=s["block_size"], topk=s["topk"],
        window_size=s["window_size"], init_blocks=s["init_blocks"],
        dense_len=s["dense_len"], dtype=dtype), **overrides})


class _Seeded:
    """The program's module with the family's initialiser (WEIGHTS) as
    its `init`."""

    def __init__(self, cfg):
        from ray_tpu.models.minicpm_sala import MiniCpmSalaModel

        self.cfg, self.module = cfg, MiniCpmSalaModel(cfg)

    def init(self, key, tokens):
        from ray_tpu.models.minicpm_sala import init_params

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
    if set(published) - REDUCIBLE - {"mixer_types"}:
        raise ValueError("`published` states the depth and the whole list "
                         "of mixers; every width is the published one")
    kept, whole = conf["mixer_types"], published.get("mixer_types",
                                                     conf["mixer_types"])
    if len(kept) != conf["num_hidden_layers"] or \
            len(whole) != published.get("num_hidden_layers", len(kept)):
        raise ValueError("mixer_types names a mixer for each of "
                         "num_hidden_layers layers, kept and published")
    if not any(whole[i: i + len(kept)] == kept
               for i in range(len(whole) - len(kept) + 1)):
        raise ValueError("the kept mixer_types are no contiguous slice of "
                         "the published list")
    if kept.count(SPARSE) * 4 != len(kept):
        raise ValueError(f"one {SPARSE!r} layer in four, as published "
                         "(8 of 32)")
    if conf["lightning_nh"] != conf["num_attention_heads"] or \
            conf["lightning_nkv"] != conf["lightning_nh"] or \
            conf["lightning_head_dim"] != conf["head_dim"]:
        raise ValueError("a lightning layer's q, k and v are all heads of "
                         "the sparse layers' size and number")
    if conf["head_dim"] != 128:
        raise ValueError("the kernels and the cost functions here are for "
                         "heads of 128")
    if conf["attn_use_rope"] or not conf["lightning_use_rope"] or \
            not conf["qk_norm"] or conf["lightning_scale"] != "1/sqrt(d)":
        raise ValueError("sparse layers without a position term, lightning "
                         "layers rotated; q and k normed; o scaled by "
                         "1/sqrt(d)")
    if not (conf["use_output_gate"] and conf["use_output_norm"]
            and conf["attn_use_output_gate"]):
        raise ValueError("both kinds of layer gate their output; the "
                         "lightning layers norm it")
    if conf["attention_bias"] or conf["tie_word_embeddings"] or \
            conf["hidden_act"] != "silu":
        raise ValueError("no biases, a head of its own, SiLU gates")
    engine = conf.get("serve", {}).get("engine", {})
    if engine and engine["page_size"] != conf["sparse_config"]["block_size"]:
        raise ValueError("a page is a block of the selection")
