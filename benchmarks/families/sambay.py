"""The `sambay` family: Phi-4-mini-flash-reasoning's decoder-hybrid-decoder
(Mamba-1 and sliding-window differential attention, one full-attention
layer whose K/V every later cross-attention layer reads, Gated Memory
Units), run through `ray_tpu/models/sambay.py` and held to
`reference/sambay.py`.  `families/dense_decoder.py` lists the names a
family gives.

A configuration file holds the published `config.json` keys and, under
`assumed`, the sizes that file does not give (`{"value", "why"}` each):
the state-space layers' (`mamba_d_state`, `mamba_d_conv`, `mamba_expand`,
`mamba_dt_rank`).  `sizes` is both together.
"""

from __future__ import annotations

import os

from benchmarks.harness import loader
from benchmarks.reference import sambay as reference  # noqa: F401

# A checkout whose program has no such model (any commit before PR 28, with
# these benchmark files laid over it) is told so here, as the cell is loaded
# and before any cluster or replica is started: the command exits 1 at once.
if not os.path.isfile(os.path.join(loader.REPO_ROOT, "ray_tpu", "models",
                                   "sambay.py")):
    raise loader.BenchmarkError(
        "this checkout's program cannot run the `sambay` family: it has no "
        "ray_tpu/models/sambay.py (the engine serves it since PR 28)")

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "sliding_window",
              "vocab_size", "layer_norm_eps", "max_position_embeddings",
              "tie_word_embeddings", "mb_per_layer", "torch_dtype")
ASSUMED_SIZES = ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                 "mamba_dt_rank")
# Nothing may be cut: the whole model fits one chip, and fewer layers would
# change how many layers read the one cache and the one memory.
REDUCIBLE: set = set()
HEAD_DIM = 64


def sizes(config: dict) -> dict:
    """The part of a configuration file that describes the model."""
    out = {k: config[k] for k in MODEL_KEYS}
    out.update({k: config["assumed"][k]["value"] for k in ASSUMED_SIZES})
    return out


def program_config(sizes: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models.sambay import SambaYConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    cfg = SambaYConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], window=sizes["sliding_window"],
        d_state=sizes["mamba_d_state"], d_conv=sizes["mamba_d_conv"],
        expand=sizes["mamba_expand"],
        norm_eps=float(sizes["layer_norm_eps"]), dtype=dtype, **overrides)
    if cfg.dt_rank != sizes["mamba_dt_rank"]:
        raise ValueError(f"models/sambay.py derives the step size's rank as "
                         f"ceil(hidden_size / 16) = {cfg.dt_rank}; this "
                         f"configuration says {sizes['mamba_dt_rank']}")
    return cfg


class _Seeded:
    """The program's module with the family's published-style initialiser
    (`models/sambay.init_params`: normal 0.02, the projections into the
    residual stream scaled by 1 / sqrt(2 L)) as its `init`."""

    def __init__(self, cfg):
        from ray_tpu.models.sambay import SambaYModel

        self.cfg, self.module = cfg, SambaYModel(cfg)

    def init(self, key, tokens):
        from ray_tpu.models.sambay import init_params

        return init_params(self.cfg, key)

    def apply(self, params, tokens):
        return self.module.apply(params, tokens)


def model(cfg):
    return _Seeded(cfg)


def loss(logits, targets):
    from ray_tpu.models.llama import cross_entropy_loss

    return cross_entropy_loss(logits, targets)


def check_file(conf: dict) -> None:
    cfg = program_config(sizes(conf))
    if cfg.head_dim != HEAD_DIM:
        raise ValueError(
            f"heads of {cfg.head_dim}: the family pairs heads of {HEAD_DIM} "
            "into the kernels' heads of 128")
    if cfg.n_heads % 4 or cfg.n_kv_heads % 2 or \
            cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("query heads pair up, KV heads pair up, and whole "
                         "query pairs share a KV pair")
    if cfg.n_layers % 4 or conf["mb_per_layer"] != 2:
        raise ValueError("the layer pattern alternates a state-space or "
                         "memory layer with an attention layer in both "
                         "halves: a multiple of four layers")
    if not conf["tie_word_embeddings"] or conf.get("mlp_bias") or \
            conf.get("lm_head_bias"):
        raise ValueError("the family ties its head and has no biases in "
                         "the feed-forward or the head")
