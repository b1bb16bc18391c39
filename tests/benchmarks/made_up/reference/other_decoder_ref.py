"""The made-up family's plain reference: the benchmark's dense decoder,
read under this family's key names.  It imports nothing of the program."""

from __future__ import annotations

from benchmarks.reference import dense_decoder

ROUNDINGS = dense_decoder.ROUNDINGS


def _sizes(sizes: dict) -> dict:
    return {"num_hidden_layers": sizes["n_layer"],
            "num_attention_heads": sizes["n_head"],
            "num_key_value_heads": sizes["n_kv_head"],
            "rope_theta": sizes["rope_base"],
            "rms_norm_eps": sizes["norm_eps"],
            "tie_word_embeddings": sizes["tied_head"]}


def logits(params, sizes, tokens, rows=None, **kw):
    return dense_decoder.logits(params, _sizes(sizes), tokens, rows, **kw)


def mean_token_loss(params, sizes, inputs, targets):
    return dense_decoder.mean_token_loss(params, _sizes(sizes), inputs,
                                         targets)
