"""What the algorithm needs: operations and bytes of each kernel and of a
training token, from shapes alone.  The yardstick; the program's own
`count_flops_per_token` counts the embedding gather as a matmul and leaves
out attention's sequence term, so it is not used."""

from __future__ import annotations

import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip.  An unknown device is an error."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device_kind {device_kind!r}; "
                       f"add it to {_PEAKS_FILE} with its source")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound gives it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")


def matmul_params(sizes: dict) -> int:
    """Parameters that are multiplied for every token: the layers'
    projections and the output head.  The embedding is a gather."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    layer = d * (hq + 2 * hkv) * dh + hq * dh * d + 3 * d * ff
    return sizes["num_hidden_layers"] * layer + sizes["vocab_size"] * d


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward + backward operations one token requires: 6 per multiplied
    parameter, plus causal attention (QK^T and PV over seq/2 keys on
    average: 2 * seq * heads * head_dim forward a layer, three times that
    with the backward).  Rematerialised work is not counted."""
    attn = (6.0 * seq_len * sizes["num_attention_heads"] * sizes["head_dim"]
            * sizes["num_hidden_layers"])
    return 6.0 * matmul_params(sizes) + attn


def flash_forward_cost(sizes: dict, batch: int, seq_len: int,
                       dtype_bytes: int = 2) -> tuple:
    """One causal flash-forward call over (batch, heads, seq, head_dim):
    operations of the lower triangle only, and q, k, v read and o written
    once (the float32 log-sum-exp row included)."""
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    flops = 4.0 * batch * hq * seq_len * seq_len * dh / 2.0
    nbytes = (batch * seq_len * dh * (2 * hq + 2 * hkv) * dtype_bytes
              + batch * hq * seq_len * 4)
    return flops, nbytes


def paged_decode_cost(sizes: dict, batch: int, resident_tokens: float,
                      dtype_bytes: int = 2) -> tuple:
    """One paged decode-attention call (one layer, one step) over
    `resident_tokens` cached tokens in all: every cached K and V is read
    once, q read and the output written; 4 operations a cached token, head
    and head_dim element."""
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    flops = 4.0 * resident_tokens * hq * dh
    nbytes = (2.0 * resident_tokens * hkv * dh * dtype_bytes
              + 2.0 * batch * hq * dh * dtype_bytes)
    return flops, nbytes


def weight_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """All parameters as served: the multiplied ones, the embedding table
    (untied), and the norm scales."""
    d = sizes["hidden_size"]
    embed = 0 if sizes["tie_word_embeddings"] else sizes["vocab_size"] * d
    norms = (2 * sizes["num_hidden_layers"] + 1) * d
    return (matmul_params(sizes) + embed + norms) * dtype_bytes


def kv_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"]
            * sizes["head_dim"] * dtype_bytes)
