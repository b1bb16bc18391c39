"""What the `sambay` family's algorithm needs, from shapes alone: the
yardstick of `shared_kv_attn_roofline` and `prefill_mfu`.  (Not a reader:
no metric has this name.  `harness/kernel_costs.py` counts a dense decoder
and is not edited.)  `sizes` is `families/sambay.sizes(config)`.

Differential attention over heads of 64 with values of 128: a query head
scores a key with 64 multiply-adds and weighs 128 value elements with it,
2 * (64 + 128) operations a query head and key.  The program pads queries
to 128 and spends 2 * (128 + 128); the padding is the program's, not the
algorithm's, and is not counted.
"""

from __future__ import annotations

HEAD_DIM = 64


def _dims(sizes: dict) -> tuple:
    d = sizes["hidden_size"]
    return (d, sizes["intermediate_size"], sizes["mamba_expand"] * d,
            sizes["mamba_d_state"], sizes["mamba_dt_rank"],
            sizes["num_attention_heads"], sizes["num_key_value_heads"])


def layer_kinds(sizes: dict) -> list:
    L = sizes["num_hidden_layers"]
    half = L // 2
    return ["window" if i % 2 else "mamba" if i <= half else
            "cross" if i % 2 else "gmu" for i in range(half + 1)] + \
        ["full"] + ["cross" if i % 2 else "gmu" for i in range(half + 2, L)]


def matmul_params(sizes: dict) -> dict:
    """Parameters multiplied for every token a layer of each kind sees
    (norms, conv, A, D and the lambda vectors are not matrix products)."""
    d, ff, E, N, R, hq, hkv = _dims(sizes)
    mlp = 3 * d * ff
    attn = d * (hq + 2 * hkv) * HEAD_DIM + hq * HEAD_DIM * d
    return {"mamba": mlp + 2 * d * E + E * (R + 2 * N) + R * E + E * d,
            "window": mlp + attn, "full": mlp + attn,
            "gmu": mlp + 2 * d * E,
            "cross": mlp + 2 * hq * HEAD_DIM * d}


def kv_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    """K and V of ONE layer: the only attention cache that grows."""
    return 2 * sizes["num_key_value_heads"] * HEAD_DIM * dtype_bytes


def shared_kv_decode_cost(sizes: dict, batch: int, resident_tokens: float,
                          dtype_bytes: int = 2) -> tuple:
    """One decode-attention call of one layer over the shared pool:
    every resident token's K and V read once, the queries read and the
    outputs (128 a query head) written."""
    hq = sizes["num_attention_heads"]
    flops = 2.0 * (HEAD_DIM + 2 * HEAD_DIM) * hq * resident_tokens
    nbytes = (resident_tokens * kv_bytes_per_token(sizes, dtype_bytes)
              + batch * hq * 3 * HEAD_DIM * dtype_bytes)
    return flops, nbytes


def pool_readers(sizes: dict) -> int:
    """Layers that read the shared pool in a decode step."""
    kinds = layer_kinds(sizes)
    return kinds.count("full") + kinds[kinds.index("full"):].count("cross")


def ring_bytes_per_step(sizes: dict, batch: int, dtype_bytes: int = 2) -> int:
    """What the window layers read in a decode step: a whole ring a
    sequence and layer (in this program by plain XLA operations, not by a
    kernel: no custom call's time holds them)."""
    return (layer_kinds(sizes).count("window") * batch
            * sizes["sliding_window"] * kv_bytes_per_token(sizes, dtype_bytes))


def prefill_flops(sizes: dict, prompt_tokens: int) -> float:
    """Operations a prompt of n tokens REQUIRES before its first token:
    the self-decoder (every layer up to the full-attention one) at all n
    positions, the cross-decoder and the head at the last one only.  Two
    operations a multiplied parameter; causal attention over the keys a
    query sees (at most `sliding_window` in a window layer); the conv and
    the scan's six operations a state element (the exponential not
    counted).  A prefill that ran every layer at every position would do
    about 1.7 times this."""
    n = int(prompt_tokens)
    d, _ff, E, N, _R, hq, _hkv = _dims(sizes)
    w = sizes["sliding_window"]
    per_key = 2.0 * (HEAD_DIM + 2 * HEAD_DIM) * hq
    keys_full = n * (n + 1) / 2.0
    m = min(n, w)
    keys_window = m * (m + 1) / 2.0 + (n - m) * w
    params = matmul_params(sizes)
    kinds = layer_kinds(sizes)
    full_at = kinds.index("full")
    total = 0.0
    for kind in kinds[: full_at + 1]:
        total += 2.0 * params[kind] * n
        if kind == "mamba":
            total += n * (2.0 * sizes["mamba_d_conv"] * E + 6.0 * E * N)
        elif kind == "window":
            total += per_key * keys_window
        else:
            total += per_key * keys_full
    for kind in kinds[full_at + 1:]:
        total += 2.0 * params[kind]
        if kind == "cross":
            total += per_key * n
    return total + 2.0 * sizes["vocab_size"] * d
