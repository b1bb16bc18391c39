"""What the `granite_hybrid` family's algorithm needs, from shapes alone:
the yardstick of `ssm_decode_roofline` and `ssm_prefill_mfu`.  (Not a
reader: no metric has this name.  `harness/kernel_costs.py` counts a dense
decoder and is not edited.)  `sizes` is
`families/granite_hybrid.sizes(config)`.

Counted is what the equations ask for.  What the program spends beyond
them is the program's and is not: the chunked matrix form's masked matrix
of decays (the recurrence itself is 6 operations a state element and
token), and the zero half of a query head padded from 64 to the kernels'
128.
"""

from __future__ import annotations

HEAD_DIM = 64
STATE_BYTES = 4         # the state S is float32, as the configuration says


def _dims(sizes: dict) -> tuple:
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    return (sizes["hidden_size"], sizes["shared_intermediate_size"], inner,
            inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"],
            sizes["mamba_n_heads"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"])


def layers(sizes: dict) -> dict:
    kinds = list(sizes["layer_types"])
    return {"mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def matmul_params(sizes: dict) -> dict:
    """Parameters multiplied for every token a layer of each kind sees
    (norms, conv, dt_bias, A and D are not matrix products)."""
    d, ff, inner, conv, heads, hq, hkv = _dims(sizes)
    mlp = 3 * d * ff
    return {"mamba": mlp + d * (inner + conv + heads) + inner * d,
            "attention": mlp + d * (hq + 2 * hkv) * HEAD_DIM
            + hq * HEAD_DIM * d}


def parameters(sizes: dict) -> int:
    """Every parameter: the multiplied ones, the conv, the per-head
    vectors, the norms, the tied embedding."""
    d, _ff, inner, conv, heads, _hq, _hkv = _dims(sizes)
    mm, n = matmul_params(sizes), layers(sizes)
    small = {"mamba": sizes["mamba_d_conv"] * conv + conv + 3 * heads
             + inner + 2 * d, "attention": 2 * d}
    return sum(n[k] * (mm[k] + small[k]) for k in n) \
        + sizes["vocab_size"] * d + d


def weight_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    return parameters(sizes) * dtype_bytes


def state_bytes_per_sequence(sizes: dict, dtype_bytes: int = 2) -> int:
    """The recurrent state of one sequence: S (heads x head x state,
    float32) and the conv window (d_conv - 1 inputs of x, B and C) of
    every Mamba-2 layer."""
    _d, _ff, inner, conv, _heads, _hq, _hkv = _dims(sizes)
    return layers(sizes)["mamba"] * (
        inner * sizes["mamba_d_state"] * STATE_BYTES
        + (sizes["mamba_d_conv"] - 1) * conv * dtype_bytes)


def kv_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    """K and V of the attention layers: the only state that grows."""
    return (layers(sizes)["attention"] * 2 * sizes["num_key_value_heads"]
            * HEAD_DIM * dtype_bytes)


def recurrence_flops_per_token(sizes: dict) -> float:
    """One Mamba-2 layer's recurrence for one token: decay, drive and
    add, then the product with C and its sum: 6 a state element."""
    return 6.0 * sizes["mamba_n_heads"] * sizes["mamba_d_head"] \
        * sizes["mamba_d_state"]


def decode_step_cost(sizes: dict, live_slots: float,
                     resident_tokens: float, dtype_bytes: int = 2) -> tuple:
    """ONE whole decode step -> (operations, bytes): every weight read
    once, the head included (the embedding is tied: its table IS the
    head); S and the conv window of each live slot read and written once;
    each resident token's K and V of every attention layer read once."""
    d, _ff, _inner, conv, _heads, hq, _hkv = _dims(sizes)
    mm, n = matmul_params(sizes), layers(sizes)
    per_token = 2.0 * (sum(n[k] * mm[k] for k in n)
                       + sizes["vocab_size"] * d) \
        + n["mamba"] * (recurrence_flops_per_token(sizes)
                        + 2.0 * sizes["mamba_d_conv"] * conv)
    flops = per_token * live_slots \
        + n["attention"] * 4.0 * hq * HEAD_DIM * resident_tokens
    nbytes = (weight_bytes(sizes, dtype_bytes)
              + 2.0 * live_slots * state_bytes_per_sequence(sizes,
                                                            dtype_bytes)
              + resident_tokens * kv_bytes_per_token(sizes, dtype_bytes))
    return flops, nbytes


def prefill_flops(sizes: dict, prompt_tokens: int) -> float:
    """Operations a prompt of n tokens REQUIRES before its first token:
    projections and feed-forward at all n positions (two a multiplied
    parameter), the causal half of attention in the attention layers at
    heads of 64, the conv and the recurrence's own six operations a state
    element, the head at the last token only."""
    n = int(prompt_tokens)
    d, _ff, _inner, conv, _heads, hq, _hkv = _dims(sizes)
    mm, count = matmul_params(sizes), layers(sizes)
    total = 2.0 * sum(count[k] * mm[k] for k in count) * n
    total += count["attention"] * 4.0 * hq * HEAD_DIM * n * (n + 1) / 2.0
    total += count["mamba"] * n * (recurrence_flops_per_token(sizes)
                                   + 2.0 * sizes["mamba_d_conv"] * conv)
    return total + 2.0 * sizes["vocab_size"] * d
