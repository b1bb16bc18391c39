"""What the `lfm2_moe` family's algorithm needs, from shapes alone: the
yardstick of `moe_decode_roofline`, `moe_prefill_mfu` and
`moe_gmm_roofline`.  (Not a reader: no metric has this name.
`harness/kernel_costs.py` counts a dense decoder and is not edited.)
`sizes` is `families/lfm2_moe.sizes(config)`.

Counted is what the equations ask for, whatever implements them: a token
needs its four experts and no other, so a decode step needs each expert
that one of its live rows CHOSE once (`experts_touched`, the router's own
count) and none of the rest; a program that streams every expert spends
bytes the step does not need.  Not counted either: the second bfloat16
term of an activation, and the zero half of a query head padded from 64
to the kernels' 128.
"""

from __future__ import annotations

HEAD_DIM = 64
FLOAT32_BYTES = 4


def layers(sizes: dict) -> dict:
    kinds = list(sizes["layer_types"])
    dense = sizes["num_dense_layers"]
    return {"conv": kinds.count("conv"),
            "full_attention": kinds.count("full_attention"),
            "dense": dense, "routed": len(kinds) - dense}


def matmul_params(sizes: dict) -> dict:
    """Parameters multiplied for every token that part sees (norms and
    the conv's taps are not matrix products)."""
    d = sizes["hidden_size"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return {"conv": 3 * d * d + d * d,
            "full_attention": d * (hq + 2 * hkv) * HEAD_DIM
            + hq * HEAD_DIM * d,
            "dense": 3 * d * sizes["intermediate_size"],
            "expert": 3 * d * sizes["moe_intermediate_size"],
            "router": d * sizes["num_experts"]}


def parameters(sizes: dict) -> int:
    """Every parameter: the multiplied ones, the conv's taps, the norms
    (two a layer, two a head-normed attention layer, the last one), the
    selection bias, the tied embedding."""
    d, E = sizes["hidden_size"], sizes["num_experts"]
    mm, n = matmul_params(sizes), layers(sizes)
    return (n["conv"] * (mm["conv"] + sizes["conv_L_cache"] * d)
            + n["full_attention"] * (mm["full_attention"] + 2 * HEAD_DIM)
            + n["dense"] * mm["dense"]
            + n["routed"] * (E * mm["expert"] + mm["router"] + E)
            + 2 * d * len(sizes["layer_types"])
            + sizes["vocab_size"] * d + d)


def expert_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """One expert's three matrices."""
    return matmul_params(sizes)["expert"] * dtype_bytes


def float32_parameters(sizes: dict) -> int:
    """What the program keeps in float32: the router's matrix and bias,
    and every norm's scale."""
    d, E = sizes["hidden_size"], sizes["num_experts"]
    n = layers(sizes)
    return (n["routed"] * (matmul_params(sizes)["router"] + E)
            + 2 * d * len(sizes["layer_types"]) + d
            + n["full_attention"] * 2 * HEAD_DIM)


def weight_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    return parameters(sizes) * dtype_bytes \
        + float32_parameters(sizes) * (FLOAT32_BYTES - dtype_bytes)


def other_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """Every weight that is no expert's: read once a step whatever the
    routing (the tied embedding IS the head)."""
    return weight_bytes(sizes, dtype_bytes) - layers(sizes)["routed"] \
        * sizes["num_experts"] * expert_bytes(sizes, dtype_bytes)


def kv_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    return (layers(sizes)["full_attention"] * 2
            * sizes["num_key_value_heads"] * HEAD_DIM * dtype_bytes)


def conv_bytes_per_sequence(sizes: dict) -> int:
    """The windows of one sequence: the last L - 1 columns of B * X of
    every conv layer, float32 as the configuration keeps them."""
    return layers(sizes)["conv"] * (sizes["conv_L_cache"] - 1) \
        * sizes["hidden_size"] * FLOAT32_BYTES


def token_flops(sizes: dict) -> float:
    """Operations one token REQUIRES of the layers' matrix products: its
    operator, its feed-forward (dense, or the router and the experts it
    chose: `num_experts_per_tok`, not the experts held), the conv's taps."""
    mm, n = matmul_params(sizes), layers(sizes)
    routed = mm["router"] + sizes["num_experts_per_tok"] * mm["expert"]
    return 2.0 * (n["conv"] * mm["conv"]
                  + n["full_attention"] * mm["full_attention"]
                  + n["dense"] * mm["dense"] + n["routed"] * routed) \
        + n["conv"] * 2.0 * sizes["conv_L_cache"] * sizes["hidden_size"]


def decode_step_cost(sizes: dict, live_slots: float, resident_tokens: float,
                     experts_touched: float, dtype_bytes: int = 2) -> tuple:
    """ONE whole decode step -> (operations, bytes): every weight that is
    no expert's once, the head included; each expert a live row chose
    once (`experts_touched`: summed over the routed layers); each
    resident token's K and V of every attention layer once; each live
    slot's conv windows once."""
    hq = sizes["num_attention_heads"]
    n = layers(sizes)
    flops = live_slots * (token_flops(sizes) + 2.0 * sizes["vocab_size"]
                          * sizes["hidden_size"]) \
        + n["full_attention"] * 4.0 * hq * HEAD_DIM * resident_tokens
    nbytes = (other_bytes(sizes, dtype_bytes)
              + experts_touched * expert_bytes(sizes, dtype_bytes)
              + resident_tokens * kv_bytes_per_token(sizes, dtype_bytes)
              + live_slots * conv_bytes_per_sequence(sizes))
    return flops, nbytes


def prefill_flops(sizes: dict, prompt_tokens: int) -> float:
    """Operations a prompt of n tokens REQUIRES before its first token:
    operators and feed-forwards at all n positions (four experts a token),
    the causal half of attention at heads of 64, the head at the last
    token only."""
    n = int(prompt_tokens)
    hq = sizes["num_attention_heads"]
    return (token_flops(sizes) * n
            + layers(sizes)["full_attention"] * 4.0 * hq * HEAD_DIM
            * n * (n + 1) / 2.0
            + 2.0 * sizes["vocab_size"] * sizes["hidden_size"])


def grouped_product_cost(sizes: dict, rows: float,
                         experts_touched: float,
                         dtype_bytes: int = 2) -> tuple:
    """The two grouped products of ONE routed layer over `rows` (row,
    expert) pairs that touch `experts_touched` experts -> (operations,
    bytes): two operations a pair and parameter of an expert; each
    touched expert's matrices once, each pair's row in and out of both
    products in the stream's float32."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    flops = 2.0 * rows * matmul_params(sizes)["expert"]
    nbytes = experts_touched * expert_bytes(sizes, dtype_bytes) \
        + rows * 4.0 * (d + 2 * f + f + d)
    return flops, nbytes


def paged_decode_cost(sizes: dict, batch: int, resident_tokens: float,
                      dtype_bytes: int = 2) -> tuple:
    """One paged decode-attention call (one layer, one step) over
    `resident_tokens` cached tokens in all, as
    `kernel_costs.paged_decode_cost` counts it, at this family's heads
    of 64."""
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    flops = 4.0 * resident_tokens * hq * HEAD_DIM
    nbytes = (2.0 * resident_tokens * hkv * HEAD_DIM * dtype_bytes
              + 2.0 * batch * hq * HEAD_DIM * dtype_bytes)
    return flops, nbytes


def kernel_order(sizes: dict) -> list:
    """The custom calls of one decode step in the order the program makes
    them: an attention layer's paged-attention call, then a routed layer's
    two grouped products ("paged", "grouped")."""
    out = []
    for i, kind in enumerate(sizes["layer_types"]):
        if kind == "full_attention":
            out.append("paged")
        if i >= sizes["num_dense_layers"]:
            out += ["grouped", "grouped"]
    return out


def split_kernel_calls(calls: list, sizes: dict) -> dict:
    """Durations of the decode program's custom calls, in the order they
    ran -> {"grouped": [...], "paged": [...]}.  A trace's `kernel_ns`
    keeps no names, but a step's calls come in `kernel_order`, over and
    over; a slot that opens inside a step only shifts where the order
    starts, and of the shifts the one that gives the paged-attention
    calls the least time in all is taken (one of them reads two layers of
    K and V, a grouped product several experts: a tenth of its time)."""
    order = kernel_order(sizes)
    if "paged" not in order or "grouped" not in order:
        return {"grouped": list(calls) if "grouped" in order else [],
                "paged": list(calls) if "paged" in order else []}
    def split(shift):
        out = {"grouped": [], "paged": []}
        for i, d in enumerate(calls):
            out[order[(i + shift) % len(order)]].append(d)
        return out

    return min((split(s) for s in range(len(order))),
               key=lambda out: sum(out["paged"]))
