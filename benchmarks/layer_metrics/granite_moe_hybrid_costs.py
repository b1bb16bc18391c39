"""What the `granite_moe_hybrid` family's algorithm needs, from shapes alone:
the yardstick of `gmh_decode_roofline`, `gmh_gmm_roofline` and
`gmh_prefill_mfu`.  (Not a reader: no metric has this name.
`harness/kernel_costs.py` counts a dense decoder and is not edited.)
`sizes` is `families/granite_moe_hybrid.sizes(config)`: the published keys,
`router_experts` (the experts routed over) and `experts_held`.

Counted is what the equations ask of THIS chip: the mixers, the shared
expert, the router over all of its columns and the head for every token;
of the routed part the (row, expert) pairs whose expert is HELD here (the
program's own count: a pair held elsewhere is another chip's work), and of
the experts' bytes those a step's live rows TOUCHED, not those held.  The
Mamba-2 state is read and written once a live row and layer.  Not counted:
the second bfloat16 term of an activation, the chunked form's masked
matrices, the gather of a pair's row that lies in no held group.
"""

from __future__ import annotations

HEAD_DIM = 128
STATE_BYTES = 4         # the state S is float32, as the configuration says
FLOAT32_BYTES = 4


def _dims(sizes: dict) -> tuple:
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    return (sizes["hidden_size"], inner,
            inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"],
            sizes["mamba_n_heads"], sizes["num_attention_heads"],
            sizes["num_key_value_heads"])


def held(sizes: dict) -> int:
    """Experts of a layer that lie on this chip."""
    share = sizes.get("experts_held")
    return int(share[1]) if share else int(sizes["num_local_experts"])


def layers(sizes: dict) -> dict:
    kinds = list(sizes["layer_types"])
    return {"mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def matmul_params(sizes: dict) -> dict:
    """Parameters multiplied for every token that part sees (norms, conv,
    dt_bias, A and D are not matrix products)."""
    d, inner, conv, heads, hq, hkv = _dims(sizes)
    return {"mamba": d * (inner + conv + heads) + inner * d,
            "attention": d * (hq + 2 * hkv) * HEAD_DIM + hq * HEAD_DIM * d,
            "shared": 3 * d * sizes["shared_intermediate_size"],
            "expert": 3 * d * sizes["intermediate_size"],
            "router": d * sizes["router_experts"]}


def float32_parameters(sizes: dict) -> int:
    """What the program keeps in float32: the router's matrix, every
    norm's scale (two a layer, the gated norm of a Mamba-2 layer, the last
    one) and a Mamba-2 head's dt_bias, A and D."""
    d, inner, _conv, heads, _hq, _hkv = _dims(sizes)
    n = layers(sizes)
    return ((n["mamba"] + n["attention"])
            * (matmul_params(sizes)["router"] + 2 * d)
            + n["mamba"] * (3 * heads + inner) + d)


def parameters(sizes: dict) -> int:
    """Every parameter held here: the multiplied ones (the held experts
    among them), the conv, the per-head vectors, the norms, the tied
    embedding."""
    d, inner, conv, heads, _hq, _hkv = _dims(sizes)
    mm, n = matmul_params(sizes), layers(sizes)
    small = {"mamba": sizes["mamba_d_conv"] * conv + conv + 3 * heads
             + inner + 2 * d, "attention": 2 * d}
    feed = mm["shared"] + mm["router"] + held(sizes) * mm["expert"]
    return sum(n[k] * (mm[k] + small[k] + feed) for k in n) \
        + sizes["vocab_size"] * d + d


def weight_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    return parameters(sizes) * dtype_bytes \
        + float32_parameters(sizes) * (FLOAT32_BYTES - dtype_bytes)


def expert_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """One expert's three matrices (from the two widths alone: a
    configuration file read as it lies will do, as the kernel sweep's)."""
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"] * dtype_bytes


def other_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """Every weight that is no routed expert's: read once a step whatever
    the routing (the tied embedding IS the head)."""
    n = layers(sizes)
    return weight_bytes(sizes, dtype_bytes) - (n["mamba"] + n["attention"]) \
        * held(sizes) * expert_bytes(sizes, dtype_bytes)


def state_bytes_per_sequence(sizes: dict, dtype_bytes: int = 2) -> int:
    """The recurrent state of one sequence: S (heads x head x state,
    float32) and the conv window (d_conv - 1 inputs of x, B and C) of
    every Mamba-2 layer."""
    _d, inner, conv, _heads, _hq, _hkv = _dims(sizes)
    return layers(sizes)["mamba"] * (
        inner * sizes["mamba_d_state"] * STATE_BYTES
        + (sizes["mamba_d_conv"] - 1) * conv * dtype_bytes)


def kv_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    return (layers(sizes)["attention"] * 2 * sizes["num_key_value_heads"]
            * HEAD_DIM * dtype_bytes)


def recurrence_flops_per_token(sizes: dict) -> float:
    """One Mamba-2 layer's recurrence for one token: 6 a state element."""
    return 6.0 * sizes["mamba_n_heads"] * sizes["mamba_d_head"] \
        * sizes["mamba_d_state"]


def token_flops(sizes: dict) -> float:
    """Operations one token REQUIRES of everything but the routed experts:
    its mixer, the shared expert and the router in every layer, the conv
    and the recurrence of a Mamba-2 layer."""
    _d, _inner, conv, _heads, _hq, _hkv = _dims(sizes)
    mm, n = matmul_params(sizes), layers(sizes)
    both = mm["shared"] + mm["router"]
    return 2.0 * sum(n[k] * (mm[k] + both) for k in n) \
        + n["mamba"] * (recurrence_flops_per_token(sizes)
                        + 2.0 * sizes["mamba_d_conv"] * conv)


def pair_flops(sizes: dict) -> float:
    """One (row, expert) pair: two operations a parameter of the expert."""
    return 2.0 * 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def decode_step_cost(sizes: dict, live_slots: float, resident_tokens: float,
                     experts_touched: float, pairs_held: float,
                     dtype_bytes: int = 2) -> tuple:
    """ONE whole decode step -> (operations, bytes): every weight that is
    no routed expert's once, the head included; each held expert a live
    row chose once (`experts_touched`: summed over the layers) and each
    held pair's operations (`pairs_held`: likewise); S and the conv window
    of each live slot read and written once; each resident token's K and V
    once."""
    d, _inner, _conv, _heads, hq, _hkv = _dims(sizes)
    flops = live_slots * (token_flops(sizes)
                          + 2.0 * sizes["vocab_size"] * d) \
        + pairs_held * pair_flops(sizes) \
        + layers(sizes)["attention"] * 4.0 * hq * HEAD_DIM * resident_tokens
    nbytes = (other_bytes(sizes, dtype_bytes)
              + experts_touched * expert_bytes(sizes, dtype_bytes)
              + 2.0 * live_slots * state_bytes_per_sequence(sizes,
                                                            dtype_bytes)
              + resident_tokens * kv_bytes_per_token(sizes, dtype_bytes))
    return flops, nbytes


def prefill_flops(sizes: dict, prompt_tokens: int) -> float:
    """Operations a prompt of n tokens REQUIRES before its first token,
    the routed experts apart (`pair_flops` a held pair, by the program's
    count): mixers, shared experts and routers at all n positions, the
    causal half of attention at heads of 128, the conv and the
    recurrence, the head at the last token only."""
    n = int(prompt_tokens)
    d, _inner, _conv, _heads, hq, _hkv = _dims(sizes)
    return (token_flops(sizes) * n
            + layers(sizes)["attention"] * 4.0 * hq * HEAD_DIM
            * n * (n + 1) / 2.0
            + 2.0 * sizes["vocab_size"] * d)


def grouped_product_cost(sizes: dict, pairs_held: float,
                         experts_touched: float,
                         dtype_bytes: int = 2) -> tuple:
    """The grouped products over `pairs_held` (row, expert) pairs that
    touch `experts_touched` held experts -> (operations, bytes): two
    operations a pair and parameter of an expert; each touched expert's
    matrices once, each pair's row in and out of both products in the
    stream's float32."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    nbytes = experts_touched * expert_bytes(sizes, dtype_bytes) \
        + pairs_held * 4.0 * (d + 2 * f + f + d)
    return pairs_held * pair_flops(sizes), nbytes


def kernel_order(sizes: dict) -> list:
    """The custom calls of one decode step in the order the program makes
    them: an attention layer's paged call, then every layer's two grouped
    products ("paged", "w13", "w2")."""
    out = []
    for kind in sizes["layer_types"]:
        if kind == "attention":
            out.append("paged")
        out += ["w13", "w2"]
    return out


def split_kernel_calls(calls: list, sizes: dict) -> dict:
    """Durations of the decode program's custom calls, in the order they
    ran -> {"grouped": [...], "paged": [...]}.  A trace's `kernel_ns`
    keeps no names, but a step's calls come in `kernel_order`, over and
    over; a slot that opens inside a step only shifts where the order
    starts.  Of the shifts the one is taken under which calls of one kind
    last most alike (the sum over the calls of how far each lies from the
    median of its kind: W1|W3 streams twice the bytes of W2, the paged call
    reads one layer's K and V)."""
    order = kernel_order(sizes)
    if not calls:
        return {"grouped": [], "paged": []}

    def split(shift):
        kinds: dict = {"w13": [], "w2": [], "paged": []}
        for i, d in enumerate(calls):
            kinds[order[(i + shift) % len(order)]].append(d)
        return kinds

    def spread(kinds):
        total = 0.0
        for values in kinds.values():
            mid = sorted(values)[len(values) // 2] if values else 0.0
            total += sum(abs(v - mid) for v in values)
        return total

    best = min((split(s) for s in range(len(order))), key=spread)
    return {"grouped": best["w13"] + best["w2"], "paged": best["paged"]}
