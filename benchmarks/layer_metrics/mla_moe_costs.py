"""What the `mla_moe` family's algorithm needs, from shapes alone: the
yardstick of `mla_decode_roofline`, `mla_paged_attn_roofline`,
`mla_gmm_roofline`, `mla_prefill_mfu` and `latent_bytes_share`.  (Not a reader: no metric has
this name.)  `sizes` is `families/mla_moe.sizes(config)`.

Counted is what the equations ask for, whatever implements them: a token
needs its six routed experts and the shared ones, so a decode step needs
each expert that one of its live rows CHOSE once (`experts_touched`, the
router's own count); a cached token is 576 values a layer (the latent and
the rotated key: 1,152 bytes in bfloat16), read once a layer and step
however the program lays a page out (it pads a row to 640).  Not counted
either: the second bfloat16 term of an activation, and the zeros that pad v
to the flash kernel's one width in a prefill.
"""

from __future__ import annotations

FLOAT32_BYTES = 4


def layers(sizes: dict) -> dict:
    dense = sizes["first_k_dense_replace"]
    return {"all": sizes["num_hidden_layers"], "dense": dense,
            "routed": sizes["num_hidden_layers"] - dense}


def latent_values(sizes: dict) -> int:
    """Values a token caches a layer: the latent and the rotated key."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def matmul_params(sizes: dict) -> dict:
    """Parameters multiplied for every token that part sees."""
    d, H = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    f = sizes["moe_intermediate_size"]
    return {"attention": d * H * qk + d * latent_values(sizes)
            + sizes["kv_lora_rank"] * H
            * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
            + H * sizes["v_head_dim"] * d,
            "dense": 3 * d * sizes["intermediate_size"],
            "expert": 3 * d * f,
            "shared": 3 * d * f * sizes["n_shared_experts"],
            "router": d * sizes["n_routed_experts"],
            "head": d * sizes["vocab_size"]}


def parameters(sizes: dict) -> int:
    """Every parameter: the multiplied ones, the norms (two a layer, the
    latent's, the last one), the selection bias, the embedding."""
    d, E = sizes["hidden_size"], sizes["n_routed_experts"]
    mm, n = matmul_params(sizes), layers(sizes)
    return (n["all"] * (mm["attention"] + sizes["kv_lora_rank"] + 2 * d)
            + n["dense"] * mm["dense"]
            + n["routed"] * (E * mm["expert"] + mm["shared"] + mm["router"]
                             + E)
            + d + mm["head"] + sizes["vocab_size"] * d)


def expert_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """One routed expert's three matrices."""
    return matmul_params(sizes)["expert"] * dtype_bytes


def float32_parameters(sizes: dict) -> int:
    """What the program keeps in float32: the router's matrix and bias,
    and every norm's scale."""
    d, E = sizes["hidden_size"], sizes["n_routed_experts"]
    n = layers(sizes)
    return (n["routed"] * (matmul_params(sizes)["router"] + E)
            + n["all"] * (2 * d + sizes["kv_lora_rank"]) + d)


def weight_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    return parameters(sizes) * dtype_bytes \
        + float32_parameters(sizes) * (FLOAT32_BYTES - dtype_bytes)


def other_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """Every weight a decode step reads whatever the routing: all that is
    no routed expert's (the shared experts and the head among them), but
    of the embedding only the rows looked up, which are counted as
    nothing."""
    return weight_bytes(sizes, dtype_bytes) \
        - sizes["vocab_size"] * sizes["hidden_size"] * dtype_bytes \
        - layers(sizes)["routed"] * sizes["n_routed_experts"] \
        * expert_bytes(sizes, dtype_bytes)


def latent_bytes_per_token(sizes: dict, dtype_bytes: int = 2) -> int:
    """Over all layers: 7 x 1,152 in the cell's configuration."""
    return layers(sizes)["all"] * latent_values(sizes) * dtype_bytes


def token_flops(sizes: dict) -> float:
    """Operations one token REQUIRES of the layers' matrix products: its
    attention's projections, its feed-forward (dense, or the router, the
    experts it chose (`num_experts_per_tok`, not the experts held) and the
    shared ones: eight experts' worth at six and two)."""
    mm, n = matmul_params(sizes), layers(sizes)
    routed = mm["router"] + sizes["num_experts_per_tok"] * mm["expert"] \
        + mm["shared"]
    return 2.0 * (n["all"] * mm["attention"] + n["dense"] * mm["dense"]
                  + n["routed"] * routed)


def latent_attention_flops(sizes: dict, cached_tokens: float) -> float:
    """One layer's decode attention over `cached_tokens` latents, absorbed:
    a head's score over the 576 cached values and its output over the 512
    of the latent, two operations each."""
    return 2.0 * cached_tokens * sizes["num_attention_heads"] \
        * (latent_values(sizes) + sizes["kv_lora_rank"])


def decode_step_cost(sizes: dict, live_slots: float, resident_tokens: float,
                     experts_touched: float, dtype_bytes: int = 2) -> tuple:
    """ONE whole decode step -> (operations, bytes): every weight that is
    no routed expert's once, the head included; each expert a live row
    chose once (`experts_touched`: summed over the routed layers); each
    resident token's latent row of every layer once."""
    n = layers(sizes)
    flops = live_slots * (token_flops(sizes)
                          + 2.0 * matmul_params(sizes)["head"]) \
        + n["all"] * latent_attention_flops(sizes, resident_tokens)
    nbytes = (other_bytes(sizes, dtype_bytes)
              + experts_touched * expert_bytes(sizes, dtype_bytes)
              + resident_tokens * latent_bytes_per_token(sizes, dtype_bytes))
    return flops, nbytes


def prefill_flops(sizes: dict, prompt_tokens: int) -> float:
    """Operations a prompt of n tokens REQUIRES before its first token:
    projections and feed-forwards at all n positions (eight experts' worth
    a token a routed layer), the causal half of attention decompressed
    (scores over 192, values over 128 a head), the head at the last token
    only."""
    n = int(prompt_tokens)
    per_pair = 2.0 * sizes["num_attention_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"])
    return (token_flops(sizes) * n
            + layers(sizes)["all"] * per_pair * n * (n + 1) / 2.0
            + 2.0 * matmul_params(sizes)["head"])


def latent_kernel_cost(sizes: dict, batch: int, resident_tokens: float,
                       dtype_bytes: int = 2) -> tuple:
    """One call of the latent paged-attention kernel (one layer, one step)
    over `resident_tokens` cached tokens in all -> (operations, bytes):
    each token's 576 values read ONCE (key and value both), the queries in
    and the outputs out in float32."""
    H = sizes["num_attention_heads"]
    nbytes = resident_tokens * latent_values(sizes) * dtype_bytes \
        + batch * H * FLOAT32_BYTES * (latent_values(sizes)
                                       + sizes["kv_lora_rank"])
    return latent_attention_flops(sizes, resident_tokens), nbytes


def grouped_product_cost(sizes: dict, rows: float, experts_touched: float,
                         dtype_bytes: int = 2) -> tuple:
    """The grouped products of the routed layers over `rows` (row, expert)
    pairs that touch `experts_touched` experts in all -> (operations,
    bytes), as `lfm2_moe_costs.grouped_product_cost` counts them: two
    operations a pair and parameter of an expert; each touched expert's
    three matrices once; each pair's row in and out of both products in the
    stream's float32 (2,048 in, 2 x 1,408 out; 1,408 in, 2,048 out)."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    flops = 2.0 * rows * matmul_params(sizes)["expert"]
    nbytes = experts_touched * expert_bytes(sizes, dtype_bytes) \
        + rows * FLOAT32_BYTES * (d + 2 * f + f + d)
    return flops, nbytes


def kernel_order(sizes: dict) -> list:
    """The custom calls of one decode step in the order the program makes
    them: a layer's latent-attention call, then a routed layer's two
    grouped products ("latent", "grouped")."""
    out = []
    for i in range(sizes["num_hidden_layers"]):
        out.append("latent")
        if i >= sizes["first_k_dense_replace"]:
            out += ["grouped", "grouped"]
    return out


def split_kernel_calls(calls: list, sizes: dict) -> dict:
    """Durations of the decode program's custom calls, in the order they
    ran -> {"grouped": [...], "latent": [...]}.  A trace's `kernel_ns`
    keeps no names, but it holds whole runs of the program from their first
    call (`trace_reduce` keeps a run by its start and a call by its run;
    only the last run may be cut short), and a step's calls come in
    `kernel_order`, over and over."""
    order = kernel_order(sizes)
    out = {"grouped": [], "latent": []}
    for i, d in enumerate(calls):
        out[order[i % len(order)]].append(d)
    return out
