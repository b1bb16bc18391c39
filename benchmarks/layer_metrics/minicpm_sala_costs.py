"""What the `minicpm_sala` family's algorithm needs, from shapes alone: the
yardstick of `sala_decode_roofline`, `sala_sparse_attn_roofline`,
`sala_prefill_mfu` and `sparse_pages_read_share`.  (Not a reader: no metric
has this name.)  `sizes` is `families/minicpm_sala.sizes(config)`.

Counted is what the equations ask for, whatever implements them: a sparse
query needs the pages it KEPT (the program's own count, `sparse_pages_read`:
a page of one K/V head of one layer), the compressed keys it can see, and
nothing of a page it dropped; a lightning layer needs its state read and
written once a token whatever the sequence's length; a prompt past
`dense_len` needs, at each position, the blocks that position keeps (all
of them until it holds more than `kept_blocks`), not the masked-out rest.
Not counted: the second bfloat16 term of an activation in a decode step,
padding up to a bucket, a page's dead tokens.
"""

from __future__ import annotations

FLOAT32_BYTES = 4
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layers(sizes: dict) -> dict:
    kinds = sizes["mixer_types"]
    return {"all": len(kinds), "sparse": kinds.count(SPARSE),
            "lightning": kinds.count(LIGHTNING)}


def kept_blocks(sizes: dict) -> int:
    s = sizes["sparse_config"]
    return s["init_blocks"] + s["window_size"] // s["block_size"] + s["topk"]


def matmul_params(sizes: dict) -> dict:
    """Parameters multiplied for every token that part sees."""
    d, D = sizes["hidden_size"], sizes["head_dim"]
    HD = sizes["num_attention_heads"] * D
    return {"sparse": d * (2 * HD + 2 * sizes["num_key_value_heads"] * D)
            + HD * d,
            "lightning": d * 4 * HD + HD * d,
            "ffn": 3 * d * sizes["intermediate_size"],
            "head": d * sizes["vocab_size"]}


def parameters(sizes: dict) -> int:
    """Every parameter: the multiplied ones, the norms (two a layer over
    the stream, q's and k's over a head, a lightning layer's output norm,
    the last one), the embedding."""
    d, D = sizes["hidden_size"], sizes["head_dim"]
    mm, n = matmul_params(sizes), layers(sizes)
    return (n["sparse"] * (mm["sparse"] + 2 * D)
            + n["lightning"] * (mm["lightning"] + 3 * D)
            + n["all"] * (mm["ffn"] + 2 * d) + d + mm["head"]
            + sizes["vocab_size"] * d)


def step_weight_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """Every weight a decode step reads: all but the embedding, of which
    only the rows looked up are, counted as nothing."""
    return (parameters(sizes) - sizes["vocab_size"] * sizes["hidden_size"]) \
        * dtype_bytes


def page_bytes(sizes: dict, dtype_bytes: int = 2) -> int:
    """K and V of one page of one K/V head of one layer."""
    return 2 * sizes["sparse_config"]["block_size"] * sizes["head_dim"] \
        * dtype_bytes


def state_bytes_per_slot(sizes: dict) -> int:
    """A sequence's lightning state over all its layers, float32."""
    return layers(sizes)["lightning"] * sizes["num_attention_heads"] \
        * sizes["head_dim"] ** 2 * FLOAT32_BYTES


def token_flops(sizes: dict) -> float:
    """Operations one token REQUIRES of the layers' matrix products."""
    mm, n = matmul_params(sizes), layers(sizes)
    return 2.0 * (n["sparse"] * mm["sparse"] + n["lightning"] * mm["lightning"]
                  + n["all"] * mm["ffn"])


def page_flops(sizes: dict) -> float:
    """One query token over one kept page of one K/V head: the heads that
    share it, scores and values, two operations each."""
    group = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    return 4.0 * group * sizes["sparse_config"]["block_size"] \
        * sizes["head_dim"]


def recurrence_flops(sizes: dict) -> float:
    """One token through one lightning layer's recurrence: the state's
    decay-and-update and its read-out, two operations a value each."""
    return 4.0 * sizes["num_attention_heads"] * sizes["head_dim"] ** 2


def selection_flops(sizes: dict, keys: float) -> float:
    """One K/V head's queries against `keys` compressed keys."""
    group = sizes["num_attention_heads"] // sizes["num_key_value_heads"]
    return 2.0 * group * keys * sizes["head_dim"]


def decode_step_cost(sizes: dict, live_rows: float, pages_read: float,
                     keys_read: float, tables_written: float,
                     dtype_bytes: int = 2) -> tuple:
    """ONE whole decode step -> (operations, bytes): every weight but the
    embedding once; each (layer, K/V head) page a live row KEPT once
    (`pages_read`) and the page each of its tables writes
    (`tables_written`: live rows x sparse layers x K/V heads); the
    compressed keys the sparse rows can see (`keys_read`); each live row's
    lightning state read and written."""
    n = layers(sizes)
    flops = live_rows * (token_flops(sizes)
                         + 2.0 * matmul_params(sizes)["head"]
                         + n["lightning"] * recurrence_flops(sizes)) \
        + pages_read * page_flops(sizes) + selection_flops(sizes, keys_read)
    nbytes = (step_weight_bytes(sizes, dtype_bytes)
              + (pages_read + tables_written) * page_bytes(sizes, dtype_bytes)
              + keys_read * sizes["head_dim"] * dtype_bytes
              + 2.0 * live_rows * state_bytes_per_slot(sizes))
    return flops, nbytes


def sparse_kernel_cost(sizes: dict, rows: int, pages_read: float,
                       tables_written: float, dtype_bytes: int = 2) -> tuple:
    """The paged kernel's calls of a step, or of any number of steps,
    together -> (operations, bytes): each kept page's K and V once, the
    page a table writes, and for each of `rows` (slot, step, layer)
    queries the float32 query in and output out."""
    H, D = sizes["num_attention_heads"], sizes["head_dim"]
    return (pages_read * page_flops(sizes),
            (pages_read + tables_written) * page_bytes(sizes, dtype_bytes)
            + 2.0 * rows * H * D * FLOAT32_BYTES)


def kept_pairs(sizes: dict, prompt_tokens: int) -> float:
    """(query, kept block) pairs of one K/V head of one sparse layer over
    a prompt of n tokens: every block up to the query's own under
    `dense_len`; past it, at most `kept_blocks` a query."""
    s = sizes["sparse_config"]
    n, b = int(prompt_tokens), s["block_size"]
    cap = kept_blocks(sizes) if n > s["dense_len"] else n
    return float(sum(min(t // b + 1, cap) for t in range(n)))


def prefill_flops(sizes: dict, prompt_tokens: int) -> float:
    """Operations a prompt of n tokens REQUIRES before its first token:
    the products at all n positions; in a sparse layer each position over
    the blocks it keeps and, past `dense_len`, its queries against the
    compressed keys it can see (n^2 / (2 stride) of them a K/V head); the
    recurrence a token and lightning layer; the head at the last token."""
    n = int(prompt_tokens)
    s, nl = sizes["sparse_config"], layers(sizes)
    hkv = sizes["num_key_value_heads"]
    keys = n * n / (2.0 * s["kernel_stride"]) if n > s["dense_len"] else 0.0
    return (token_flops(sizes) * n
            + nl["sparse"] * hkv * (kept_pairs(sizes, n) * page_flops(sizes)
                                    + selection_flops(sizes, keys))
            + nl["lightning"] * recurrence_flops(sizes) * n
            + 2.0 * matmul_params(sizes)["head"])
