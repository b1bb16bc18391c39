"""The Pallas kernels alone, compiled for a described v5e at the shapes the
smoke and the dense cells run (`tests/chip_compile.py` says how; the other
families' kernels are in their own `test_chip_compile_<family>.py`)."""

import re

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from ray_tpu.ops import attention
from tests.chip_compile import (KERNEL, _compile_for_the_chip,  # noqa: F401
                                one_chip, paged_call, topo)


def _qkv(one_chip, seq, hkv=32):
    q = jax.ShapeDtypeStruct((1, 32, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, hkv, seq, 128), jnp.bfloat16,
                              sharding=one_chip)
    return q, kv, kv


def _flash(q, k, v):
    return attention.flash_attention(q, k, v, None, True)


@pytest.mark.parametrize("seq", [2048, 200],
                         ids=["seq2048", "bucket200_not_pow2"])
def test_flash_forward(one_chip, seq):
    compiled = jax.jit(_flash).lower(*_qkv(one_chip, seq)).compile()
    assert KERNEL in compiled.as_text()


def _compiled_flash_grad(q, k, v):
    def loss(q, k, v):
        return _flash(q, k, v).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()


def test_flash_forward_backward(one_chip):
    compiled = _compiled_flash_grad(*_qkv(one_chip, 2048))
    assert KERNEL in compiled.as_text()


def test_flash_backward_keeps_no_score_block_in_hbm(one_chip):
    """The gradient at the train cell's shapes (`internlm2-train-packed2k`:
    4 rows of 2048 tokens, 16 heads of 128, K and V already repeated, bf16,
    causal): the forward kernel and the backward's one, and no buffer of a
    block of scores.  The scan of einsums this replaced held
    f32[4,16,512,2048] (268 MB) three times over and its bf16 copy twice:
    537 MB of temporaries; delta and lse, as rows, are all that is left."""
    B, H, S, D, block = 4, 16, 2048, 128, 512
    x = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=one_chip)
    compiled = _compiled_flash_grad(x, x, x)
    text = compiled.as_text()
    assert text.count(KERNEL) == 2
    for scores in (f"[{B},{H},{block},{S}]", f"[{B},{H},{S},{S}]"):
        assert scores not in text
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 0.2e9 < B * H * block * S * 4


@pytest.mark.parametrize("seq, dtype", [(32768, jnp.bfloat16),
                                        (200, jnp.bfloat16),
                                        (197, jnp.float32)],
                         ids=["seq32768", "bucket200_not_pow2", "vit197_f32"])
def test_flash_backward_other_lengths(one_chip, seq, dtype):
    """The backward holds dq's float32 accumulator for the whole query
    length in VMEM (16.8 MB at 32,768 x 128, beside the output block twice:
    over the compiler's default limit, which the call raises by what it
    holds); and lengths that are one block, no multiple of the tile."""
    x = jax.ShapeDtypeStruct((1, 2, seq, 128), dtype, sharding=one_chip)
    assert _compiled_flash_grad(x, x, x).as_text().count(KERNEL) == 2


@pytest.mark.parametrize("B, pool_pages", [(32, 385), (4, 193)],
                         ids=["chat_open_b32", "docs_closed_b4"])
def test_paged_decode_batch(one_chip, B, pool_pages):
    """The two serve cells' shapes (`benchmarks/configs/mistral-7b-v0.3-
    l16*.json`: 32/8 heads of 128, pages of 64, a table of ceil((2304 + 8)
    / 64) = 37 columns, the pool with its dummy page), as a decode step
    calls the kernel: with the step's rows, the pools aliased in place."""
    compiled = paged_call(one_chip, B, 32, 8, pool_pages, 37, jnp.bfloat16,
                           writes=True)
    assert KERNEL in compiled.as_text()
    pool_bytes = pool_pages * 8 * 64 * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_state_moves_counts_what_a_compiled_program_holds():
    """The counter on a text with one of each: a layout copy in a loop's
    body, half a leaf prefetched in slices there, a whole leaf moved
    outside, and copies of other shapes, which do not count."""
    pool = "bf16[6,8,64,128]"
    text = f"""
%fused (p: {pool}) -> {pool} {{
  ROOT %copy.9 = {pool}{{3,2,1,0}} copy(%p)
}}

%body (arg: ({pool})) -> ({pool}) {{
  %copy.1 = {pool}{{3,1,2,0:T(8,128)(2,1)}} copy(%x)
  %copy.2 = bf16[32,128]{{1,0}} copy(%y)
  %slice-start.1 = (({pool}{{3,2,1,0}}), bf16[3,8,64,128]{{3,2,1,0:S(1)}}, s32[]) slice-start(%x), slice={{[0:3], [0:8], [0:64], [0:128]}}
  %f = {pool} fusion(%x), kind=kLoop, calls=%fused
}}

ENTRY %main (a: {pool}) -> {pool} {{
  %w = ({pool}) while(%t), condition=%cond, body=%body
  %copy-start.1 = ({pool}{{3,2,1,0:S(1)}}, {pool}{{3,2,1,0}}, u32[]) copy-start(%a)
  ROOT %copy.3 = f32[6,8,64,128]{{3,2,1,0}} copy(%b)
}}
"""
    state = [jax.ShapeDtypeStruct((6, 8, 64, 128), jnp.bfloat16)]
    assert chip_smoke.state_moves(text, state) == {
        "loop": {"copy": 2, "copy-start": 0, "slice-start": 1,
                 "moved": 0.5},
        "outside": {"copy": 0, "copy-start": 1, "slice-start": 0,
                    "moved": 1}}


@pytest.mark.parametrize("tokens, top_k, d, f", [
    (64, 6, 2048, 1408), (8192, 6, 2048, 1408), (1024, 4, 2048, 1536)],
    ids=["kimi_decode_64", "kimi_prompt_8192", "lfm2_prompt_1024"])
def test_routed_layer_holds_no_doubled_rows(one_chip, monkeypatch, tokens,
                                            top_k, d, f):
    """`expert_ffn` at a decode step's and at a prompt's shapes, lowered
    and compiled for the chip: two Pallas calls, and no array of 2 x pairs
    rows anywhere around them (until PR 47 each product's rows were laid
    out as (pairs, 2, k) and copied to (2 pairs, k), its result back): the
    kernel makes the two terms itself.  Nor is there an array of (pairs,
    d) that XLA gathered or gated (until PR 54 `u[order % T]`, `silu(a) *
    b` over (pairs, 2 f) and `y[argsort(order)]` stood around the calls):
    the kernel's row copies lower for the chip, the first call stores
    (pairs, f), and the second's rows lie where they belong in parts of
    128 lanes, which the gated sum reads as they lie."""
    from ray_tpu.models import lfm2_moe
    from ray_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "_interpret_mode", lambda: False)
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    lowered = jax.jit(lfm2_moe.expert_ffn).lower(
        S((tokens, d), jnp.float32), S((tokens, top_k), jnp.int32),
        S((tokens, top_k), jnp.float32), S((64, d, 2 * f), jnp.bfloat16),
        S((64, f, d), jnp.bfloat16))
    compiled = lowered.compile().as_text()
    assert compiled.count(KERNEL) == 2
    pairs = tokens * top_k
    assert not re.search(rf"tensor<{2 * pairs}x", lowered.as_text())
    assert not re.search(rf"\[{2 * pairs},", compiled)
    # the two calls' results, and nothing else as large: of the second's,
    # one reader (the gated sum); no (pairs, d) and no ungated (pairs, 2 f)
    assert re.search(rf"f32\[{pairs},{f}\]\S* custom-call", compiled)
    placed = rf"f32\[{pairs * d // 128},128\]"
    assert re.search(placed + r"\S* custom-call", compiled)
    assert len(re.findall(rf"= {placed}", compiled)) == 1
    assert not re.search(rf"f32\[{pairs},({d}|{2 * f})\]", compiled)

