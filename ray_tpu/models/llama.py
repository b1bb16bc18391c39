"""Llama-family decoder, TPU-first.

The reference trains LLMs only through external torch engines (its release
gates fine-tune GPT-J/vicuna via DeepSpeed/FSDP — reference:
release/release_tests.yaml:879,:891); the model itself is not part of the
framework. Here the flagship decoder IS part of the framework: flax.linen
modules whose parameter names line up with
`ray_tpu.parallel.TRANSFORMER_RULES` so TP/FSDP shardings apply by rule,
attention goes through the Pallas flash kernel (`ray_tpu.ops`), and
sequence parallelism swaps in ring attention under `shard_map`.

Conventions: activations (batch, seq, d_model), attention internals
(batch, heads, seq, head_dim), bfloat16 params optional, f32 RMSNorm.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import (
    FLASH_LSE,
    FLASH_OUT,
    causal_over_itself,
    flash_attention,
    mha_reference,
    ring_attention,
    ulysses_attention,
)


# What a rematerialised layer keeps from its first forward under
# `remat_policy == "full"` (see `LlamaConfig.remat_policy`): the flash
# kernel's two results, q, k and v after rope and before the GQA repeat
# (the repeat is re-done from the kept KV heads) and the attention block's
# output. Each was kept because it shortened the train step on the chip
# (PERF.md section 6, PR 34: 767 -> 734 -> 709 -> 698 ms).
_KEPT_UNDER_FULL = (FLASH_OUT, FLASH_LSE, "attn_q", "attn_k", "attn_v",
                    "attn_block_out")


class PagedKVCache(NamedTuple):
    """Per-layer paged KV state for batched single-token decode.

    The KV cache is a shared pool of fixed-size pages (the vLLM block
    table idea, TPU-shaped — see ops/paged_attention.py); each sequence
    owns rows of `table`. HBM scales with resident tokens, not
    max_len x slots.
    """

    k_pool: Any    # (P, Hkv, page_size, D) — head-then-page minor layout
    v_pool: Any    # (P, Hkv, page_size, D)   (see ops/paged_attention.py)
    table: Any     # (B, NP) int32 pool indices per sequence
    length: Any    # (B,) int32 tokens already cached (= write offset)


class FreshKV:
    """In place of a cache: there are no earlier keys, and the caller wants
    each layer's K and V of the tokens it hands in (a prefill whose K/V go
    into pages). Attention is then causal attention of the prompt over
    itself, through the flash forward kernel whatever `cfg.attention`
    says of the cache-less path, and what comes back a layer is (k, v) of
    shape (B, Hkv, S, D): the prompt's length, not a cache's."""


FRESH_KV = FreshKV()


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # attention impl: "flash" (pallas), "ring" (sequence-parallel, inside
    # shard_map over axis sp), "reference" (plain jnp)
    attention: str = "flash"
    remat: bool = True
    # "full": a layer is recomputed from its input in the backward, all but
    # its attention: the flash kernel's `out` and `lse`, the q, k, v that
    # feed it and the attention block's output are kept from the first
    # forward (`_KEPT_UNDER_FULL`; 135 MB a layer at 8,192 tokens of
    # InternLM2-1.8B's widths), so the re-run forward holds norms and the
    # MLP's two input matmuls only. The kernel is the dearest thing a layer
    # can run twice (a quarter of its compute floor where the matmuls
    # around it reach 80%).
    # "dots": save matmul outputs, recompute elementwise and the kernel
    # (far less recompute per backward at more memory).
    remat_policy: str = "dots"
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


LLAMA2_7B = LlamaConfig()
LLAMA2_13B = LlamaConfig(d_model=5120, n_layers=40, n_heads=40, n_kv_heads=40,
                         d_ff=13824)
LLAMA3_8B = LlamaConfig(vocab_size=128256, d_model=4096, n_layers=32,
                        n_heads=32, n_kv_heads=8, d_ff=14336,
                        rope_theta=500000.0, max_seq_len=8192)
TINY = LlamaConfig(vocab_size=256, d_model=128, n_layers=2, n_heads=4,
                   n_kv_heads=4, d_ff=256, max_seq_len=256,
                   dtype=jnp.float32, attention="reference", remat=False)


def rope_frequencies(head_dim: int, max_len: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                       # (max_len, head_dim/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, positions):
    """x: (B, H, S, D). positions: (B, S) or (S,)."""
    if positions.ndim == 1:
        cos_p = cos[positions][None, None]
        sin_p = sin[positions][None, None]
    else:
        cos_p = cos[positions][:, None]
        sin_p = sin[positions][:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos_p - x2 * sin_p,
                           x2 * cos_p + x1 * sin_p], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                  + self.eps)
        return (norm * scale).astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None):
        cfg = self.cfg
        B, S, _ = x.shape
        Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                                  param_dtype=cfg.dtype)
        q = dense(Hq * Dh, name="q_proj")(x).reshape(B, S, Hq, Dh)
        k = dense(Hkv * Dh, name="k_proj")(x).reshape(B, S, Hkv, Dh)
        v = dense(Hkv * Dh, name="v_proj")(x).reshape(B, S, Hkv, Dh)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # (B,H,S,D)

        cos, sin = rope_frequencies(Dh, cfg.max_seq_len, cfg.rope_theta)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        if isinstance(kv_cache, PagedKVCache):
            # Batched single-token decode over the shared page pool: the
            # kernel puts this step's K/V into each sequence's current
            # page, in place, and attends over its page table (GQA
            # handled in-kernel; no head repetition, no per-slot max_len
            # cache). The pools are (P, Hkv, page, D).
            from ray_tpu.ops.paged_attention import (
                paged_decode_attention_batch)

            pc = kv_cache
            out, k_pool, v_pool = paged_decode_attention_batch(
                q[:, :, 0, :], pc.k_pool, pc.v_pool, pc.table,
                pc.length + 1, k_new=k[:, :, 0, :], v_new=v[:, :, 0, :])
            out = out[:, :, None, :].astype(cfg.dtype)
            out = out.transpose(0, 2, 1, 3).reshape(B, S, Hq * Dh)
            out = dense(cfg.d_model, name="o_proj")(out)
            return out, PagedKVCache(k_pool, v_pool, pc.table,
                                     pc.length + 1)

        if isinstance(kv_cache, FreshKV):
            # Right-padded rows: a position past a row's last token gives
            # K/V nobody attends (causal here, masked by length in decode).
            out = causal_over_itself(q, k, v)
            out = out.transpose(0, 2, 1, 3).reshape(B, S, Hq * Dh)
            return dense(cfg.d_model, name="o_proj")(out), (k, v)

        new_cache = None
        if kv_cache is None:
            q = checkpoint_name(q, "attn_q")
            k = checkpoint_name(k, "attn_k")
            v = checkpoint_name(v, "attn_v")
        else:
            # There are earlier keys: append to the cache (a decode step, a
            # suffix behind a cached prefix) and attend over all of it.
            ck, cv, cache_len = kv_cache
            k = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_len, axis=2)
            v = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_len, axis=2)
            new_cache = (k, v, cache_len + S)

        if Hkv != Hq:  # GQA: repeat kv heads
            rep = Hq // Hkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)

        if kv_cache is not None:
            # Decode attention over the cache with position masking.
            total = k.shape[2]
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) / jnp.sqrt(Dh)
            kpos = jnp.arange(total)[None, None, None, :]
            qpos = positions[:, None, :, None] if positions.ndim == 2 \
                else positions[None, None, :, None]
            s = jnp.where(kpos <= qpos, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", p,
                             v.astype(jnp.float32)).astype(cfg.dtype)
        elif cfg.attention == "flash":
            out = _flash_on_mesh(q, k, v)
        elif cfg.attention == "ring":
            out = ring_attention(q, k, v, axis="sp", causal=True)
        elif cfg.attention == "ulysses":
            out = ulysses_attention(q, k, v, axis="sp", causal=True)
        else:
            out = mha_reference(q, k, v, causal=True)

        out = out.transpose(0, 2, 1, 3).reshape(B, S, Hq * Dh)
        out = dense(cfg.d_model, name="o_proj")(out)
        if kv_cache is not None:
            return out, new_cache
        return checkpoint_name(out, "attn_block_out")


def _flash_on_mesh(q, k, v):
    """Causal flash attention, per shard when traced under a mesh.

    The compiler cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so a step traced inside a multi-device mesh
    (`shard_train_step` does that) runs the kernel on each device's own
    batch rows and heads: batch over the data axes, heads over `tp`, as
    TRANSFORMER_RULES lays the activations out."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return flash_attention(q, k, v, None, True)
    data = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    spec = P(data or None, "tp" if "tp" in mesh.axis_names else None,
             None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, None, True),
        in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                                  param_dtype=cfg.dtype)
        gate = dense(cfg.d_ff, name="gate_proj")(x)
        up = dense(cfg.d_ff, name="up_proj")(x)
        return dense(cfg.d_model, name="down_proj")(nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, name="input_norm")(x)
        if kv_cache is not None:
            attn, new_cache = Attention(cfg, name="attn")(h, positions, kv_cache)
        else:
            attn = Attention(cfg, name="attn")(h, positions)
            new_cache = None
        x = x + attn
        h = RMSNorm(cfg.rms_eps, name="post_attn_norm")(x)
        x = x + MLP(cfg, name="mlp")(h)
        if kv_cache is not None:
            return x, new_cache
        return x


class LlamaModel(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.dtype, name="embed")
        x = embed(tokens)
        layer_cls = DecoderLayer
        if cfg.remat and kv_caches is None:
            policy = (jax.checkpoint_policies.save_only_these_names(
                          *_KEPT_UNDER_FULL)
                      if cfg.remat_policy == "full" else
                      jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            layer_cls = nn.remat(DecoderLayer, policy=policy)
        new_caches = []
        if isinstance(kv_caches, FreshKV):
            kv_caches = [kv_caches] * cfg.n_layers
        for i in range(cfg.n_layers):
            layer = layer_cls(cfg, name=f"layers_{i}")
            if kv_caches is not None:
                x, c = layer(x, positions, kv_caches[i])
                new_caches.append(c)
            else:
                x = layer(x, positions)
        x = RMSNorm(cfg.rms_eps, name="norm")(x)
        # (Attention and MLP are named by their modules, `layers_<i>/attn`
        # and `/mlp`; a tied head has no module of its own.)
        with jax.named_scope("head"):
            if cfg.tie_embeddings:
                logits = embed.attend(x.astype(cfg.dtype))
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False,
                                  dtype=cfg.dtype, param_dtype=cfg.dtype,
                                  name="lm_head")(x)
            logits = logits.astype(jnp.float32)
        if kv_caches is not None:
            return logits, new_caches
        return logits


def init_kv_caches(cfg: LlamaConfig, batch: int, max_len: int):
    Dh = cfg.head_dim
    return [(jnp.zeros((batch, cfg.n_kv_heads, max_len, Dh), cfg.dtype),
             jnp.zeros((batch, cfg.n_kv_heads, max_len, Dh), cfg.dtype), 0)
            for _ in range(cfg.n_layers)]


def cross_entropy_loss(logits, targets, mask=None):
    # logsumexp form instead of materializing log_softmax: the full
    # (B,S,V) f32 normalized array never hits HBM — lse reduces
    # immediately (~2% MFU on v5e at d_model 2048/vocab 32k).
    l32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(l32, axis=-1)
    picked = jnp.take_along_axis(l32, targets[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def count_flops_per_token(cfg: LlamaConfig) -> float:
    """Approximate forward+backward FLOPs per token (6·N + attention)."""
    n = (cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
         + cfg.n_layers * (
             cfg.d_model * cfg.head_dim * (cfg.n_heads + 2 * cfg.n_kv_heads)
             + cfg.n_heads * cfg.head_dim * cfg.d_model
             + 3 * cfg.d_model * cfg.d_ff))
    return 6.0 * n
