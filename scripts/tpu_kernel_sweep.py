"""On-chip validation + block-size sweep for the Pallas kernels.

Runs ONLY when a real accelerator answers (the test suite covers the
interpret-mode path on CPU).  Produces:
  1. correctness: flash_attention fwd/bwd vs the reference einsum path,
     and paged_decode_attention_batch vs a dense reference, on-chip;
  2. a (block_q, block_k) timing sweep of flash fwd+bwd at the bench
     shape (B2 H16 S2048 D128, causal, bf16).

Usage: python scripts/tpu_kernel_sweep.py [--sweep-only|--check-only]
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    jax.block_until_ready(x)


def reference_attention(q, k, v, causal=True):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), Sk - Sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def check_flash():
    from ray_tpu.ops.attention import flash_attention
    B, H, S, D = 2, 4, 1024, 128
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, S, D), jnp.bfloat16)
    do = jax.random.normal(kg, (B, H, S, D), jnp.bfloat16)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True)
                       .astype(jnp.float32) * do.astype(jnp.float32))

    def f_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) *
                       do.astype(jnp.float32))

    out_f = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, True))(
        q, k, v)
    out_r = reference_attention(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32) - out_r)))

    gf = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    bwd_err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(gf, gr))
    # bf16 inputs, f32 accumulation: ~1e-2 abs error is expected at S=1024.
    ok = fwd_err < 0.05 and bwd_err < 0.25
    print(json.dumps({"check": "flash_attention_onchip",
                      "fwd_max_abs_err": round(fwd_err, 5),
                      "bwd_max_abs_err": round(bwd_err, 5), "ok": ok}))
    return ok


def check_paged(Hkv: int = 8, fused_heads: bool = False):
    """Hkv == H exercises MHA; Hkv < H exercises the GQA grouped-query
    q-block path (groups > 1), which must be validated on-chip too.
    fused_heads validates the all-heads-per-page-step grid variant."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_batch
    B, H, D, page, npages_seq, pool_pages = 4, 8, 128, 16, 8, 64
    groups = H // Hkv
    lengths = np.array([37, 128, 1, 100], np.int32)
    rng = np.random.default_rng(0)
    kq = jax.random.PRNGKey(1)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    k_pool = jnp.asarray(rng.standard_normal(
        (pool_pages, Hkv, page, D)), jnp.bfloat16)     # (P, Hkv, page, D)
    v_pool = jnp.asarray(rng.standard_normal(
        (pool_pages, Hkv, page, D)), jnp.bfloat16)
    tables = np.zeros((B, npages_seq), np.int32)
    used = set()
    for b in range(B):
        for p in range((int(lengths[b]) + page - 1) // page):
            pick = rng.integers(0, pool_pages)
            while int(pick) in used:
                pick = rng.integers(0, pool_pages)
            used.add(int(pick))
            tables[b, p] = pick
    tables = jnp.asarray(tables)
    lengths_j = jnp.asarray(lengths)

    out = paged_decode_attention_batch(q, k_pool, v_pool, tables,
                                       lengths_j,
                                       fused_heads=fused_heads)

    # dense reference per sequence
    err = 0.0
    for b in range(B):
        L = int(lengths[b])
        npg = (L + page - 1) // page
        kb = np.concatenate([np.asarray(k_pool[tables[b, p]]).transpose(
            1, 0, 2) for p in range(npg)], 0)[:L]       # (L, Hkv, D)
        vb = np.concatenate([np.asarray(v_pool[tables[b, p]]).transpose(
            1, 0, 2) for p in range(npg)], 0)[:L]
        kb = np.repeat(kb, groups, axis=1)              # GQA: (L, H, D)
        vb = np.repeat(vb, groups, axis=1)
        qb = np.asarray(q[b], np.float32)                 # (H, D)
        s = np.einsum("hd,lhd->hl", qb, kb.astype(np.float32))
        s /= np.sqrt(D)
        p_ = np.exp(s - s.max(-1, keepdims=True))
        p_ /= p_.sum(-1, keepdims=True)
        ref = np.einsum("hl,lhd->hd", p_, vb.astype(np.float32))
        err = max(err, float(np.max(np.abs(
            np.asarray(out[b], np.float32) - ref))))
    ok = err < 0.05
    print(json.dumps({"check": "paged_decode_onchip", "Hkv": Hkv,
                      "groups": groups, "fused": fused_heads,
                      "max_abs_err": round(err, 5), "ok": ok}))
    return ok


def sweep_flash():
    from ray_tpu.ops.attention import flash_attention
    B, H, S, D = 2, 16, 2048, 128     # bench shape
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, S, D), jnp.bfloat16)
    do = jax.random.normal(kg, (B, H, S, D), jnp.bfloat16)

    results = []
    for bq in (256, 512, 1024):
        for bk in (256, 512, 1024):
            fn = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, None, True, block_q=bq,
                                    block_k=bk).astype(jnp.float32)
                    * do.astype(jnp.float32)),
                argnums=(0, 1, 2)))
            try:
                g = fn(q, k, v)          # compile + warm
                _sync(g[0])
                t0 = time.perf_counter()
                reps = 10
                for _ in range(reps):
                    g = fn(q, k, v)
                _sync(g[0])
                dt = (time.perf_counter() - t0) / reps * 1e3
            except Exception as e:      # noqa: BLE001 — record and move on
                results.append({"block_q": bq, "block_k": bk,
                                "error": str(e)[:120]})
                continue
            results.append({"block_q": bq, "block_k": bk,
                            "fwd_bwd_ms": round(dt, 3)})
            print(json.dumps(results[-1]), flush=True)
    good = [r for r in results if "fwd_bwd_ms" in r]
    if good:
        best = min(good, key=lambda r: r["fwd_bwd_ms"])
        print(json.dumps({"sweep": "flash_fwd_bwd_B2H16S2048D128",
                          "best": best, "all": results}))


def main():
    if jax.default_backend() != "tpu":
        sys.exit(f"tpu_kernel_sweep: an on-chip script, and the JAX backend "
                 f"here is {jax.default_backend()!r} (tests cover the CPU)")
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    ok = True
    if mode != "--sweep-only":
        ok = check_flash() and ok
        ok = check_paged(Hkv=8) and ok   # MHA
        ok = check_paged(Hkv=2) and ok   # GQA, groups=4
        ok = check_paged(Hkv=8, fused_heads=True) and ok
        ok = check_paged(Hkv=2, fused_heads=True) and ok
    if mode != "--check-only":
        sweep_flash()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
