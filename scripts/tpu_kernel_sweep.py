"""On-chip validation + block-size sweep for the Pallas kernels.

Runs ONLY when a real accelerator answers (the test suite covers the
interpret-mode path on CPU).  Produces:
  1. correctness: flash_attention fwd/bwd vs the reference einsum path,
     and paged_decode_attention_batch vs a dense reference, on-chip;
  2. a (block_q, block_k) timing sweep of flash fwd+bwd at the bench
     shape (B2 H16 S2048 D128, causal, bf16).

  3. `--gmm`: the grouped product (`ops/grouped_matmul.py`) at the two
     routed cells' own shapes over row and column tiles, the custom
     call's DEVICE time read from a profiler trace, against its least
     time and beside `megablox.gmm` over the doubled rows: the table
     `_tiles` rests on (PERF.md, PRs 46 and 47); then, as `--ffn` alone
     does on any tree, the whole `expert_ffn` at the same shapes.

  4. `--prefill`: WHOLE prefill programs of the families that stop at
     their prompt's end (`ops/prompt_blocks.py`), at their cells' widths:
     by rows x bucket, by the block of positions, by the flash kernel's
     blocks and by how far the prompts fill the bucket, the program's
     DEVICE time from a trace: the table `prompt_blocks.rows_of_a_block`
     rests on (PERF.md, PR 50).  On a tree without that module it times
     the whole-bucket program alone (a parent's).

Usage: python scripts/tpu_kernel_sweep.py
           [--sweep-only|--check-only|--latent|--gathered|--gmm|--ffn
            |--prefill [family ...]]
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# `python3 scripts/tpu_kernel_sweep.py` from the root of a checkout: the
# package is not installed, and sys.path[0] is scripts/.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # Google Cloud, "TPU v5e"


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


def check_paged(Hkv: int = 8, page: int = 16, npages_seq: int = 8,
                lengths=(37, 128, 1, 100), H: int = 8):
    """Hkv == H exercises MHA; Hkv < H exercises the GQA grouped-query
    q-block path (groups > 1), which must be validated on-chip too.
    `lengths` may be ragged and may hold 0 (an empty slot: its row is
    ignored, as the engine ignores it).  Then the same call with the
    current tokens' rows handed in: the pools it returns are the pools
    with those rows scattered in, bit for bit, and its output is the
    read-only call's on them."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_batch
    D = 128
    B = len(lengths)
    pool_pages = B * npages_seq + 1
    groups = H // Hkv
    lengths = np.asarray(lengths, np.int32)
    rng = np.random.default_rng(0)
    kq = jax.random.PRNGKey(1)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    k_pool = jnp.asarray(rng.standard_normal(
        (pool_pages, Hkv, page, D)), jnp.bfloat16)     # (P, Hkv, page, D)
    v_pool = jnp.asarray(rng.standard_normal(
        (pool_pages, Hkv, page, D)), jnp.bfloat16)
    # Scattered, non-monotonic rows; page 0 pads them, as the engine's
    # dummy page does.
    free = list(1 + rng.permutation(pool_pages - 1))
    tables = np.zeros((B, npages_seq), np.int32)
    for b in range(B):
        for p in range((int(lengths[b]) + page - 1) // page):
            tables[b, p] = free.pop()
    tables = jnp.asarray(tables)
    lengths_j = jnp.asarray(lengths)

    out = paged_decode_attention_batch(q, k_pool, v_pool, tables,
                                       lengths_j)

    # dense reference per sequence
    err = 0.0
    finite = bool(np.isfinite(np.asarray(out, np.float32)).all())
    for b in range(B):
        L = int(lengths[b])
        if L == 0:
            continue
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
    # the write: rows at position length - 1 (a length of 0 has none)
    k_new = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, D),
                              jnp.bfloat16)
    v_new = jax.random.normal(jax.random.PRNGKey(3), (B, Hkv, D),
                              jnp.bfloat16)
    at = np.maximum(lengths - 1, 0)
    rows = np.flatnonzero(lengths > 0)
    pages = np.asarray(tables)[rows, at[rows] // page]
    k_ref = k_pool.at[pages, :, at[rows] % page].set(k_new[rows])
    v_ref = v_pool.at[pages, :, at[rows] % page].set(v_new[rows])
    out_ref = paged_decode_attention_batch(q, k_ref, v_ref, tables,
                                           lengths_j)
    out_w, k_got, v_got = paged_decode_attention_batch(
        q, k_pool, v_pool, tables, lengths_j, k_new=k_new, v_new=v_new)
    bits = lambda a: np.asarray(a).view(np.uint16)  # noqa: E731
    pools_equal = bool((bits(k_got) == bits(k_ref)).all()
                       and (bits(v_got) == bits(v_ref)).all())
    live = lengths > 0
    out_equal = bool((bits(out_w)[live] == bits(out_ref)[live]).all())
    ok = finite and err < 0.05 and pools_equal and out_equal
    print(json.dumps({"check": "paged_decode_onchip", "Hkv": Hkv,
                      "groups": groups, "page": page,
                      "lengths": lengths.tolist(), "finite": finite,
                      "max_abs_err": round(err, 5),
                      "written_pools_bit_equal": pools_equal,
                      "written_output_bit_equal": out_equal, "ok": ok}))
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


def _latent_case(lengths, page: int, npages_seq: int, H: int = 16,
                 W: int = 640, seed: int = 0):
    """A pool of latent rows (zeros past the 576 cached values, as the
    model writes them), scattered tables, queries and the step's rows."""
    B = len(lengths)
    pool_pages = B * npages_seq + 1
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((pool_pages, page, W)).astype(np.float32)
    pool[..., 576:] = 0.0
    free = list(1 + rng.permutation(pool_pages - 1))
    tables = np.zeros((B, npages_seq), np.int32)
    for b in range(B):
        for p in range((int(lengths[b]) + page - 1) // page):
            tables[b, p] = free.pop()
    q = rng.standard_normal((B, H, W)).astype(np.float32) * 0.3
    new = rng.standard_normal((B, W)).astype(np.float32)
    q[..., 576:] = 0.0
    new[..., 576:] = 0.0
    return (jnp.asarray(q), jnp.asarray(pool, jnp.bfloat16),
            jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)),
            jnp.asarray(new, jnp.bfloat16))


def check_latent(page: int = 64, npages_seq: int = 37,
                 lengths=(0, 1, 64, 65, 0, 700, 37 * 64, 0)):
    """The latent-page kernel on the chip against numpy over gathered
    pages: the pool it returns is the pool with the step's rows scattered
    in, bit for bit, and its output the softmax over those rows."""
    from ray_tpu.ops.paged_attention import paged_latent_attention_batch
    q, pool, tables, lens, new = _latent_case(lengths, page, npages_seq)
    scale = 192 ** -0.5
    out, got = paged_latent_attention_batch(q, pool, tables, lens, new,
                                            d_value=512, sm_scale=scale)
    lengths = np.asarray(lengths)
    at = np.maximum(lengths - 1, 0)
    rows = np.flatnonzero(lengths > 0)
    pages = np.asarray(tables)[rows, at[rows] // page]
    want = pool.at[pages, at[rows] % page].set(new[rows])
    bits = lambda a: np.asarray(a).view(np.uint16)  # noqa: E731
    pool_equal = bool((bits(got) == bits(want)).all())
    err = 0.0
    for b in rows:
        L = int(lengths[b])
        kb = np.concatenate([np.asarray(want[tables[b, p]], np.float32)
                             for p in range(-(-L // page))], 0)[:L]
        s = np.asarray(q[b]) @ kb.T * scale
        p_ = np.exp(s - s.max(-1, keepdims=True))
        p_ /= p_.sum(-1, keepdims=True)
        err = max(err, float(np.abs(np.asarray(out[b])
                                    - p_ @ kb[:, :512]).max()))
    empty = float(np.abs(np.asarray(out)[lengths == 0]).max(initial=0.0))
    ok = pool_equal and err < 2e-3 and empty == 0.0
    print(json.dumps({"check": "paged_latent_onchip", "page": page,
                      "lengths": lengths.tolist(),
                      "max_abs_err": err, "empty_rows_max": empty,
                      "written_pool_bit_equal": pool_equal, "ok": ok}))
    return ok


def time_latent(batch: int = 64, page: int = 64):
    """The latent-page kernel alone at 64 rows of 2k, 4k and 8k tokens
    against its least time (1,152 bytes a token read once; 16 heads x
    (576 + 512) x 2 operations a token), the chip's published peaks."""
    from ray_tpu.ops.paged_attention import paged_latent_attention_batch
    for tokens in (2048, 4096, 8192):
        q, pool, tables, lens, new = _latent_case(
            [tokens] * batch, page, tokens // page + 1)
        f = jax.jit(functools.partial(paged_latent_attention_batch,
                                      d_value=512, sm_scale=192 ** -0.5),
                    donate_argnums=(1,))
        out, pool = f(q, pool, tables, lens, new)
        _sync(out)
        t0 = time.perf_counter()
        n = 20
        for _ in range(n):
            out, pool = f(q, pool, tables, lens, new)
        _sync(out)
        ms = (time.perf_counter() - t0) / n * 1e3
        resident = batch * tokens
        least_ms = max(resident * 1152 / PEAK_BYTES,
                       resident * 16 * (576 + 512) * 2 / PEAK_FLOPS) * 1e3
        print(json.dumps({"time": "paged_latent", "rows": batch,
                          "tokens_a_row": tokens, "ms": round(ms, 4),
                          "least_ms": round(least_ms, 4),
                          "roofline_share": round(least_ms / ms, 4)}))


def time_gathered(rows: int = 24, page: int = 64, table: int = 128):
    """The paged kernel alone as a sparse layer of `models/minicpm_sala.py`
    calls it (`--gathered`): 12 sequences x 2 K/V heads as 24 rows of 16
    float32 query heads over ONE K/V head, a gathered table of 128 columns
    of which 97 are live (the sparse regime) or all (a sequence at
    `dense_len`), pool rows of one (page, head): against its least time
    (K and V of each live page once, 32 KB; 16 heads x 64 x 128 x 4
    operations a page), the chip's published peaks."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_batch
    rng = np.random.default_rng(0)
    pool_pages = rows * table + 1
    pool = lambda: jnp.asarray(rng.standard_normal(  # noqa: E731
        (pool_pages, 1, page, 128)), jnp.bfloat16)
    k_pool, v_pool = pool(), pool()
    tables = jnp.asarray(1 + rng.permutation(pool_pages - 1).reshape(
        rows, table), jnp.int32)
    q = jnp.asarray(rng.standard_normal((rows, 16, 128)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((rows, 1, 128)), jnp.float32)
    f = jax.jit(lambda q, k, v, t, n, kn, vn: paged_decode_attention_batch(
        q, k, v, t, n, k_new=kn, v_new=vn), donate_argnums=(1, 2))
    for pages in (97, table):
        lens = jnp.full((rows,), (pages - 1) * page + 17, jnp.int32)
        out, k_pool, v_pool = f(q, k_pool, v_pool, tables, lens, new, new)
        _sync(out)
        t0 = time.perf_counter()
        n = 50
        for _ in range(n):
            out, k_pool, v_pool = f(q, k_pool, v_pool, tables, lens, new,
                                    new)
        _sync(out)
        ms = (time.perf_counter() - t0) / n * 1e3
        live = rows * pages
        least_ms = max(live * 2 * page * 128 * 2 / PEAK_BYTES,
                       live * 4 * 16 * page * 128 / PEAK_FLOPS) * 1e3
        print(json.dumps({"time": "paged_gathered", "rows": rows,
                          "live_pages_a_row": pages, "ms": round(ms, 4),
                          "least_ms": round(least_ms, 4),
                          "roofline_share": round(least_ms / ms, 4)}))


# ---- the grouped product: tiles against device time ------------------------

GMM_ROW_TILES = (32, 64, 128, 256)
GMM_REPS = 5
# family -> (configuration file, its costs, its key for the experts held,
# experts a decode step touches in the cell (ledger, PRs 42 and 45),
# prompt tokens: short, median and long prompts of the cell's mix)
GMM_FAMILIES = {
    "mla_moe": ("kimi-vl-a3b-l7.json", "mla_moe_costs", "n_routed_experts",
                63, (512, 2048, 8192)),
    "lfm2_moe": ("lfm2-24b-a2b-l9.json", "lfm2_moe_costs", "num_experts",
                 40, (128, 256, 512, 1024, 4096)),
    # (one chip's share: 36 groups held of the 72 routed over, so that of a
    # token's ten pairs about five lie in a group; PR 52)
    "granite_moe_hybrid": ("granite-4.0-h-small-l10-e36.json",
                           "granite_moe_hybrid_costs", "num_local_experts",
                           36, (512, 2048)),
}


def _even_sizes(pairs: int, groups: int, touched: int):
    """Rows of each group, one a (row, expert) pair, of a decode step: its
    pairs spread evenly over `touched` groups that lie evenly among all."""
    at = np.linspace(0, groups - 1, touched).round().astype(int)
    sizes = np.zeros(groups, np.int32)
    sizes[at] = pairs // touched
    sizes[at[: pairs % touched]] += 1
    return sizes


def _routed_sizes(tokens: int, top_k: int, groups: int, rng,
                  routed: int | None = None):
    """The same of a prompt: each token draws distinct experts at random,
    of the `routed` experts there are (the groups held, where a layer's
    are not divided over chips): the pairs of the others lie elsewhere."""
    sizes = np.zeros(routed or groups, np.int32)
    for _ in range(tokens):
        sizes[rng.choice(len(sizes), top_k, replace=False)] += 1
    return sizes[:groups]


def _gmm_cases(family: str):
    """(label, group sizes, tokens) of the family's decode step and
    prompts, and its (k, n) of W1|W3 and of W2."""
    name, costs, experts_key, touched, prompts = GMM_FAMILIES[family]
    with open(os.path.join(_REPO, "benchmarks", "configs", name)) as f:
        conf = json.load(f)
    E, top_k = conf[experts_key], conf["num_experts_per_tok"]
    # (a chip's share of a layer: the pairs are spread over all of the
    # experts routed over, and those of the groups held are here)
    routed = conf.get("published", {}).get(experts_key, E)
    d = conf["hidden_size"]
    f_ = conf.get("moe_intermediate_size") or conf["intermediate_size"]
    batch = conf["serve"]["engine"]["max_batch"]
    rng = np.random.default_rng(0)
    cases = [(f"decode {batch}",
              _even_sizes(batch * top_k * E // routed, E, touched), batch)]
    cases += [(f"prompt {t}", _routed_sizes(t, top_k, E, rng, routed), t)
              for t in prompts]
    return conf, costs, cases, (("w13", d, 2 * f_), ("w2", f_, d))


def _gmm_cost(pairs: int, touched: int, k: int, n: int) -> tuple:
    """One product's part of `grouped_product_cost` -> (operations,
    bytes): two operations a pair and parameter; each touched group's
    matrix once, each pair's row in and out in float32."""
    return 2.0 * pairs * k * n, touched * k * n * 2 + pairs * 4.0 * (k + n)


def _gmm_tilings(k: int, n: int):
    """Row tiles (float32 rows) x column tiles, the contraction whole: the
    columns in 512s (256s for 2,816: PR 46's tile for a prompt), in two
    halves, and whole."""
    cols = (512 if n % 512 == 0 else 256, n // 2, n)
    return [(tm, k, tn) for tn in cols for tm in GMM_ROW_TILES]


def _doubled_rows_tiles(rows: int, groups: int, k: int, n: int) -> tuple:
    """What PR 46 gave `megablox.gmm` over DOUBLED rows (two bfloat16 rows
    a pair): the sweep's yardstick."""
    cols = next((c for c in (512, 384, 256, 128) if n % c == 0), min(n, 512))
    if rows > 64 * groups:
        return 256, k, cols
    if cols < min(n, 512) and n % 256 == 0 and k * n <= 6 * 2 ** 20:
        cols = n // 2
    return 128, k, cols


def _device_ms(trace_dir: str) -> dict:
    """{program: [(ms of its Pallas calls, ms of the whole run, ms of each
    call in the run's order), a run each]} from the xplane a profiler
    session left: the line `XLA Modules` holds the runs of a jitted
    function, `XLA Ops` its operations, a kernel by its target."""
    import glob
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines["XLA Modules"].events)
        calls = sorted((e.start_ns, e.duration_ns)
                       for e in lines["XLA Ops"].events
                       if 'custom_call_target="tpu_custom_call"' in e.name)
        for t0, t1, name in runs:
            each = tuple(d / 1e6 for s, d in calls if t0 <= s < t1)
            name = name.split("(")[0].removeprefix("jit_")
            out.setdefault(name, []).append(
                (sum(each), (t1 - t0) / 1e6, each))
    return out


def _median(values):
    return sorted(values)[len(values) // 2]


def _traced_ms(fns: dict, args) -> dict:
    """{name: (median ms of the Pallas calls, of the whole run, of each
    call)} of jitted functions (compiled already) over `args`, GMM_REPS
    runs each."""
    import tempfile

    got = None
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for f in fns.values():
                for _ in range(GMM_REPS):
                    got = f(*args)      # (one result held)
                _sync(got)
        ms = _device_ms(trace_dir)
    return {name: (_median([r[0] for r in ms[name]]),
                   _median([r[1] for r in ms[name]]),
                   tuple(map(_median, zip(*(r[2] for r in ms[name])))))
            for name in fns if ms.get(name)}


def _emitter(file_name: str):
    out_dir = os.path.join(_REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    record = open(os.path.join(out_dir, file_name), "w")
    lines = []

    def emit(line):
        lines.append(line)
        record.write(json.dumps(line) + "\n")
        record.flush()
        print(json.dumps(line), flush=True)

    return emit, lines


def sweep_gmm(families):
    """One line a (family, case, product, tiling): the median device time
    of the repo's kernel alone (float32 rows in, the two terms made inside)
    against the product's least time, its share of `grouped_product_cost`
    (the two products' shares add up to it), and whether the rows of
    groups came out bit for bit as `megablox.gmm` gives them over the
    DOUBLED rows with the two halves added; that call, at the tiles PR 46
    gave it, is timed beside (tiles [0, 0, 0])."""
    import importlib

    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ray_tpu.models.sambay import _halves, _two_terms
    from ray_tpu.ops import grouped_matmul as gm

    device = jax.devices()[0].device_kind
    emit, lines = _emitter("gmm_sweep.jsonl")
    for family in families:
        conf, costs, cases, products = _gmm_cases(family)
        costs = importlib.import_module("benchmarks.layer_metrics." + costs)
        for which, k, n in products:
            for label, sizes, _ in cases:
                E, rows = len(sizes), int(sizes.sum())
                touched = int((sizes > 0).sum())
                flops, nbytes = _gmm_cost(rows, touched, k, n)
                assert np.allclose(
                    np.sum([_gmm_cost(rows, touched, a, b)
                            for _, a, b in products], axis=0),
                    costs.grouped_product_cost(conf, rows, touched))
                least = max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) * 1e3
                x = jax.random.normal(jax.random.PRNGKey(2), (rows, k),
                                      jnp.float32)
                args = (x, jax.random.normal(
                    jax.random.PRNGKey(1), (E, k, n), jnp.bfloat16) * 0.02,
                    jnp.asarray(sizes))
                said = {"family": family, "case": label, "product": which}

                old_tiles = _doubled_rows_tiles(2 * rows, E, k, n)

                def doubled(xx, w, sizes):
                    return gmm(jnp.pad(xx, ((0, -xx.shape[0] % old_tiles[0]),
                                            (0, 0))),
                               w, 2 * sizes, jnp.float32,
                               old_tiles, interpret=gm._interpret_mode())[
                                   : xx.shape[0]]
                doubled.__name__ = "doubled_rows"
                doubled = jax.jit(doubled)
                xx = jax.jit(lambda x: _two_terms(x[:, None], 1).reshape(
                    2 * rows, k))(x)
                first = jax.jit(lambda o: _halves(
                    o.reshape(rows, 2, -1), 1)[:, 0])(
                        doubled(xx, *args[1:]))
                fns, same = {}, {}
                for tiles in _gmm_tilings(k, n):
                    if gm._vmem_bytes(tiles[0], k, n, tiles[2], 2) \
                            > gm._VMEM_LIMIT:
                        continue

                    def run(x, w, sizes, tiles=tiles):
                        M = x.shape[0]      # (the rows as they lie)
                        return gm._grouped_call(
                            x, w, sizes, jnp.pad(
                                jnp.arange(M, dtype=jnp.int32),
                                (0, -M % tiles[0])),
                            None, two_terms=_two_terms, tiles=tiles,
                            gated=False, interpret=gm._interpret_mode())[:M]
                    run.__name__ = "grouped_%d_%d_%d" % tiles
                    f = jax.jit(run)
                    try:
                        same[tiles] = bool(jnp.array_equal(f(*args), first))
                        fns[run.__name__] = f
                    except Exception as e:  # noqa: BLE001 — record, go on
                        emit({**said, "tiles": list(tiles),
                              "error": str(e).splitlines()[0][:160]})
                first = None
                ms = _traced_ms(fns, args)
                ms.update(_traced_ms({"doubled_rows": doubled},
                                     (xx,) + args[1:]))
                for tiles in [(0, 0, 0)] + _gmm_tilings(k, n):
                    name = "grouped_%d_%d_%d" % tiles if tiles[0] \
                        else "doubled_rows"
                    if name not in ms:
                        continue
                    emit({**said, "rows": rows, "groups": E, "k": k, "n": n,
                          "touched": touched,
                          "rows_a_group": round(rows / E, 1),
                          "tiles": list(tiles),
                          "doubled_rows_tiles": list(old_tiles),
                          "same_bits_as_doubled_rows": same.get(tiles, True),
                          "ms": round(ms[name][0], 4),
                          "least_ms": round(least, 4),
                          "bound": "bytes" if nbytes / PEAK_BYTES
                          > flops / PEAK_FLOPS else "operations",
                          "share": round(least / ms[name][0], 4),
                          "device": device})
    table = _gmm_table(lines)
    with open(os.path.join(_REPO, "chiprun_out", "gmm_sweep.md"), "w") as f:
        f.write(table + "\n")
    print(table)


def _gmm_table(lines) -> str:
    """The sweep as a table: a row a (family, case, product, column tile),
    a column a row tile, `ms (share of the least time)`; in bold what
    `_tiles` picks, `!` where the bits differ from the doubled rows'; the
    doubled rows through `megablox.gmm` in a column of their own."""
    from ray_tpu.ops.grouped_matmul import _tiles

    def cell(ln):
        text = f"{ln['ms']:.3f} ({100 * ln['share']:.0f}%)" \
            + ("" if ln["same_bits_as_doubled_rows"] else " !")
        picked = _tiles(ln["k"], ln["n"])
        return f"**{text}**" if list(picked) == ln["tiles"] else text

    rows, doubled = {}, {}
    for ln in (ln for ln in lines if "ms" in ln):
        tm, tk, tn = ln["tiles"]
        if not tm:
            doubled[ln["family"], ln["case"], ln["product"]] = ln
            continue
        key = (ln["family"], ln["case"], ln["product"], tn)
        rows.setdefault(key, {"rows_a_group": ln["rows_a_group"],
                              "least_ms": ln["least_ms"]})[tm] = ln
    head = ["family", "case", "product", "rows a group", "least ms",
            "doubled rows, gmm", "cols"] \
        + [f"rows {tm}" for tm in GMM_ROW_TILES]
    table = ["| " + " | ".join(head) + " |",
             "|" + " --- |" * len(head)]
    for (family, case, which, tn), r in rows.items():
        cells = [cell(r[tm]) if tm in r else "-" for tm in GMM_ROW_TILES]
        old = doubled.get((family, case, which))
        table.append("| " + " | ".join(
            [family, case, which, str(r["rows_a_group"]),
             f"{r['least_ms']:.3f}",
             f"{old['ms']:.3f} at {old['doubled_rows_tiles'][0]} x "
             f"{old['doubled_rows_tiles'][2]}" if old else "-", str(tn)]
            + cells) + " |")
    return "\n".join(table)


def _pairs_by_expert(idx, gates, E, valid=None, first=None):
    """The (row, expert) pairs of a call as `lfm2_moe.expert_ffn` sorts
    them -> (the sorted order, the rows of each of the E groups held here,
    the gates kept): a pair of a row that is not `valid`, or whose expert
    is none of `first` ... `first + E - 1`, is sorted past the last group
    and its gate is 0."""
    T, k = idx.shape
    flat = idx.T.reshape(-1)
    kept = gates
    if first is not None:
        here = (idx >= first) & (idx < first + E)
        flat = jnp.where(here.T.reshape(-1), flat - first, E)
        kept = jnp.where(here, kept, 0.0)
    if valid is not None:
        flat = jnp.where(jnp.tile(valid, k), flat, E)
        kept = jnp.where(valid[:, None], kept, 0.0)
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    return jnp.argsort(flat), sizes, kept


def _expert_ffn_around_the_kernel(u, idx, gates, w13, w2, valid=None,
                                  first=None):
    """`lfm2_moe.expert_ffn` as it was until PR 54, XLA moving the rows
    AROUND the kernel: a gather lays the sorted pairs' rows out, the kernel
    takes them as they lie, XLA gates its result, and a second sort and
    gather bring the rows back to pair order.  (On this tree the kernel
    under it is this tree's, over `arange`; a parent's own numbers come
    from this script run on the parent's tree.)"""
    from ray_tpu.models.sambay import _two_terms
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    T, k = idx.shape
    order, sizes, kept = _pairs_by_expert(idx, gates, w13.shape[0], valid,
                                          first)
    x = u[order % T]
    a, b = jnp.split(grouped_matmul(x, w13, sizes, _two_terms), 2, axis=-1)
    y = grouped_matmul(jax.nn.silu(a) * b, w2, sizes, _two_terms)
    y = y[jnp.argsort(order)].reshape(k, T, -1)
    kept = kept.T[..., None]
    return jnp.sum(jnp.where(kept > 0, y, 0.0) * kept, axis=0)


def time_expert_ffn(families):
    """The whole routed feed-forward (`lfm2_moe.expert_ffn`: the sort, both
    grouped products and what stands around them) at the sweep's cases:
    the median device time of the jitted call, of each of its two Pallas
    calls and of what stands around them, and the same of the layer as it
    was until PR 54 (`_expert_ffn_around_the_kernel`) beside it, with how
    far the two results lie apart.  `expert_ffn` itself reads nothing of
    the kernel's own, so the same script times a parent commit's tree
    (`--ffn`)."""
    from ray_tpu.models.lfm2_moe import expert_ffn

    emit, lines = _emitter("expert_ffn.jsonl")
    for family in families:
        conf, _, cases, products = _gmm_cases(family)
        (_, d, f2), _ = products
        top_k = conf["num_experts_per_tok"]
        for label, sizes, tokens in cases:
            E = len(sizes)
            rng = np.random.default_rng(1)
            share = {}
            if sizes.sum() == tokens * top_k:
                idx = rng.permutation(np.repeat(np.arange(E), sizes)) \
                    .reshape(tokens, top_k).astype(np.int32)
            else:       # a chip's share: the rest of the pairs lie elsewhere
                routed = conf["published"][GMM_FAMILIES[family][2]]
                idx = np.stack([rng.choice(routed, top_k, replace=False)
                                for _ in range(tokens)]).astype(np.int32)
                sizes = np.bincount(idx[idx < E], minlength=E)
                share = {"first": 0}
            keys = jax.random.split(jax.random.PRNGKey(3), 4)
            args = (jax.random.normal(keys[0], (tokens, d), jnp.float32),
                    jnp.asarray(idx),
                    jax.nn.softmax(jax.random.normal(
                        keys[1], (tokens, top_k), jnp.float32)),
                    jax.random.normal(keys[2], (E, d, f2),
                                      jnp.bfloat16) * 0.02,
                    jax.random.normal(keys[3], (E, f2 // 2, d),
                                      jnp.bfloat16) * 0.02)

            def ffn(*a, share=share):
                return expert_ffn(*a, **share)[0]

            def around(*a, share=share):
                return _expert_ffn_around_the_kernel(*a, **share)
            fns = {"ffn": jax.jit(ffn), "around": jax.jit(around)}
            out = fns["ffn"](*args)
            apart = float(jnp.max(jnp.abs(fns["around"](*args) - out)))
            for name, (kernel_ms, whole_ms, each) in _traced_ms(
                    fns, args).items():
                emit({"family": family, "case": label, "tokens": tokens,
                      "pairs": int(sizes.sum()),
                      "rows_moved": "by_xla_around_the_kernel"
                      if name == "around" else "as_the_tree_has_it",
                      "kernels_ms": round(kernel_ms, 4),
                      "each_kernel_ms": [round(ms, 4) for ms in each],
                      "expert_ffn_ms": round(whole_ms, 4),
                      "around_ms": round(whole_ms - kernel_ms, 4),
                      "checksum": float(jnp.sum(jnp.abs(out))),
                      **({"apart": apart} if name == "around" else {}),
                      "device": jax.devices()[0].device_kind})
    table = _ffn_table(lines)
    with open(os.path.join(_REPO, "chiprun_out", "expert_ffn.md"), "w") as f:
        f.write(table + "\n")
    print(table)


def _ffn_table(lines) -> str:
    """A call a row: the whole layer, W1|W3's kernel, W2's and what stands
    around them (ms), XLA moving the rows around the kernel | as the tree
    has it."""
    head = ["family", "call", "pairs held", "whole layer", "W1|W3", "W2",
            "around the kernels"]
    table = ["| " + " | ".join(head) + " |", "|" + " --- |" * len(head)]
    calls = {}
    for ln in lines:
        calls.setdefault((ln["family"], ln["case"], ln["pairs"]), {})[
            ln["rows_moved"]] = ln
    for (family, case, pairs), by in calls.items():
        pair = [by[key] for key in ("by_xla_around_the_kernel",
                                    "as_the_tree_has_it") if key in by]
        cells = [" \\| ".join(f"{value(ln):.3f}" for ln in pair)
                 for value in (lambda ln: ln["expert_ffn_ms"],
                               lambda ln: ln["each_kernel_ms"][0],
                               lambda ln: ln["each_kernel_ms"][1],
                               lambda ln: ln["around_ms"])]
        table.append("| " + " | ".join([family, case, str(pairs)] + cells)
                     + " |")
    return "\n".join(table)


# family -> (configuration file, rows x bucket of the programs timed).
# The next family that takes `prompt_blocks` (granite_hybrid, lfm2_moe) is
# sized here: add its line.
PREFILL_FAMILIES = {
    "dense_decoder": ("mistral-7b-v0.3-l16-b4.json",
                      ((1, 1024), (1, 2048), (4, 2048))),
    "mla_moe": ("kimi-vl-a3b-l7.json", ((1, 2048), (1, 4096), (1, 8192))),
    "sambay": ("phi-4-mini-flash-reasoning.json",
               ((1, 2048), (1, 4096), (1, 8192), (1, 16384), (2, 8192))),
}
PREFILL_BLOCKS = (128, 256, 512, 1024)      # positions of a block
# the flash kernel's (block_q, block_k), tried at PREFILL_AT's block
PREFILL_KERNEL_BLOCKS = ((512, 1024), (512, 512))
# (a family with no entry takes no `prompt_blocks`: `sambay` prefills from
# the host, `sweep_prefill_from_host`)
PREFILL_AT = {"dense_decoder": 512, "mla_moe": 256}
PREFILL_FILLS = (0.6, 0.75, 1.0)
PREFILL_REPS = 3
# ... whose block (`SambaYServing.block`) is tried at these, the longest row
# at these shares of its bucket
PREFILL_HOST_BLOCKS = (512, 1024, 2048)
PREFILL_HOST_FILLS = (0.75, 1.0)


def _ends_apart(rows: int, bucket: int, fill: float) -> list:
    """Each row's last index: a group's rows end apart, the longest at
    `fill` of the bucket."""
    return [int(bucket * fill * (1 - 0.1 * r)) - 1 for r in range(rows)]


def _seeded_like(shapes, key):
    """Weights of the shapes' types, normal(0, 0.02), made on the device
    leaf by leaf (a sweep times programs; it judges no logits)."""
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        jax.jit(lambda k, a=a: (jax.random.normal(k, a.shape, jnp.float32)
                                * 0.02).astype(a.dtype))(k)
        for k, a in zip(keys, leaves)])


def sweep_prefill_from_host(family, make_serving, params, cases, emit):
    """A family that prefills from the host (`prefill_from_host`: `sambay`,
    a block of positions a dispatch, then a tail): the SEQUENCE the engine
    dispatches, timed whole.  Lines of {family, rows, bucket, block, fill,
    blocks, ms, block_ms, tail_ms, wall_ms}: `ms` the device time of all of
    a sequence's programs (the median of PREFILL_REPS sequences), `block_ms`
    and `tail_ms` the median run of the two programs that hold layers,
    `wall_ms` the host's clock from the first dispatch to the logits (what
    the chip waited for the host is the difference)."""
    import tempfile

    for block in PREFILL_HOST_BLOCKS:
        serving = make_serving()
        serving.block = block       # (before its first program is traced)
        for rows, bucket in cases:
            tokens = np.asarray(jax.random.randint(
                jax.random.PRNGKey(2), (rows, bucket), 1,
                serving.cfg.vocab_size), np.int32)
            for fill in PREFILL_HOST_FILLS:
                last = np.asarray(_ends_apart(rows, bucket, fill), np.int32)
                _sync(serving.prefill_from_host(params, tokens, last))
                walls = []
                with tempfile.TemporaryDirectory() as trace_dir:
                    with jax.profiler.trace(trace_dir):
                        for _ in range(PREFILL_REPS):
                            t0 = time.perf_counter()
                            got = serving.prefill_from_host(params, tokens,
                                                            last)
                            _sync(got)
                            walls.append((time.perf_counter() - t0) * 1e3)
                    runs = _device_ms(trace_dir)
                emit({"family": family, "rows": rows, "bucket": bucket,
                      "block": block, "fill": fill,
                      "blocks": int(serving.prompt_blocks(last + 1)),
                      "ms": round(sum(r[1] for of in runs.values()
                                      for r in of) / PREFILL_REPS, 3),
                      "block_ms": round(_median(
                          [r[1] for r in runs["prompt_block"]]), 3),
                      "tail_ms": round(_median(
                          [r[1] for r in runs["prompt_tail"]]), 3),
                      "wall_ms": round(_median(walls), 3),
                      "checksum": float(jnp.sum(jnp.abs(got[0]))),
                      "device": jax.devices()[0].device_kind})


def sweep_prefill(families):
    """Lines of {family, rows, bucket, block, kernel_blocks, fill, ms}:
    the median device time of the whole program, `family.prefill` jitted
    as the engine jits it, over PREFILL_REPS runs a fill."""
    import importlib
    import tempfile

    from ray_tpu.ops import attention
    from ray_tpu.serve.llm_families import family_of
    try:
        from ray_tpu.ops import prompt_blocks
    except ImportError:         # a tree from before the module
        prompt_blocks = None

    emit, lines = _emitter("prefill_sweep.jsonl")
    kernel = attention.causal_over_itself

    def variants(family):
        if prompt_blocks is None or family not in PREFILL_AT:
            return [(None, None)]
        return [(None, None)] + [(b, None) for b in PREFILL_BLOCKS] + \
            [(PREFILL_AT[family], kb) for kb in PREFILL_KERNEL_BLOCKS]

    for family in families:
        name, cases = PREFILL_FAMILIES[family]
        with open(os.path.join(_REPO, "benchmarks", "configs", name)) as f:
            conf = json.load(f)
        adapter = importlib.import_module(f"benchmarks.families.{family}")
        cfg = adapter.program_config(adapter.sizes(conf))  # (the replica's)
        serving = family_of(cfg, conf["serve"]["engine"]["max_len"])
        params = _seeded_like(jax.eval_shape(
            lambda: serving.model.init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 8), jnp.int32))),
            jax.random.PRNGKey(1))
        if hasattr(serving, "prefill_from_host"):
            sweep_prefill_from_host(
                family, lambda: family_of(cfg, serving.max_len), params,
                cases, emit)
            continue
        for rows, bucket in cases:
            tokens = jax.random.randint(jax.random.PRNGKey(2),
                                        (rows, bucket), 1, cfg.vocab_size)
            for block, kernel_blocks in variants(family):
                # (the block is the module's rule and the kernel's blocks
                # its defaults: a sweep sets them from outside)
                if prompt_blocks is not None:
                    whole = block is None
                    prompt_blocks.rows_of_a_block = \
                        lambda *a, _b=(1 << 30 if whole else block), **k: _b
                patched = kernel if kernel_blocks is None else \
                    functools.partial(kernel, block_q=kernel_blocks[0],
                                      block_k=kernel_blocks[1])
                # (`mla_moe` looks the kernel up at each call, `llama`
                # holds the name)
                attention.causal_over_itself = patched
                sys.modules["ray_tpu.models.llama"].causal_over_itself = \
                    patched

                def prefill(p, t, last):
                    return serving.prefill(p, t, last)[0]
                prefill.__name__ = f"prefill_{rows}x{bucket}_{block}_" + \
                    "x".join(map(str, kernel_blocks or ()))
                f = jax.jit(prefill)
                for fill in PREFILL_FILLS:
                    last = jnp.asarray(_ends_apart(rows, bucket, fill),
                                       jnp.int32)
                    _sync(f(params, tokens, last))
                    with tempfile.TemporaryDirectory() as trace_dir:
                        with jax.profiler.trace(trace_dir):
                            for _ in range(PREFILL_REPS):
                                got = f(params, tokens, last)
                            _sync(got)
                        runs = _device_ms(trace_dir)
                    (key, ms), = ((k, v) for k, v in runs.items()
                                  if k.startswith("prefill_"))
                    emit({"family": family, "rows": rows, "bucket": bucket,
                          "block": block, "kernel_blocks": kernel_blocks,
                          "fill": fill,
                          "ms": round(_median([r[1] for r in ms]), 3),
                          "kernel_ms": round(_median([r[0] for r in ms]), 3),
                          "checksum": float(jnp.sum(jnp.abs(got))),
                          "device": jax.devices()[0].device_kind})
        del params
    return lines


def main():
    if jax.default_backend() != "tpu":
        sys.exit(f"tpu_kernel_sweep: an on-chip script, and the JAX backend "
                 f"here is {jax.default_backend()!r} (tests cover the CPU)")
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    ok = True
    if mode == "--latent":      # the latent-page kernel alone, and its time
        ok = check_latent() and check_latent(lengths=(5, 128, 1000, 64))
        time_latent()
        sys.exit(0 if ok else 1)
    if mode == "--gathered":    # the paged kernel over a gathered table
        ok = check_paged(Hkv=1, H=16, page=64, npages_seq=128,
                         lengths=(96 * 64 + 17, 8192, 1, 0, 6000, 97 * 64))
        time_gathered()
        sys.exit(0 if ok else 1)
    if mode == "--prefill":     # whole prefill programs by block and fill
        sweep_prefill(sys.argv[2:] or list(PREFILL_FAMILIES))
        sys.exit(0)
    if mode == "--gmm":         # the grouped product's tiles, from a trace
        sweep_gmm(sys.argv[2:] or list(GMM_FAMILIES))
    if mode in ("--gmm", "--ffn"):  # and the routed layer around it
        time_expert_ffn(sys.argv[2:] or list(GMM_FAMILIES))
        sys.exit(0)
    if mode != "--sweep-only":
        ok = check_flash() and ok
        ok = check_paged(Hkv=8) and ok   # MHA
        ok = check_paged(Hkv=2) and ok   # GQA, groups=4
        # The serving shape: pages of 64, a table of 37, so that a block
        # holds 8 pages; 1 token, a page, a page and one, a part-filled
        # last block, a full table, and empty slots first, between, last.
        ok = check_paged(Hkv=2, page=64, npages_seq=37,
                         lengths=(0, 1, 64, 65, 0, 700, 37 * 64, 0)) and ok
        ok = check_latent() and ok
        # A sparse layer's call: a row a (sequence, K/V head), 16 query
        # heads over ONE K/V head, a gathered table of 128 columns.
        ok = check_paged(Hkv=1, H=16, page=64, npages_seq=128,
                         lengths=(96 * 64 + 17, 8192, 1, 0, 6000)) and ok
    if mode != "--check-only":
        sweep_flash()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
