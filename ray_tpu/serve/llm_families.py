"""What a model tells the serving engine: the one seam between
`LLMEngine` (slots, admission, pages, sampling, streams: host logic that
knows no architecture) and a model family (what a sequence's state is and
how the device advances it).

A family object answers, for its configuration:

    model                       the flax module (`engine.model`)
    rewinds                     True where an uncommitted decode step may
                                simply be run again (its cache writes are
                                overwritten before they are read: attention
                                caches).  False where a step changes state
                                for good (recurrent state): the engine then
                                sizes every slot's steps BEFORE a chunk is
                                dispatched and the program holds a slot
                                still past its count (`live`).
    portable_kv                 True where a stream's whole state is a
                                per-layer KV prefix, which another engine
                                can be handed (`submit_prefilled`) and a
                                drain snapshot can carry
    pool_readers                layers that read a sequence's pages in one
                                decode step through ONE table row of ONE pool
    state_bytes_per_slot        bytes of the fixed per-slot state of one
                                sequence, from shapes alone (0: all of
                                its state is pages)
    prefill_width(bucket, max_batch)   rows of the batched prefill program
                                at a bucket (fixed, so one program a bucket)
    prompt_pages(bucket, page_size)    page columns `write_prompt` takes
    init_state(max_batch, num_pages, page_size)
                                the engine's whole decode state, a pytree:
                                what is paged (pools of `num_pages` pages)
                                and what is fixed per slot (leading axis
                                `max_batch`)
    prefill(params, tokens (W, bucket), last_idx (W,))
                                -> float32 logits (W, V) at each row's last
                                token, and the rows' fresh state AT that
                                token (right-padding must not leak into it)
    write_prompt(state, fresh, slots (W,), page_ids (W, n))
                                -> state with the rows' fresh state in it:
                                paged parts scattered to `page_ids` (padding
                                rows and columns name the dummy page), fixed
                                parts written whole to `slots` (a padding
                                row's slot is `max_batch`: dropped).  Writing
                                a slot's fixed state whole IS how a slot is
                                cleared: a reused slot starts from what its
                                own prefill computed from zero.
    decode(params, token, pos, state, tables, lens, live)
                                -> float32 logits (B, V), state; one token
                                a slot.  `live` (B,) bool or None.
    prefill_computed(bucket, lengths)   (optional) positions the prefill
                                program computes for a group of prompts of
                                `lengths` tokens, where it stops short of
                                the bucket (host arithmetic; absent: every
                                row's whole bucket).  `engine.prefill`
                                carries it as `computed`.
    prefill_from_host(params, tokens, last_idx)
                                (optional, and then INSTEAD of `prefill`)
                                the same from the host's numpy arrays, by
                                programs the family jits and dispatches
                                itself on the engine's thread, fetching
                                nothing: a family that can carry its state
                                along a prompt computes it a block of
                                positions a dispatch, as many as the longest
                                row has, and no program loops over blocks.
                                Its first dispatch comes at once (the
                                engine takes the group to be on the chip
                                from the call).  With it goes
                                `prompt_blocks(lengths)`, the dispatches a
                                group takes (host arithmetic): the group's
                                spans carry it as `blocks`.  The engine jits
                                no prefill of such a family.
    step_counters, prefill_counters     (optional) what `decode` and
                                `prefill` count on the device: ((name,
                                "sum" | "max"), ...).  Where a family names
                                some, the function returns one more value,
                                an int32 array with one count each; the
                                engine fetches it with the tokens, reduces
                                a chunk's steps as the pair says, and puts
                                the result on `engine.decode.wait` or
                                `engine.prefill` and into `report_metrics()`.

Freeing a slot is the engine's: its pages go back to the allocator, its
table row to the dummy page, its length to 0.  Fixed state needs nothing
then; it is replaced at the next admission.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                           GraniteHybridModel)
from ray_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeModel
from ray_tpu.models.llama import (FreshKV, LlamaConfig, LlamaModel,
                                  PagedKVCache)
from ray_tpu.models.minicpm_sala import (LIGHTNING, SPARSE, STEP_COUNTS,
                                         MiniCpmSalaConfig, MiniCpmSalaModel)
from ray_tpu.models.mla_moe import MlaMoeConfig, MlaMoeModel
from ray_tpu.models.sambay import SambaYConfig, SambaYModel
from ray_tpu.ops import prompt_blocks

# Rows of the batched prefill program (fewer where the slots are fewer).
BATCH_PREFILL_WIDTH = 8


def _fixed_bytes_per_slot(family, fixed, page_size: int = 64) -> int:
    """Bytes one slot holds of the per-slot part of a family's state
    (`fixed(state)` picks it out), from shapes alone."""
    state = jax.eval_shape(lambda: family.init_state(1, 1, page_size))
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(fixed(state)))


def _pages(a, page_size: int):
    """(W, H, L, D) -> (W * L / page_size, H, page_size, D): the rows'
    tokens cut into pool pages, in the order of a flattened (W, L / ps)."""
    W, H, L, D = a.shape
    return a.reshape(W, H, L // page_size, page_size, D) \
        .transpose(0, 2, 1, 3, 4).reshape(-1, H, page_size, D)


class LlamaServing:
    """Rotary GQA decoders through `models/llama.py`: a (k, v) pool a
    layer, nothing fixed."""

    rewinds = True
    portable_kv = True
    pool_readers = 1
    state_bytes_per_slot = 0

    def __init__(self, cfg: LlamaConfig, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        self.model = LlamaModel(cfg)

    def prefill_width(self, bucket: int, max_batch: int) -> int:
        return min(BATCH_PREFILL_WIDTH, max_batch)

    def prompt_pages(self, bucket: int, page_size: int) -> int:
        # the prefill's K/V are as long as the bucket (a page multiple:
        # a power of two no smaller than a page, or max_len itself)
        return bucket // page_size

    def init_state(self, max_batch: int, num_pages: int, page_size: int):
        shape = (num_pages, self.cfg.n_kv_heads, page_size,
                 self.cfg.head_dim)
        return [(jnp.zeros(shape, self.cfg.dtype),
                 jnp.zeros(shape, self.cfg.dtype))
                for _ in range(self.cfg.n_layers)]

    def prefill(self, params, tokens, last_idx):
        # tokens: (W, bucket) right-padded. A slot's pages hold no
        # earlier keys, so the prompt attends over itself (`FreshKV`:
        # the flash kernel, no cache of max_len) and each layer's K/V
        # come back (W, Hkv, bucket, D). Those past a row's true length
        # are garbage, but they land on the dummy page or are overwritten
        # at index `length` before the decode kernel, which reads no
        # further than that, attends them. `FreshKV(last_idx)`: the
        # program stops at the prompts' end, and the hidden row of each
        # row's last token is gathered BEFORE the head: the (W, bucket,
        # vocab) logits are never made.
        positions = jnp.arange(tokens.shape[1])[None, :]
        return self.model.apply(params, tokens, positions,
                                kv_caches=FreshKV(last_idx))

    def prefill_computed(self, bucket: int, lengths) -> int:
        return prompt_blocks.positions_computed(
            bucket, prompt_blocks.rows_of_a_block(self.cfg.dtype, 1, bucket),
            lengths)

    def write_prompt(self, pools, fresh, slots, page_ids):
        # Scatter the rows' (W, Hkv, L, D) K/V into pool pages (pool
        # layout (P, Hkv, page, D)): rows flatten into one scatter;
        # page_ids past a prompt point at the dummy page (garbage there
        # is fine).
        flat = page_ids.reshape(-1)
        ps = pools[0][0].shape[2]
        return [(kp.at[flat].set(_pages(k, ps)),
                 vp.at[flat].set(_pages(v, ps)))
                for (kp, vp), (k, v) in zip(pools, fresh)]

    def decode(self, params, token, pos, pools, tables, lens, live):
        caches = [PagedKVCache(k, v, tables, lens) for (k, v) in pools]
        logits, new = self.model.apply(params, token[:, None], pos[:, None],
                                       kv_caches=caches)
        return logits[:, 0], [(c.k_pool, c.v_pool) for c in new]


# Tokens one prefill dispatch of a hybrid family may hold: a row of the
# largest bucket alone, eight rows of 2,048: the feed-forward's (tokens,
# 2 x d_ff) intermediate is 0.67 GB at 16,384 tokens and d_ff 10,240.
_PREFILL_TOKENS = 16384


def _rows_under_the_token_cap(bucket: int, max_batch: int,
                              tokens: int = _PREFILL_TOKENS) -> int:
    return max(1, min(BATCH_PREFILL_WIDTH, max_batch, tokens // bucket))


def sambay_block(cfg: SambaYConfig) -> int:
    """Positions of one dispatch of a `sambay` prompt's carried layers
    (`SambaYServing.prefill_from_host`): whole windows, so that a ring in
    slot order is the window before the next block.  ONE window: device ms
    of the sequence a prompt takes (blocks, then the tail) at published
    widths, from a profiler trace (`scripts/tpu_kernel_sweep.py --prefill
    sambay`, TPU v5 lite, PR 56), the longest row at 75% | 100% of its
    bucket, beside the parent's whole-bucket program at any fill:

        rows x bucket    whole    1 window (512)   2 (1,024)      4 (2,048)
        1 x 2,048         67.6    50.4 |  65.3    66.7 |  66.7    68.3 |  68.3
        1 x 4,096        141.6    95.3 | 125.2    97.3 | 127.9   131.0 | 131.0
        1 x 8,192        297.0   185.1 | 245.0   189.3 | 250.5   193.9 | 256.7
        1 x 16,384       584.0   365.0 | 484.7   373.4 | 495.8   382.6 | 508.1
        2 x 8,192        573.8   383.3 | 508.9   388.7 | 516.1   407.3 | 541.0
        one block         -      14.96 (2 rows: 31.41)   30.61    62.76

    A position costs no less in a longer block (29.2 / 29.9 / 30.6 us), a
    longer block computes more of what no prompt holds (a prompt of 513
    tokens pays for two blocks of 512 or one of 2,048), and the host's
    dispatches cost the chip nothing it can see (the sequence's wall time
    is 5-6 ms over its device time at every block and length: the tail's
    fetch).  The tail takes 5.4-6.2 ms."""
    return cfg.window


class SambaYServing:
    """`models/sambay.py`: pages of ONE layer's K/V (read by every cross
    layer), a ring of `window` tokens for each window layer, and (conv
    window, scan state) for each Mamba layer."""

    rewinds = False
    portable_kv = False

    def __init__(self, cfg: SambaYConfig, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        self.model = SambaYModel(cfg)
        # the full layer and the cross layers behind it
        self.pool_readers = 1 + len(cfg.layers_of("cross"))
        self.state_bytes_per_slot = _fixed_bytes_per_slot(
            self, lambda s: (s["rings"], s["mamba"]))
        # Positions of one dispatch of a prompt's carried layers.
        self.block = sambay_block(cfg)
        model = self.model

        def fresh(W):
            kv = (W, cfg.kv_pairs, self.block, 2 * cfg.head_dim)
            return model.fresh_state(W), (jnp.zeros(kv, cfg.dtype),
                                          jnp.zeros(kv, cfg.dtype))

        def prompt_block(params, tokens, start, last_idx, state):
            return model.apply(params, tokens, start, last_idx, state,
                               method=SambaYModel.prompt_block)

        def prompt_tail(bucket, params, state, kv, last_idx):
            k, v = (jnp.concatenate(a, axis=2)[:, :, :bucket]
                    for a in zip(*kv))
            return model.apply(params, state, k, v, last_idx,
                               method=SambaYModel.prompt_tail), (k, v)

        # a group's state before its first block, and a block of K and V
        # that no row reached
        self._fresh = jax.jit(fresh, static_argnums=0)
        self._block = jax.jit(prompt_block, donate_argnums=4)
        self._tail = jax.jit(prompt_tail, static_argnums=0)

    def prefill_width(self, bucket: int, max_batch: int) -> int:
        return _rows_under_the_token_cap(bucket, max_batch)

    def prompt_pages(self, bucket: int, page_size: int) -> int:
        return bucket // page_size

    def init_state(self, max_batch: int, num_pages: int, page_size: int):
        c = self.cfg
        # (every leaf a buffer of its own: the state is donated)
        pool = lambda: jnp.zeros(  # noqa: E731
            (num_pages, c.kv_pairs, page_size, 2 * c.head_dim), c.dtype)
        # fixed per slot: what a prompt's blocks carry along a row
        rows = self.model.fresh_state(max_batch)
        return {"pool": (pool(), pool()), "rings": rows["rings"],
                "mamba": rows["mamba"]}

    def prompt_blocks(self, lengths) -> int:
        """Blocks the longest of a group's prompts has: how many times
        `prefill_from_host` dispatches the block's program."""
        return -(-max(lengths) // self.block)

    def prefill_computed(self, bucket: int, lengths) -> int:
        return len(lengths) * self.prompt_blocks(lengths) * self.block

    def prefill_from_host(self, params, tokens, last_idx):
        """The loop over a prompt's blocks, on the host: `tokens` (W,
        bucket) and `last_idx` (W,) as numpy arrays.  ONE program a width,
        "a block of positions through layers 0 .. L/2 after the state
        before it" (`SambaYModel.prompt_block`; the block's first position
        is a traced scalar), dispatched as many times as the longest row
        has blocks, each taking the state the one before returned
        (donated); then ONE program a (width, bucket) that holds no such
        layer (`prompt_tail` over the blocks' K and V laid end to end).
        Nothing is fetched in between, and the first dispatch (the state
        before the first block: zeros) is made at once."""
        W, bucket = tokens.shape
        state, nothing = self._fresh(W)
        block = self.block
        n = self.prompt_blocks(last_idx + 1)
        padded = np.zeros((W, n * block), np.int32)
        padded[:, :min(bucket, n * block)] = tokens[:, :n * block]
        last = jnp.asarray(last_idx)
        kv = []
        for b in range(n):
            state, got = self._block(
                params, padded[:, b * block:(b + 1) * block],
                np.int32(b * block), last, state)
            kv.append(got)
        # (what lies past the longest row's last block: zeros, which the
        # tail's mask hides and `write_prompt` sends to the dummy page)
        kv += [nothing] * (-(-bucket // block) - n)
        logits, cache = self._tail(bucket, params, state, kv, last)
        return logits, {"mamba": state["mamba"], "rings": state["rings"],
                        "cache": cache}

    def write_prompt(self, state, fresh, slots, page_ids):
        flat = page_ids.reshape(-1)
        kp, vp = state["pool"]
        ps = kp.shape[2]
        k, v = fresh["cache"]
        put = lambda old, new: old.at[slots].set(  # noqa: E731
            new, mode="drop")
        return {
            "pool": (kp.at[flat].set(_pages(k, ps)),
                     vp.at[flat].set(_pages(v, ps))),
            "rings": [tuple(map(put, old, new)) for old, new in
                      zip(state["rings"], fresh["rings"])],
            "mamba": [tuple(map(put, old, new)) for old, new in
                      zip(state["mamba"], fresh["mamba"])]}

    def decode(self, params, token, pos, state, tables, lens, live):
        # no position embedding of any kind: `pos` is not read
        return self.model.apply(params, token, state, tables, lens, live,
                                method=SambaYModel.decode)


class GraniteHybridServing:
    """`models/granite_hybrid.py`: a (k, v) pool for each attention layer,
    all under one table row a sequence (as `LlamaServing`), and (conv
    window, state) for each Mamba-2 layer, fixed per slot (as
    `SambaYServing.mamba`; the state of one layer is `mamba_heads` x 64 x
    128 float32, 2 MB at Granite-4.0-H-Micro's 64 heads and 4 MB at
    -Small's 128, so the slots an engine can hold are bounded by
    `state_bytes_per_slot`, not by `kv_pool_tokens`).  Heads of 64 lie in
    the pools two to a kernel's head of 128, heads of 128 as they are
    (`GraniteHybridConfig.kv_pool_heads`).  With routed experts a decode
    step and a prefill count what the routed layers did, as
    `Lfm2MoeServing`'s do, and two counts more: the (row, expert) pairs
    the router made and those whose expert this chip holds."""

    rewinds = False
    portable_kv = False
    pool_readers = 1

    def __init__(self, cfg: GraniteHybridConfig, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        self.model = GraniteHybridModel(cfg)
        self.state_bytes_per_slot = _fixed_bytes_per_slot(
            self, lambda s: s["ssm"])
        if cfg.n_experts:
            # `models/granite_hybrid.EXPERT_COUNTS`, a step's over its
            # live rows, a prefill's over real tokens
            self.step_counters = (
                ("experts_touched", "sum"), ("expert_slots", "sum"),
                ("expert_rows_max", "max"), ("expert_pairs_held", "sum"),
                ("expert_pairs", "sum"))
            self.prefill_counters = (("expert_rows_max", "max"),
                                     ("expert_rows", "sum"))

    def prefill_width(self, bucket: int, max_batch: int) -> int:
        # With routed experts a token is `top_k` (row, expert) pairs of
        # float32 rows in and out of the grouped products, whichever chip
        # holds the expert (`lfm2_moe.expert_ffn`): ten pairs of 4,096 are
        # 0.5 MB a token and layer, so a dispatch holds a quarter of the
        # dense member's tokens.  (Since PR 54 the grouped kernel brings
        # the rows in itself and a pair held elsewhere moves nothing: the
        # cap rests on temporaries that are gone and is left where it was,
        # ROADMAP Speed 2.)
        return _rows_under_the_token_cap(
            bucket, max_batch,
            _PREFILL_TOKENS // 4 if self.cfg.n_experts else _PREFILL_TOKENS)

    def prompt_pages(self, bucket: int, page_size: int) -> int:
        return bucket // page_size

    def init_state(self, max_batch: int, num_pages: int, page_size: int):
        c = self.cfg
        B = max_batch
        heads, width = c.kv_pool_heads
        # (every leaf a buffer of its own: the state is donated)
        pool = lambda: jnp.zeros(  # noqa: E731
            (num_pages, heads, page_size, width), c.dtype)
        return {
            "pools": [(pool(), pool()) for _ in c.layers_of("attention")],
            "ssm": [(jnp.zeros((B, c.d_conv - 1, c.conv_dim), c.dtype),
                     jnp.zeros((B, c.mamba_heads, c.mamba_head_dim,
                                c.d_state), jnp.float32))
                    for _ in c.layers_of("mamba")]}

    def prefill(self, params, tokens, last_idx):
        out = self.model.apply(params, tokens, last_idx,
                               method=GraniteHybridModel.prefill)
        return (*out[:2], out[2][2:4]) if self.cfg.n_experts else out

    def write_prompt(self, state, fresh, slots, page_ids):
        flat = page_ids.reshape(-1)
        ps = state["pools"][0][0].shape[2]
        put = lambda old, new: old.at[slots].set(  # noqa: E731
            new, mode="drop")
        return {
            "pools": [(kp.at[flat].set(_pages(k, ps)),
                       vp.at[flat].set(_pages(v, ps)))
                      for (kp, vp), (k, v) in zip(state["pools"],
                                                  fresh["kv"])],
            "ssm": [tuple(map(put, old, new)) for old, new in
                    zip(state["ssm"], fresh["ssm"])]}

    def decode(self, params, token, pos, state, tables, lens, live):
        # (with routed experts the model's counts follow, in
        # `step_counters`' order: the held pairs are its `expert_rows`)
        return self.model.apply(params, token, pos, state, tables, lens,
                                live, method=GraniteHybridModel.decode)


class Lfm2MoeServing:
    """`models/lfm2_moe.py`: a (k, v) pool for each attention layer, all
    under one table row a sequence (as `GraniteHybridServing`), and the
    short conv's window for each conv layer, float32 and fixed per slot
    (16 KB a layer at the published sizes: slots are nearly free, and what a decode step
    costs is the experts its live rows touch, which the programs count)."""

    rewinds = False
    portable_kv = False
    pool_readers = 1
    # `models/lfm2_moe.EXPERT_COUNTS`: the first three of a decode step
    # (over its live rows), the last two of a prefill (over real tokens)
    step_counters = (("experts_touched", "sum"), ("expert_slots", "sum"),
                     ("expert_rows_max", "max"))
    prefill_counters = (("expert_rows_max", "max"), ("expert_rows", "sum"))

    def __init__(self, cfg: Lfm2MoeConfig, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        self.model = Lfm2MoeModel(cfg)
        self.state_bytes_per_slot = _fixed_bytes_per_slot(
            self, lambda s: s["conv"])

    def prefill_width(self, bucket: int, max_batch: int) -> int:
        # Half the other hybrids' tokens a dispatch: a token is four
        # (row, expert) pairs of two terms each in the grouped products,
        # 2.4 GB of temporaries at 8,192 tokens and 4.8 at 16,384, beside
        # 10.4 GB of weights (compiled for a described v5e, PR 42; the pair
        # rows are no longer laid out around the kernel, PR 54: as above).
        return _rows_under_the_token_cap(bucket, max_batch,
                                         _PREFILL_TOKENS // 2)

    def prompt_pages(self, bucket: int, page_size: int) -> int:
        return bucket // page_size

    def init_state(self, max_batch: int, num_pages: int, page_size: int):
        c = self.cfg
        # (every leaf a buffer of its own: the state is donated)
        pool = lambda: jnp.zeros(  # noqa: E731
            (num_pages, c.n_kv_heads // 2, page_size, 2 * c.head_dim),
            c.dtype)
        return {
            "pools": [(pool(), pool())
                      for _ in c.layers_of("full_attention")],
            "conv": [jnp.zeros((max_batch, c.conv_L - 1, c.d_model),
                               jnp.float32)
                     for _ in c.layers_of("conv")]}

    def prefill(self, params, tokens, last_idx):
        logits, fresh, counts = self.model.apply(
            params, tokens, last_idx, method=Lfm2MoeModel.prefill)
        return logits, fresh, counts[2:]

    def write_prompt(self, state, fresh, slots, page_ids):
        flat = page_ids.reshape(-1)
        ps = state["pools"][0][0].shape[2]
        return {
            "pools": [(kp.at[flat].set(_pages(k, ps)),
                       vp.at[flat].set(_pages(v, ps)))
                      for (kp, vp), (k, v) in zip(state["pools"],
                                                  fresh["kv"])],
            "conv": [old.at[slots].set(new, mode="drop")
                     for old, new in zip(state["conv"], fresh["conv"])]}

    def decode(self, params, token, pos, state, tables, lens, live):
        logits, state, counts = self.model.apply(
            params, token, pos, state, tables, lens, live,
            method=Lfm2MoeModel.decode)
        return logits, state, counts[:3]


class MlaMoeServing:
    """`models/mla_moe.py`: ONE pool a layer of latent rows, (pages, page,
    `latent_row`), shared by every head and under one table row a sequence;
    nothing fixed per slot, so a step may be run again.  A token holds
    `latent_dim` values a layer (576: 1,152 bytes in bfloat16, a fourteenth
    of sixteen K/V heads of 128) in a row padded to whole lanes (640)."""

    rewinds = True
    portable_kv = False     # (a hand-over carries (k, v) pairs a layer)
    pool_readers = 1
    state_bytes_per_slot = 0
    # `models/lfm2_moe.EXPERT_COUNTS` as `Lfm2MoeServing` names them, and
    # what the step's latent kernels read: the tokens resident under its
    # occupied rows (one layer's; summed over a chunk's steps)
    step_counters = (("experts_touched", "sum"), ("expert_slots", "sum"),
                     ("expert_rows_max", "max"), ("latent_tokens", "sum"))
    prefill_counters = (("expert_rows_max", "max"), ("expert_rows", "sum"))

    def __init__(self, cfg: MlaMoeConfig, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        self.model = MlaMoeModel(cfg)

    def prefill_width(self, bucket: int, max_batch: int) -> int:
        # A token is six (row, expert) pairs of two terms each in the
        # grouped products: 8,192 tokens a dispatch, as `Lfm2MoeServing`.
        return _rows_under_the_token_cap(bucket, max_batch,
                                         _PREFILL_TOKENS // 2)

    def prompt_pages(self, bucket: int, page_size: int) -> int:
        return bucket // page_size

    def init_state(self, max_batch: int, num_pages: int, page_size: int):
        c = self.cfg
        # (every leaf a buffer of its own: the state is donated)
        return [jnp.zeros((num_pages, page_size, c.latent_row), c.dtype)
                for _ in range(c.n_layers)]

    def prefill(self, params, tokens, last_idx):
        logits, rows, counts = self.model.apply(
            params, tokens, last_idx, method=MlaMoeModel.prefill)
        return logits, rows, counts[2:]

    def prefill_computed(self, bucket: int, lengths) -> int:
        return prompt_blocks.positions_computed(
            bucket, prompt_blocks.rows_of_a_block(self.cfg.dtype, 2, bucket),
            lengths)

    def write_prompt(self, pools, fresh, slots, page_ids):
        # rows (W, L, row) cut into pages, in the order of a flattened
        # (W, L / page): padding names the dummy page
        flat = page_ids.reshape(-1)
        page = pools[0].shape[1:]
        return [pool.at[flat].set(rows.reshape(-1, *page))
                for pool, rows in zip(pools, fresh)]

    def decode(self, params, token, pos, pools, tables, lens, live):
        occupied = lens > 0 if live is None else live
        logits, pools, counts = self.model.apply(
            params, token, pos, pools, tables, lens, occupied,
            method=MlaMoeModel.decode)
        read = jnp.sum(jnp.where(occupied, lens + 1, 0))
        return logits, pools, jnp.concatenate(
            [counts[:3], read.astype(jnp.int32)[None]])


class MiniCpmSalaServing:
    """`models/minicpm_sala.py`: a sparse layer holds a (k, v) pool with one
    row a (page, K/V head), `page * Hkv + head`, so that a decode step's
    kernel reads for each K/V head the pages its own selection gathered; a
    float32 pool of compressed keys, one row a page, under the same table
    row; and,
    fixed per slot, the sums of the two key segments still open.  A
    lightning layer holds its state S (H, 128, 128) float32, fixed per slot
    (2 MiB a layer at the published sizes, as `GraniteHybridServing`'s).
    A page IS a block of the selection: `page_size` must be the
    configuration's `block_size`."""

    rewinds = False
    portable_kv = False
    pool_readers = 1
    # `models/minicpm_sala.STEP_COUNTS`: over a step's live rows and its
    # (sparse layer, K/V head) tables; a prefill counts its prompts past
    # `dense_len`
    step_counters = tuple((name, "sum") for name in STEP_COUNTS)
    prefill_counters = (("sparse_prompts", "sum"),)

    def __init__(self, cfg: MiniCpmSalaConfig, max_len: int):
        self.cfg, self.max_len = cfg, max_len
        self.model = MiniCpmSalaModel(cfg)
        self.state_bytes_per_slot = _fixed_bytes_per_slot(
            self, lambda s: (s["open"], s["lightning"]), cfg.block_size)

    def prefill_width(self, bucket: int, max_batch: int) -> int:
        # One program a bucket: the prompts are long (a 32,768 bucket alone
        # is the tokens eight rows of another family's are), and the
        # clients of a closed loop arrive one at a time.
        return 1

    def prompt_pages(self, bucket: int, page_size: int) -> int:
        return -(-bucket // page_size)

    def init_state(self, max_batch: int, num_pages: int, page_size: int):
        c = self.cfg
        if page_size != c.block_size:
            raise ValueError(
                f"page_size={page_size}: a page is a block of the selection "
                f"(block_size {c.block_size})")
        # (every leaf a buffer of its own: the state is donated)
        pool = lambda: jnp.zeros(  # noqa: E731
            (num_pages * c.n_kv_heads, 1, page_size, c.head_dim), c.dtype)
        sparse, lightning = c.layers_of(SPARSE), c.layers_of(LIGHTNING)
        return {
            "pools": [(pool(), pool()) for _ in sparse],
            "cpools": [jnp.zeros((num_pages, c.ckey_row), jnp.float32)
                       for _ in sparse],
            "open": [jnp.zeros((max_batch, c.n_kv_heads, 2, c.head_dim),
                               jnp.float32) for _ in sparse],
            "lightning": [jnp.zeros((max_batch, c.n_heads, c.head_dim,
                                     c.head_dim), jnp.float32)
                          for _ in lightning]}

    def prefill(self, params, tokens, last_idx):
        return self.model.apply(params, tokens, last_idx,
                                method=MiniCpmSalaModel.prefill)

    def prefill_computed(self, bucket: int, lengths) -> int:
        return prompt_blocks.positions_computed(bucket, self.cfg.row_block,
                                                lengths)

    def write_prompt(self, state, fresh, slots, page_ids):
        c = self.cfg
        flat = page_ids.reshape(-1)
        ps = state["pools"][0][0].shape[2]
        rows = (flat[:, None] * c.n_kv_heads
                + jnp.arange(c.n_kv_heads)[None]).reshape(-1)
        # (a bucket that is no whole pages: its last page is padded)
        paged = lambda a: _pages(jnp.pad(  # noqa: E731
            a, ((0, 0), (0, 0), (0, -a.shape[2] % ps), (0, 0))), ps) \
            .reshape(-1, 1, ps, c.head_dim)
        put = lambda old, new: old.at[slots].set(  # noqa: E731
            new, mode="drop")
        return {
            "pools": [(kp.at[rows].set(paged(k)), vp.at[rows].set(paged(v)))
                      for (kp, vp), (k, v) in zip(state["pools"],
                                                  fresh["kv"])],
            "cpools": [pool.at[flat].set(ck.reshape(-1, c.ckey_row))
                       for pool, ck in zip(state["cpools"], fresh["ckeys"])],
            "open": list(map(put, state["open"], fresh["open"])),
            "lightning": list(map(put, state["lightning"],
                                  fresh["lightning"]))}

    def decode(self, params, token, pos, state, tables, lens, live):
        return self.model.apply(
            params, token, pos, state, tables, lens,
            lens > 0 if live is None else live,
            method=MiniCpmSalaModel.decode)


_FAMILIES = {LlamaConfig: LlamaServing, SambaYConfig: SambaYServing,
             GraniteHybridConfig: GraniteHybridServing,
             Lfm2MoeConfig: Lfm2MoeServing, MlaMoeConfig: MlaMoeServing,
             MiniCpmSalaConfig: MiniCpmSalaServing}


def family_of(cfg, max_len: int):
    """The serving family of a model configuration."""
    for kind, family in _FAMILIES.items():
        if isinstance(cfg, kind):
            return family(cfg, max_len)
    raise TypeError(
        f"LLMEngine serves {[k.__name__ for k in _FAMILIES]}; got "
        f"{type(cfg).__name__} (a new family is a class in "
        "ray_tpu/serve/llm_families.py)")
