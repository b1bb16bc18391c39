"""A prefill stops at its prompt's end (`ray_tpu/ops/prompt_blocks.py`): the
helper's own cases, and the dense and `mla_moe` prefills in blocks against
the form that runs the whole bucket at once, bit for bit (CPU, the tiny
models of `tests/tiny_families.py`).

Bit for bit, on this backend, under one condition the cases keep: a product
whose result is narrower than 64 columns (a K/V projection of 32) comes out
of XLA:CPU with other last bits under 64 rows than over them, whatever cuts
the rows, so a block times the rows of a group is 64 here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import prompt_blocks
from ray_tpu.ops.prompt_blocks import in_blocks, positions_computed


def _primitives(jaxpr) -> set:
    """The primitives of a program, a kernel's own body left aside."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names |= _primitives(sub)
    return names


# ---- the helper alone (out of `models/minicpm_sala.py`, PR 49) -----------


def _walk(carry, xb, pos):
    """A row-wise function that owns up to where it ran: the carry counts
    the blocks computed, y is x + its position + 1 (never zero)."""
    return carry + 1, (xb["a"] + pos[..., None] + 1.0, pos)


@pytest.mark.parametrize("S, block, last, start, ran", [
    (32, 8, [31], 0, 4),            # a prompt that fills its rows
    (32, 8, [7], 0, 1),             # ends on a block's last position
    (32, 8, [8], 0, 2),             # ... and one past it
    (32, 8, [0], 0, 1),             # one token
    (32, 8, [3, 20, 9], 0, 3),      # a group: skipped past EVERY row only
    (30, 8, [29], 0, 4),            # no whole blocks: the last runs past S
    (30, 8, [12, 2], 0, 2),
    (32, 8, [40], 16, 4),           # positions from `start`
    (32, 8, [23], 16, 1),
], ids=["full", "edge", "edge+1", "one", "group", "ragged", "ragged-group",
        "start", "start-edge"])
def test_blocks_past_every_rows_end_are_not_computed(S, block, last, start,
                                                     ran):
    B = len(last)
    x = {"a": jnp.arange(B * S * 2, dtype=jnp.float32).reshape(B, S, 2)}
    carry, (y, pos) = jax.jit(
        lambda x, last: in_blocks(_walk, 0, x, block, last, start))(
            x, jnp.asarray(last))
    assert int(carry) == ran
    reached = min(ran * block, S)
    want = np.asarray(x["a"]) + (start + np.arange(S))[None, :, None] + 1.0
    assert y.shape == (B, S, 2) and pos.shape == (B, S)
    np.testing.assert_array_equal(y[:, :reached], want[:, :reached])
    np.testing.assert_array_equal(
        pos[:, :reached], np.broadcast_to(start + np.arange(reached),
                                          (B, reached)))
    # a skipped block's results are zeros, whatever f would have given
    assert not np.asarray(y[:, reached:]).any()
    assert not np.asarray(pos[:, reached:]).any()


@pytest.mark.parametrize("S, block", [(16, 16), (8, 16), (16, 1 << 30)])
def test_rows_no_longer_than_a_block_run_once_with_no_scan(S, block):
    """... so that a short bucket's program is what it was without the
    helper: f once over the rows as they are, no scan, no cond."""
    x = {"a": jnp.ones((2, S, 2))}
    jaxpr = jax.make_jaxpr(lambda x, last: in_blocks(
        _walk, 0, x, block, last))(x, jnp.asarray([3, 0]))
    assert not _primitives(jaxpr.jaxpr) & {"scan", "while", "cond"}
    carry, (y, _) = in_blocks(_walk, 0, x, block, jnp.asarray([3, 0]))
    assert int(carry) == 1 and np.asarray(y).all()
    longer = jax.make_jaxpr(lambda x, last: in_blocks(
        _walk, 0, x, S // 2, last))(x, jnp.asarray([3, 0]))
    assert {"scan", "cond"} <= _primitives(longer.jaxpr)


@pytest.mark.parametrize("bucket, block, lengths, want", [
    (2048, 512, [1120], 1536), (2048, 512, [1024], 1024),
    (2048, 512, [1025], 1536), (2048, 512, [2048], 2048),
    (2048, 512, [1], 512), (2048, 256, [700, 1100, 300], 3 * 1280),
    (512, 512, [100], 512), (256, 512, [100, 7], 512),
    (33280, 2048, [33000], 17 * 2048)])
def test_positions_computed_are_the_reached_blocks_of_every_row(
        bucket, block, lengths, want):
    assert positions_computed(bucket, block, lengths) == want
    # ... which is what the helper runs
    if bucket > block:
        blocks, _ = in_blocks(
            lambda c, xb, pos: (c + 1, xb), 0,
            jnp.zeros((len(lengths), bucket, 1)), block,
            jnp.asarray(lengths) - 1)
        assert int(blocks) * block * len(lengths) == want


def test_the_block_follows_from_the_weights_type_and_the_terms():
    """Nothing else chooses it: no option, no model's name, and a group of
    rows goes the way a row alone does."""
    import inspect

    assert list(inspect.signature(
        prompt_blocks.rows_of_a_block).parameters) == [
            "weight_dtype", "terms", "positions"]
    one = prompt_blocks.rows_of_a_block(jnp.bfloat16, 1, 8192)
    two = prompt_blocks.rows_of_a_block(jnp.bfloat16, 2, 8192)
    assert one % 128 == 0 and two % 128 == 0 and two <= one
    # rows of no more than two blocks are one (their second is never
    # skipped), and rows of no more than 1,024 positions (a scanning
    # program's price at a warm start); longer ones are cut
    for block, terms in ((one, 1), (two, 2)):
        for positions in (16, block, 2 * block, 1024):
            assert prompt_blocks.rows_of_a_block(
                jnp.bfloat16, terms, positions) == positions
        for positions in (2048, 4096, 8192):
            assert prompt_blocks.rows_of_a_block(
                jnp.bfloat16, terms, positions) == block


def test_like_layers_share_one_trace_and_only_inside_one_program():
    """`traced_once`: the program that asked traces the function once for
    all its calls at one shape; the next program traces it anew, so
    nothing patched between two programs of a process is missed."""
    traced = []

    def double(x):
        traced.append(x.shape)
        return 2 * x

    def program(x):
        shared = prompt_blocks.traced_once(double)
        return shared(x) + shared(x + 1) + shared(x[:1]).sum()

    assert float(jax.jit(program)(jnp.ones(3))[0]) == 8.0
    assert traced == [(3,), (1,)]
    jax.jit(lambda x: program(x) + 1)(jnp.ones(3))
    assert traced == [(3,), (1,)] * 2


# ---- the two families' prefills in blocks against the whole bucket -------

BUCKET = 128
# (lengths of a group's rows, the block): a block times the rows is 64
CASES = {
    "one-row": ([29], 64), "fills-its-bucket": ([128], 64),
    "ends-on-a-blocks-edge": ([64], 64), "edge-and-one": ([65], 64),
    "a-group": ([100, 29, 1], 32), "a-group-that-fills": ([128, 64], 32),
    "a-group-on-an-edge": ([32, 31], 32),
}


def _family(name):
    from tests import tiny_families

    tiny = getattr(tiny_families, name)
    from ray_tpu.serve.llm_families import family_of

    return tiny, family_of(tiny.cfg, BUCKET)


def _prefill(monkeypatch, serving, tiny, tokens, last, block):
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block",
                        lambda *a, **k: block)
    return jax.jit(serving.prefill)(tiny.params, tokens, last)


def _rows(lengths, vocab):
    tokens = np.zeros((len(lengths), BUCKET), np.int32)
    rng = np.random.default_rng(sum(lengths))
    for r, n in enumerate(lengths):
        tokens[r, :n] = rng.integers(1, vocab, n)
    return jnp.asarray(tokens), jnp.asarray(lengths, jnp.int32) - 1


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("name", ["dense", "mla_moe"])
def test_a_prefill_in_blocks_is_the_whole_buckets(monkeypatch, name, case):
    """Last-token logits, every cached row at a position a prompt holds and
    (`mla_moe`) what the routed layers counted: bit for bit those of the
    form that computes the whole bucket (a block no shorter than it: one
    call of every row-wise function, no scan).  (Both families' projections
    run whole: past the prompts' end the cached rows are what the whole
    bucket's are.)"""
    lengths, block = CASES[case]
    tiny, serving = _family(name)
    tokens, last = _rows(lengths, tiny.cfg.vocab_size)
    want = _prefill(monkeypatch, serving, tiny, tokens, last, 1 << 30)
    got = _prefill(monkeypatch, serving, tiny, tokens, last, block)
    np.testing.assert_array_equal(got[0], want[0])
    assert got[0].shape == (len(lengths), tiny.cfg.vocab_size)
    for a, b in zip(got[2:], want[2:]):
        np.testing.assert_array_equal(a, b)
    reached = -(-max(lengths) // block) * block
    along = 1 if name == "mla_moe" else 2       # (B, S, row); (B, H, S, D)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        a, b = np.moveaxis(a, along, 1), np.moveaxis(b, along, 1)
        assert a.shape[1] == BUCKET
        for r, n in enumerate(lengths):
            np.testing.assert_array_equal(a[r, :n], b[r, :n])
        assert reached == BUCKET or b[:, reached:].any()


@pytest.mark.parametrize("lengths", [[29], [128], [64, 65, 1]])
def test_the_dense_prefill_is_the_whole_forward_at_each_rows_last_token(
        monkeypatch, lengths):
    """... and against `FRESH_KV`, the form with no lengths at all (every
    position's logits, the kernel over the whole bucket): the head on one
    position a row gives that position's logits, and K/V are the same at
    every position a prompt holds."""
    from ray_tpu.models.llama import FRESH_KV

    tiny, serving = _family("dense")
    tokens, last = _rows(lengths, tiny.cfg.vocab_size)
    whole, fresh = jax.jit(lambda p, t: serving.model.apply(
        p, t, jnp.arange(BUCKET)[None], kv_caches=FRESH_KV))(tiny.params,
                                                             tokens)
    assert whole.shape == (len(lengths), BUCKET, tiny.cfg.vocab_size)
    got, kv = _prefill(monkeypatch, serving, tiny, tokens, last,
                       64 if len(lengths) == 1 else 32)
    for r, n in enumerate(lengths):
        np.testing.assert_array_equal(got[r], whole[r, n - 1])
        for (k, v), (wk, wv) in zip(kv, fresh):
            np.testing.assert_array_equal(k[r, :, :n], wk[r, :, :n])
            np.testing.assert_array_equal(v[r, :, :n], wv[r, :, :n])


def _count(jaxpr, primitive: str) -> int:
    """... and how often one stands in it."""
    return sum((eqn.primitive.name == primitive) + sum(
        _count(sub, primitive)
        for sub in jax.core.jaxprs_in_params(eqn.params)
        if eqn.primitive.name != "pallas_call") for eqn in jaxpr.eqns)


@pytest.mark.parametrize("name", ["dense", "mla_moe"])
def test_a_bucket_no_longer_than_a_block_scans_nothing(monkeypatch, name):
    """The rule's own block (hundreds of positions) against the tiny
    engines' buckets: the program is the one a block without end gives,
    every row-wise function called once; in blocks it holds a scan over a
    cond more for each layer (what follows its attention); and no array of
    either holds (rows,
    bucket, vocabulary) (at a vocabulary no other width of the tiny model
    is)."""
    import dataclasses

    from ray_tpu.serve.llm_families import family_of

    tiny, _ = _family(name)
    cfg = dataclasses.replace(tiny.cfg, vocab_size=72)
    serving, params = family_of(cfg, BUCKET), tiny.make(cfg)
    tokens, last = _rows([100, 29], cfg.vocab_size)
    trace = lambda: jax.make_jaxpr(serving.prefill)(  # noqa: E731
        params, tokens, last)
    own = trace()
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block",
                        lambda *a, **k: 1 << 30)
    assert str(trace()) == str(own)
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block", lambda *a, **k: 32)
    blocked = trace()
    for primitive in ("scan", "cond"):
        assert _count(blocked.jaxpr, primitive) == \
            _count(own.jaxpr, primitive) + cfg.n_layers

    def shapes(j):
        for eqn in j.eqns:
            yield from (v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    for j in (own, blocked):
        assert not [s for s in shapes(j.jaxpr)
                    if len(s) == 3 and s[-1] == cfg.vocab_size and s[1] > 1]


@pytest.mark.parametrize("name, lengths, computed", [
    ("dense", [100, 29], 2 * 128), ("dense", [64], 64), ("dense", [65], 96),
    ("mla_moe", [33, 7, 1], 3 * 64), ("mla_moe", [128], 128)])
def test_a_family_says_what_its_prefill_computes(monkeypatch, name, lengths,
                                                 computed):
    """`prefill_computed`: host arithmetic on the lengths, at the block the
    program itself takes."""
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block",
                        lambda *a, **k: 32)
    _, serving = _family(name)
    assert serving.prefill_computed(BUCKET, lengths) == computed
    monkeypatch.undo()
    assert serving.prefill_computed(BUCKET, lengths) == BUCKET * len(lengths)


def test_the_prefill_sweep_times_programs_the_cells_engines_run():
    """`scripts/tpu_kernel_sweep.py --prefill` takes its widths from the
    cells' configuration files, and each (rows, bucket) it times is a
    program that cell's engine runs: one row, or the width the family gives
    the bucket; the blocks it tries bracket the rule's (`sambay`, which
    prefills from the host a block a dispatch, is timed as that sequence at
    the buckets its cell's long prompts fall in, at blocks that bracket
    the family's own)."""
    import importlib
    import json
    import os
    import sys

    from ray_tpu.serve.llm_families import family_of

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "scripts"))
    try:
        sweep = importlib.import_module("tpu_kernel_sweep")
    finally:
        sys.path.remove(os.path.join(repo, "scripts"))
    assert set(sweep.PREFILL_FAMILIES) - set(sweep.PREFILL_AT) == {"sambay"}
    for family, (name, cases) in sweep.PREFILL_FAMILIES.items():
        with open(os.path.join(repo, "benchmarks", "configs", name)) as f:
            conf = json.load(f)
        assert conf.get("family", "dense_decoder") == family
        adapter = importlib.import_module(f"benchmarks.families.{family}")
        engine = conf["serve"]["engine"]
        serving = family_of(adapter.program_config(adapter.sizes(conf)),
                            engine["max_len"])
        for rows, bucket in cases:
            assert bucket <= engine["max_len"] and bucket & (bucket - 1) == 0
            assert rows in (1, serving.prefill_width(bucket,
                                                     engine["max_batch"]))
        if family not in sweep.PREFILL_AT:
            assert not hasattr(serving, "prefill")
            assert serving.block in sweep.PREFILL_HOST_BLOCKS
            assert serving.prefill_computed(1 << 20, [1]) == serving.block
            continue
        block = serving.prefill_computed(1 << 20, [1])  # (a row's first)
        assert min(sweep.PREFILL_BLOCKS) <= block <= max(sweep.PREFILL_BLOCKS)
        assert sweep.PREFILL_AT[family] in sweep.PREFILL_BLOCKS
    assert 1.0 in sweep.PREFILL_FILLS and 1.0 in sweep.PREFILL_HOST_FILLS
    assert (1, 16384) in sweep.PREFILL_FAMILIES["sambay"][1]


def _planted_in_a_prefill():
    from benchmarks.tools.mla_moe_faults import FAULTS

    return [*FAULTS, "latents_kept_to_8_bits"]


@pytest.mark.parametrize("fault", _planted_in_a_prefill())
def test_a_fault_planted_after_a_sound_prefill_is_in_the_next_one(
        monkeypatch, fault):
    """`benchmarks/tools/mla_moe_faults.py` serves the sound engine first
    and plants each fault after it, in the same process, by patching a name
    the prefill's layers call: the next engine's prefill program (in
    blocks, its like layers sharing one trace) must be traced as patched,
    so the fault shows in the PREFILL's own logits, at the last token of a
    row and of a group; and with the patch gone the program is sound
    again."""
    from benchmarks.tools.mla_moe_faults import planted

    tiny, serving = _family("mla_moe")
    tokens, last = _rows([100, 29], tiny.cfg.vocab_size)
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block",
                        lambda *a, **k: 32)

    def an_engines_prefill():
        # (an engine jits a function of its own: `serve/llm.py`)
        return np.asarray(jax.jit(lambda p, t, at: serving.prefill(p, t, at))(
            tiny.params, tokens, last)[0])

    sound = an_engines_prefill()
    with planted(fault):
        faulty = an_engines_prefill()
    assert np.abs(faulty - sound).max(-1).min() > 1e-3
    np.testing.assert_array_equal(an_engines_prefill(), sound)


def test_a_name_patched_between_two_dense_prefills_is_traced_as_patched(
        monkeypatch):
    """... and the dense family's: what its layers share (`_after_heads`)
    is one program's alone."""
    from unittest import mock

    from ray_tpu.models import llama

    tiny, serving = _family("dense")
    tokens, last = _rows([100, 29], tiny.cfg.vocab_size)
    monkeypatch.setattr(prompt_blocks, "rows_of_a_block",
                        lambda *a, **k: 32)

    def an_engines_prefill():
        return np.asarray(jax.jit(lambda p, t, at: serving.prefill(p, t, at))(
            tiny.params, tokens, last)[0])

    sound = an_engines_prefill()
    with mock.patch.object(llama.nn, "silu", jax.nn.relu):
        faulty = an_engines_prefill()
    assert np.abs(faulty - sound).max(-1).min() > 1e-3
    np.testing.assert_array_equal(an_engines_prefill(), sound)
