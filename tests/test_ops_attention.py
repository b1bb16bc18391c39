"""Kernel correctness: flash attention vs reference, ring attention on an
8-device CPU mesh (the SPMD fake backend, SURVEY.md §4)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from jax import shard_map
except ImportError:  # pre-jax.shard_map releases
    from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import flash_attention, mha_reference, ring_attention


def _rand_qkv(key, B=2, H=4, S=256, D=64, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, S, D), dtype)
    k = jax.random.normal(k2, (B, H, S, D), dtype)
    v = jax.random.normal(k3, (B, H, S, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, None, causal, 128, 128)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _grads(attn, q, k, v, do):
    """Gradients of sum(attn(q, k, v) * do) with respect to q, k and v."""
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32)
                       * do.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _f32(*xs):
    """The reference takes float32 copies, so that nothing in it rounds to
    the storage type (`mha_reference` returns its inputs' dtype)."""
    return tuple(x.astype(jnp.float32) for x in xs)


def _assert_grads_close(got, want, tol):
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol, rtol=tol, err_msg=name)


# Tolerances by storage type.  float32: the kernels against the plain
# reference differ by summation order only.  bfloat16: p and ds are rounded
# to bf16 before their matmuls and the results once more (2^-8 relative),
# against a reference that computes in float32 from the same bf16 inputs.
_GRAD_TOL = {jnp.float32: 1e-4, jnp.bfloat16: 4e-2}

# (S, blocks asked for): one block; lengths that are no multiple of the
# default block (100, and an odd one, 129: each runs as one block); a length
# whose block is halved until it divides (96: three blocks of 32); 4 and 3
# blocks a side, so that blocks above the diagonal are skipped and their
# index maps clamped; block_q != block_k ("rect": two diagonal blocks a key
# block, or half a one).
_GRAD_SHAPES = {
    "one_block": (128, (128, 128)),
    "len100_one_block": (100, (512, 512)),
    "len129_odd_one_block": (129, (512, 512)),
    "len96_block_halved": (96, (64, 64)),
    "blocks4x4": (256, (64, 64)),
    "blocks3x3_default_fit": (768, (512, 512)),
    "rect_q32_k64": (256, (32, 64)),
    "rect_q64_k32": (256, (64, 32)),
}


@pytest.mark.parametrize("shape", _GRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_grad_matches_reference(causal, dtype, shape):
    S, (bq, bk) = _GRAD_SHAPES[shape]
    key = jax.random.PRNGKey(1)
    q, k, v = _rand_qkv(key, B=2, H=2, S=S, D=32, dtype=dtype)
    do = jax.random.normal(jax.random.fold_in(key, 7), q.shape, dtype)
    got = _grads(lambda q, k, v: flash_attention(q, k, v, None, causal,
                                                 bq, bk), q, k, v, do)
    want = _grads(lambda q, k, v: mha_reference(q, k, v, causal=causal),
                  *_f32(q, k, v), do)
    assert all(g.dtype == dtype for g in got)
    _assert_grads_close(got, want, _GRAD_TOL[dtype])


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_grad_cross_lengths(causal):
    """Fewer queries than keys (Sq 128, Sk 256; positions count from the
    top left, as the forward has them): two query blocks, four key blocks."""
    key = jax.random.PRNGKey(4)
    q, _, _ = _rand_qkv(key, B=1, H=2, S=128, D=32)
    _, k, v = _rand_qkv(jax.random.fold_in(key, 1), B=1, H=2, S=256, D=32)
    do = jax.random.normal(jax.random.fold_in(key, 2), q.shape)

    got = _grads(lambda q, k, v: flash_attention(q, k, v, None, causal,
                                                 64, 64), q, k, v, do)
    want = _grads(lambda q, k, v: mha_reference(q, k, v, causal=causal),
                  q, k, v, do)
    _assert_grads_close(got, want, 1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_grad_as_llama_attention_calls_it(dtype):
    """K and V of 8 heads repeated to the 16 query heads before the call
    (`models/llama.py`, GQA): the gradient of `jnp.repeat` sums each
    group's dk and dv back onto its KV head."""
    key = jax.random.PRNGKey(6)
    B, Hq, Hkv, S, D = 1, 16, 8, 256, 32
    kq, kk, kv, kd = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, Hq, S, D), dtype)
    k = jax.random.normal(kk, (B, Hkv, S, D), dtype)
    v = jax.random.normal(kv, (B, Hkv, S, D), dtype)
    do = jax.random.normal(kd, q.shape, dtype)

    def gqa(attn):
        def call(q, k, v):
            rep = q.shape[1] // k.shape[1]
            return attn(q, jnp.repeat(k, rep, axis=1),
                        jnp.repeat(v, rep, axis=1))
        return call

    got = _grads(gqa(lambda q, k, v: flash_attention(q, k, v, None, True,
                                                     64, 64)), q, k, v, do)
    want = _grads(gqa(lambda q, k, v: mha_reference(q, k, v, causal=True)),
                  *_f32(q, k, v), do)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    # dk, dv are sums of two heads' bf16-rounded gradients
    _assert_grads_close(got, want, 2 * _GRAD_TOL[dtype])


def test_flash_grad_through_flash_on_mesh():
    """The train step's call under a mesh (`llama._flash_on_mesh`): the
    kernels run per shard inside a shard_map, batch over `fsdp` and heads
    over `tp`, and their outputs vary over the axes their operands do."""
    from ray_tpu.models.llama import _flash_on_mesh

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("fsdp", "tp"))
    key = jax.random.PRNGKey(8)
    q, k, v = _rand_qkv(key, B=2, H=4, S=256, D=32)
    do = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    on_mesh = NamedSharding(mesh, P("fsdp", "tp", None, None))

    def step_on_mesh(q, k, v, do):      # as `shard_train_step` traces a step
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _grads(_flash_on_mesh, q, k, v, do)

    got = jax.jit(step_on_mesh, in_shardings=(on_mesh,) * 4)(q, k, v, do)
    want = _grads(lambda q, k, v: mha_reference(q, k, v, causal=True),
                  q, k, v, do)
    _assert_grads_close(got, want, 1e-4)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_grad_through_ulysses(causal, monkeypatch):
    """`ulysses_attention` with the kernels on its inside: heads scattered
    over `sp`, the whole sequence gathered, gradients through both
    all-to-alls.  Off the chip it calls `mha_reference` where the chip calls
    `flash_attention`; the test puts the kernels (interpreted) there.  The
    Pallas interpreter refuses a kernel body outside any `pl.when` branch
    under a checked shard_map (the forward's, without the causal skip), so
    only the causal case checks that outputs vary as the operands do."""
    from ray_tpu.ops import attention

    calls = []

    def flash(q, k, v, sm_scale, causal):
        calls.append(causal)
        return flash_attention(q, k, v, sm_scale, causal, 64, 64)

    monkeypatch.setattr(attention, "mha_reference", flash)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("sp",))
    key = jax.random.PRNGKey(9)
    q, k, v = _rand_qkv(key, B=1, H=8, S=256, D=32)
    do = jax.random.normal(jax.random.fold_in(key, 5), q.shape)
    seq = P(None, None, "sp", None)
    ulysses = shard_map(
        functools.partial(attention.ulysses_attention, axis="sp",
                          causal=causal),
        mesh=mesh, in_specs=(seq,) * 3, out_specs=seq, check_vma=causal)
    got = jax.jit(lambda q, k, v, do: _grads(ulysses, q, k, v, do))(
        q, k, v, do)
    assert calls == [causal]
    want = _grads(lambda q, k, v: mha_reference(q, k, v, causal=causal),
                  q, k, v, do)
    _assert_grads_close(got, want, 1e-4)


def test_flash_bf16():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, None, True, 128, 128)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    assert len(jax.devices()) == 8
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("sp",))
    B, H, S, D = 2, 2, 256, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), B=B, H=H, S=S, D=D)

    ring = shard_map(
        functools.partial(ring_attention, axis="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = jax.jit(ring)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(causal):
    from ray_tpu.ops.attention import ulysses_attention

    assert len(jax.devices()) == 8
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("sp",))
    B, H, S, D = 2, 8, 256, 32  # H divisible by the sp axis
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), B=B, H=H, S=S, D=D)

    ulysses = shard_map(
        functools.partial(ulysses_attention, axis="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = jax.jit(ulysses)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_non_block_multiple_seq():
    """Sequences that aren't multiples of the default block must still work
    (blocks auto-shrink to a divisor)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import flash_attention, mha_reference

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 768, 32))
    out = jax.jit(lambda q: flash_attention(q, q, q, None, True))(q)
    ref = mha_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # Odd length degrades to a single block but stays correct.
    q3 = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 129, 16))
    out3 = jax.jit(lambda q: flash_attention(q, q, q, None, False))(q3)
    ref3 = mha_reference(q3, q3, q3, causal=False)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(ref3),
                               atol=2e-5, rtol=2e-5)


def test_residual_names_change_no_program_outside_a_checkpoint(monkeypatch):
    """`_fa_fwd` names the kernel's results for a `jax.checkpoint` policy
    around the caller; where there is none, the forward and the gradient
    lower to the text they lower to without the names."""
    from ray_tpu.ops import attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(11), B=1, H=2, S=128, D=32)
    do = jnp.ones_like(q)

    def texts():
        call = lambda q, k, v: flash_attention(q, k, v, None, True)  # noqa: E731
        lowered = (jax.jit(call).lower(q, k, v),
                   jax.jit(lambda *x: _grads(call, *x)).lower(q, k, v, do))
        # (private functions are numbered by a counter the process keeps)
        return [re.sub(r"(@\w+?)_\d+\b", r"\1", x.as_text()) for x in lowered]

    named = texts()
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    assert texts() == named
