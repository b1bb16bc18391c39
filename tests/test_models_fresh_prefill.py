"""The dense decoder's prefill with no earlier keys (`FRESH_KV`: the prompt
over itself through the flash kernel) against the branch that appends to a
zeroed cache and masks by position (CPU, tiny configs).  Out of
`test_models.py`, whose longest tests these are: a file is one worker's
chain."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (FRESH_KV, LlamaConfig, LlamaModel,
                                  init_kv_caches)


@pytest.mark.parametrize("bucket, max_len", [(16, 96), (64, 96), (96, 96)],
                         ids=["a-page", "mid", "max_len"])
@pytest.mark.parametrize("heads, kv_heads, head_dim",
                         [(4, 4, 32), (4, 2, 32), (32, 8, 16)],
                         ids=["mha", "gqa", "gqa-32-8"])
def test_fresh_prefill_equals_the_masked_softmax_branch(
        heads, kv_heads, head_dim, bucket, max_len):
    """A prefill with no earlier keys (`FRESH_KV`: the prompt over itself,
    the flash kernel reading grouped K/V in place) against the branch that
    appends to a zeroed cache of `max_len` and masks by position, same
    weights: logits at each row's last token and K/V up to its length, on
    right-padded rows of unequal length; K/V come back as long as the
    bucket, not the cache; and padding leaks into no row (a row alone at
    its own length gives the same last logits)."""
    cfg = LlamaConfig(vocab_size=128, d_model=heads * head_dim, n_layers=2,
                      n_heads=heads, n_kv_heads=kv_heads, d_ff=128,
                      max_seq_len=128, dtype=jnp.float32,
                      attention="reference", remat=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    lens = [bucket, max(1, bucket // 2 - 3), 1]
    tokens = np.zeros((3, bucket), np.int32)
    rng = np.random.default_rng(bucket)
    for r, n in enumerate(lens):
        tokens[r, :n] = rng.integers(1, cfg.vocab_size, n)
    positions = jnp.arange(bucket)[None, :]
    want, cache = model.apply(params, tokens, positions,
                              kv_caches=init_kv_caches(cfg, 3, max_len))
    got, fresh = model.apply(params, tokens, positions, kv_caches=FRESH_KV)
    assert len(fresh) == cfg.n_layers
    for (k, v), (ck, cv, _n) in zip(fresh, cache):
        assert k.shape == v.shape == (3, kv_heads, bucket, head_dim)
        assert ck.shape[2] == max_len
        for r, n in enumerate(lens):
            np.testing.assert_allclose(k[r, :, :n], ck[r, :, :n], atol=2e-5)
            np.testing.assert_allclose(v[r, :, :n], cv[r, :, :n], atol=2e-5)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, n - 1], want[r, n - 1],
                                   atol=2e-4, rtol=2e-4)
        alone, _ = model.apply(params, tokens[r:r + 1, :n],
                               jnp.arange(n)[None, :], kv_caches=FRESH_KV)
        np.testing.assert_allclose(got[r, n - 1], alone[0, n - 1],
                                   atol=2e-4, rtol=2e-4)


def test_fresh_prefill_in_the_served_type():
    """bfloat16, as the serve cells run it: q, k, v go to the kernel in the
    served type (float32 softmax statistics inside it), K/V come back in
    it, and the last logits stay within bf16 rounding of the masked
    branch's, which computes its scores in float32."""
    cfg = LlamaConfig(vocab_size=128, d_model=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      attention="reference", remat=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    positions = jnp.arange(64)[None, :]
    want, _ = model.apply(params, tokens, positions,
                          kv_caches=init_kv_caches(cfg, 2, 128))
    got, fresh = model.apply(params, tokens, positions, kv_caches=FRESH_KV)
    assert {x.dtype for x in jax.tree_util.tree_leaves(fresh)} == \
        {jnp.dtype(jnp.bfloat16)}
    np.testing.assert_allclose(got[:, -1], want[:, -1], atol=0.06)
