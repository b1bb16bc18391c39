"""Model correctness smoke tests (CPU, tiny configs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (
    FRESH_KV,
    TINY,
    LlamaConfig,
    LlamaModel,
    count_flops_per_token,
    cross_entropy_loss,
    init_kv_caches,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TINY
    model = LlamaModel(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    return cfg, model, params


def test_forward_shape(tiny_model):
    cfg, model, params = tiny_model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_loss_decreases_with_training(tiny_model):
    cfg, model, params = tiny_model
    import optax

    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            return cross_entropy_loss(model.apply(p, inp), tgt)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_param_names_match_sharding_rules(tiny_model):
    from ray_tpu.parallel import TRANSFORMER_RULES, P

    cfg, model, params = tiny_model
    specs = TRANSFORMER_RULES.tree_specs(params)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_path = {"/".join(str(getattr(k, "key", k)) for k in path): spec
               for path, spec in flat}
    qs = [s for p, s in by_path.items() if "q_proj/kernel" in p]
    assert qs and all(s == P("fsdp", "tp") for s in qs)
    downs = [s for p, s in by_path.items() if "down_proj/kernel" in p]
    assert downs and all(s == P("tp", "fsdp") for s in downs)


def test_kv_cache_decode_matches_full_forward(tiny_model):
    cfg, model, params = tiny_model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
    full_logits = model.apply(params, tokens)

    caches = init_kv_caches(cfg, 1, 16)
    # Prefill first 4 tokens, then decode one at a time.
    logits, caches = model.apply(params, tokens[:, :4],
                                 positions=jnp.arange(4), kv_caches=caches)
    outs = [logits]
    for i in range(4, 8):
        logits, caches = model.apply(
            params, tokens[:, i:i + 1],
            positions=jnp.array([i]), kv_caches=caches)
        outs.append(logits)
    stitched = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(stitched), np.asarray(full_logits),
                               atol=2e-4, rtol=2e-4)


def test_fresh_prefill_holds_the_kernel_once_a_layer():
    """What the prefill's program is made of: the forward kernel once a
    layer and no other (tests/test_chip_compile_dense.py: what the compiled
    program at the cells' widths holds, and what it no longer does)."""
    cfg = LlamaConfig(vocab_size=128, d_model=128, n_layers=3, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128,
                      dtype=jnp.float32, attention="reference", remat=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    jaxpr = jax.make_jaxpr(lambda p, t: model.apply(
        p, t, jnp.arange(32)[None, :], kv_caches=FRESH_KV))(
            params, jnp.zeros((2, 32), jnp.int32))
    assert _kernel_call_sites(jaxpr.jaxpr) == \
        {"_causal_over_itself": cfg.n_layers}


def test_gqa_config():
    cfg = LlamaConfig(vocab_size=64, d_model=64, n_layers=1, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=64,
                      dtype=jnp.float32, attention="reference", remat=False)
    model = LlamaModel(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (1, 8, 64)


def test_flops_estimate_7b():
    from ray_tpu.models.llama import LLAMA2_7B

    flops = count_flops_per_token(LLAMA2_7B)
    # ~6 * 6.7B params
    assert 3.5e10 < flops < 4.5e10


def test_vit_forward_and_train_step():
    import optax
    from ray_tpu.models import VIT_TINY, ViT, vit_loss
    from ray_tpu.parallel import MeshConfig, TRANSFORMER_RULES, make_mesh
    from ray_tpu.train.spmd import (init_sharded_state, make_train_step,
                                    shard_train_step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    model = ViT(VIT_TINY)
    imgs = jnp.zeros((4, 32, 32, 3), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), imgs)
    logits = jax.jit(model.apply)(params, imgs)
    assert logits.shape == (4, VIT_TINY.num_classes)
    assert np.isfinite(np.asarray(logits)).all()

    # The same transformer sharding rules cover ViT params (q/o/up/down
    # names align), so the sharded train step compiles over a dp x tp mesh.
    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    opt = optax.adam(1e-3)
    state, specs = init_sharded_state(
        mesh, lambda im: model.init(jax.random.PRNGKey(0), im),
        TRANSFORMER_RULES, opt, imgs)

    def loss_fn(p, batch):
        return vit_loss(model.apply(p, batch[0]), batch[1])

    step = make_train_step(loss_fn, opt)
    bs = (P(("dp", "fsdp"), None, None, None), P(("dp", "fsdp")))
    sstep = shard_train_step(step, mesh, specs, bs)
    labels = jnp.zeros((4,), jnp.int32)
    ex = jax.device_put((imgs, labels), jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), bs,
        is_leaf=lambda x: isinstance(x, P)))
    state, metrics = sstep(state, ex)
    assert np.isfinite(float(metrics["loss"]))


def test_dit_forward_and_loss():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.dit import DiT, DiTConfig, ddpm_loss

    cfg = DiTConfig(image_size=8, patch_size=2, d_model=32, n_layers=2,
                    n_heads=2, num_classes=4, timesteps=50,
                    dtype=jnp.float32, attention="reference")
    model = DiT(cfg)
    imgs = jnp.zeros((2, 8, 8, 3))
    t = jnp.zeros((2,), jnp.float32)
    labels = jnp.zeros((2,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), imgs, t, labels)
    out = jax.jit(model.apply)(params, imgs, t, labels)
    assert out.shape == (2, 8, 8, 3)
    # adaLN-Zero: zero-init final proj => initial prediction is exactly 0.
    assert float(jnp.abs(out).max()) == 0.0

    loss_fn = jax.jit(lambda p, b, l, r: ddpm_loss(model, p, b, l, r))
    loss = loss_fn(params, jnp.ones((2, 8, 8, 3)), labels,
                   jax.random.PRNGKey(1))
    # Prediction 0 vs unit gaussian noise target -> MSE ~ 1.
    assert 0.5 < float(loss) < 2.0
    grads = jax.jit(jax.grad(lambda p: ddpm_loss(
        model, p, jnp.ones((2, 8, 8, 3)), labels,
        jax.random.PRNGKey(1))))(params)
    gnorm = sum(float(jnp.abs(g).sum())
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


def test_dit_ddim_sampler():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.dit import DiT, DiTConfig, ddim_sample

    cfg = DiTConfig(image_size=8, patch_size=2, d_model=32, n_layers=1,
                    n_heads=2, num_classes=4, timesteps=20,
                    dtype=jnp.float32, attention="reference")
    model = DiT(cfg)
    imgs = jnp.zeros((1, 8, 8, 3))
    params = model.init(jax.random.PRNGKey(0), imgs, jnp.zeros((1,)),
                        jnp.zeros((1,), jnp.int32))
    out = jax.jit(lambda p, r: ddim_sample(
        model, p, r, num=2, steps=5,
        labels=jnp.zeros((2,), jnp.int32), guidance=1.0))(
        params, jax.random.PRNGKey(2))
    assert out.shape == (2, 8, 8, 3)
    import numpy as np

    assert np.isfinite(np.asarray(out)).all()


def test_dit_param_count_matches():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.dit import DiT, DiTConfig, count_dit_params

    cfg = DiTConfig(image_size=8, patch_size=2, d_model=32, n_layers=2,
                    n_heads=2, num_classes=4, timesteps=10,
                    dtype=jnp.float32, attention="reference")
    model = DiT(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)),
                        jnp.zeros((1,)), jnp.zeros((1,), jnp.int32))
    actual = sum(int(np.prod(x.shape))
                 for x in jax.tree_util.tree_leaves(params))
    assert count_dit_params(cfg) == actual


# ---- what a rematerialised layer keeps under remat_policy "full" ----------


def _flash_decoder(n_kv_heads, dtype=jnp.bfloat16, **overrides):
    cfg = LlamaConfig(vocab_size=64, d_model=128, n_layers=2, n_heads=4,
                      n_kv_heads=n_kv_heads, d_ff=256, max_seq_len=128,
                      dtype=dtype, attention="flash", **overrides)
    model = LlamaModel(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 129), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :8])

    def loss(params):
        return cross_entropy_loss(model.apply(params, tokens[:, :-1]),
                                  tokens[:, 1:])

    return cfg, params, loss


def _kernel_call_sites(jaxpr, counts=None):
    """Pallas calls by kernel name, every call site of every sub-jaxpr
    counted (a jitted function called by two layers counts twice)."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_name
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_call_sites(sub, counts)
    return counts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n_kv_heads", [2, 4], ids=["gqa", "mha"])
def test_full_remat_gradients_equal_no_remat(n_kv_heads, dtype):
    """What "full" keeps are the arrays the first forward produced, so the
    backward kernel is handed the same residuals bit for bit, and every
    gradient is the one the model gives with no rematerialisation.  (Not
    jitted: the CPU compiler fuses bf16 arithmetic differently in
    different programs, with or without a checkpoint.)"""
    _, params, plain = _flash_decoder(n_kv_heads, dtype, remat=False)
    _, _, kept = _flash_decoder(n_kv_heads, dtype, remat=True,
                                remat_policy="full")
    want = jax.grad(plain)(params)
    got = jax.grad(kept)(params)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        assert np.isfinite(np.asarray(g, np.float32)).all(), path
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)


@pytest.mark.parametrize("policy, forwards_a_layer", [("full", 1),
                                                      ("dots", 2)])
def test_full_remat_runs_the_flash_forward_once_a_layer(policy,
                                                        forwards_a_layer):
    """The gradient's program holds the forward kernel once a layer under
    "full" (its results are kept; the re-run forward of the layer holds no
    kernel) and the backward's kernel once a layer.  "dots" keeps matmul
    outputs only, so its re-run forward still holds the kernel."""
    cfg, params, loss = _flash_decoder(2, remat=True, remat_policy=policy)
    calls = _kernel_call_sites(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert calls == {"_flash_fwd_kernel": forwards_a_layer * cfg.n_layers,
                     "_flash_bwd_kernel": cfg.n_layers}
