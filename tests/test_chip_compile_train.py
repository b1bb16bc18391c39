"""The whole train step compiled for a described v5e: the smoke's, the train
cell's, and the smoke's over four chips (`tests/chip_compile.py` says how).
The longest chain of the chip-compile files: three whole steps, 285 s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
from ray_tpu.models.llama import LLAMA3_8B, LlamaConfig
from tests.chip_compile import (HBM_BYTES, KERNEL,  # noqa: F401
                                _compile_for_the_chip, peak_bytes, topo)


def _abstract_train(cfg, mesh, batch, seq):
    prog = chip_smoke.train_program(cfg, mesh)
    state = jax.eval_shape(prog.build_state)
    shard = lambda tree, specs: jax.tree_util.tree_map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: isinstance(x, P))
    from ray_tpu.train.spmd import state_specs_from_rules
    from ray_tpu.parallel import TRANSFORMER_RULES

    specs = state_specs_from_rules(state, TRANSFORMER_RULES)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return prog.sharded_step(specs), shard(state, specs), \
        shard((tok, tok), prog.batch_spec)


# XLA compiles a whole train step for the described chip in native code:
# 76-105 s on the 8-core sandbox beside five other workers, and it scales
# with the machine. The limit is a guard against hangs, not a budget.
_WHOLE_STEP_LIMIT = pytest.mark.time_limit(600)


# `internlm2-train-packed2k` (`benchmarks/configs/internlm2-1.8b.json`:
# InternLM2-1.8B whole, 4 rows of 2,048 tokens, remat "full").  Its ceiling
# is the peak the cell held on the chip from PR 23 to PR 30: what "full"
# keeps of a layer has to stay under it (PR 34: 12.63 GB with the kernel's
# results, q, k, v and the attention block's output kept; 9.53 GB with
# nothing kept).
CELL_TRAIN = LlamaConfig(vocab_size=92544, d_model=2048, n_layers=24,
                         n_heads=16, n_kv_heads=8, d_ff=8192,
                         max_seq_len=32768, rope_theta=1e6,
                         **chip_smoke.TRAIN_OVERRIDES)


@_WHOLE_STEP_LIMIT
@pytest.mark.parametrize("cfg, rows, ceiling", [
    pytest.param(chip_smoke.smoke_config(LLAMA3_8B, chip_smoke.TRAIN_LAYERS,
                                         **chip_smoke.TRAIN_OVERRIDES),
                 chip_smoke.TRAIN_BATCH, HBM_BYTES,
                 id="smoke_llama3_8b_12_layers"),
    pytest.param(CELL_TRAIN, 4, 12.85e9,
                 id="cell_internlm2_train_packed2k")])
def test_train_step_fits_one_chip(topo, cfg, rows, ceiling):
    from ray_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(fsdp=1), devices=topo.devices[:1])
    step, state, batch = _abstract_train(cfg, mesh, rows,
                                         chip_smoke.TRAIN_SEQ)
    compiled = step.lower(state, batch).compile()
    # A layer's forward kernel once and its backward kernel once: remat
    # "full" keeps the forward kernel's results, so the layer's re-run
    # forward holds no kernel (three a layer before PR 34).
    assert compiled.as_text().count(KERNEL) == 2 * cfg.n_layers
    assert peak_bytes(compiled) <= ceiling


@_WHOLE_STEP_LIMIT
def test_train_step_sharded_over_four_chips(topo):
    """`chip_smoke.py --chips 4` at a cut depth: the whole 32 layers take
    three minutes to compile (done by hand; CHANGES.md has the bytes).
    What this guards is depth-independent: the flash kernel inside a
    program partitioned over four devices, and a state that is sharded."""
    from ray_tpu.parallel import MeshConfig, make_mesh

    cfg = chip_smoke.smoke_config(LLAMA3_8B, 4, **chip_smoke.TRAIN_OVERRIDES)
    mesh = make_mesh(MeshConfig(**chip_smoke.FOUR_CHIP_MESH),
                     devices=topo.devices)
    step, state, batch = _abstract_train(
        cfg, mesh, chip_smoke.FOUR_CHIP_BATCH, chip_smoke.TRAIN_SEQ)
    compiled = step.lower(state, batch).compile()
    # Under the mesh the kernels run inside `jax.shard_map`
    # (`llama._flash_on_mesh`); the names `_fa_fwd` gives their results
    # inside the mapped function reach the checkpoint policy all the same:
    # two kernels a layer here too, not three.
    assert compiled.as_text().count(KERNEL) == 2 * cfg.n_layers
    assert peak_bytes(compiled) < HBM_BYTES
    # Per-device bytes: what one device is handed of the state is a
    # quarter of the whole (norm scales and scalars replicate).
    whole = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state))
    assert compiled.memory_analysis().argument_size_in_bytes < 0.3 * whole

