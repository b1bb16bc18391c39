"""What the `test_chip_compile_*.py` files share: the described v5e, and
how a program is compiled for it and read.

No chip is attached: the TPU compiler runs against a topology
description (`on-chip-measurement` guide, section 2, third rehearsal),
so what the chip's compiler would refuse (a misaligned block, too much
VMEM, a program over 16 GB, a kernel that cannot be partitioned) fails
here, at the widths `chip_smoke.py` and the benchmark's cells run.
Nothing executes; these say nothing about results or times.

One file a kind of program (kernels, the dense engine, the train step, and
one for each other family), so that `--dist loadfile` hands them to
different workers: together they are the costliest tenth of the suite, and
as one file they were one worker's chain (876 s of 1,165 at PR 47).  A
test file imports the fixtures it uses BY NAME (`topo`, `one_chip`,
`_compile_for_the_chip`, which is autouse where it is imported).
Everything that touches the topology happens inside fixtures and tests,
never at import: only a worker that is handed one of these files loads the
TPU library.  This module holds no test.
"""

import json
import os
import signal
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402
from ray_tpu.ops import attention, paged_attention  # noqa: E402

HBM_BYTES = 16 * 1024 ** 3
KERNEL = "tpu_custom_call"
NOTHING = dict.fromkeys(chip_smoke.STATE_MOVES + ("moved",), 0)
NO_MOVES = {"loop": NOTHING, "outside": NOTHING}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # The TPU library installs its own SIGTERM handler when it loads, and
    # that handler prints a stack trace.  A test run that is cut by its
    # clock ends in SIGTERM; the trace would land on the line of dots the
    # run is counted by.  Keep the handler this process had.
    sigterm = signal.getsignal(signal.SIGTERM)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        signal.signal(signal.SIGTERM, sigterm)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch):
    """The default backend here is the CPU, where both kernel files
    choose interpret mode; these compiles are for the chip, at the
    chip's default matmul precision (conftest asks for "highest", which
    Mosaic refuses for bf16 operands).  The persistent cache cannot read
    a described-device entry back, so it stays off around them."""
    monkeypatch.setattr(attention, "_interpret_mode", lambda: False)
    monkeypatch.setattr(paged_attention, "_interpret_mode", lambda: False)
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    compilation_cache.reset_cache()


def on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def gb(tree) -> float:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)) / 1e9


def shape_on(one_chip):
    """-> S(shape, dtype): a ShapeDtypeStruct that lies on the chip."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def abstract_params(model):
    """A model's parameters as SHAPES (no array of that size exists here)."""
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                             jnp.zeros((1, 8), jnp.int32)))


def cell_config(name: str) -> dict:
    with open(os.path.join(_REPO, "benchmarks", "configs", name)) as f:
        return json.load(f)


def compiled_decode_chunk(eng, params, one_chip, pools=None):
    """The engine's decode chunk compiled for the chip at the engine's
    own shapes (a count of steps a slot where the family needs one), over
    the engine's own pools or the shapes handed in."""
    B = eng.max_batch
    S = shape_on(one_chip)
    steps = () if eng.family.rewinds else (S((B,), jnp.int32),)
    return eng._decode_chunk_paged.lower(
        on(one_chip, params), S((B,), jnp.int32), S((B,), jnp.int32),
        on(one_chip, eng._pools if pools is None else pools),
        S(eng._tables.shape, jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.int32),
        S((B,), jnp.float32), S((2,), jnp.uint32), S((), jnp.int32),
        *steps).compile()


def compiled_prefill(eng, params, one_chip, W, bucket):
    """The engine's prefill of W rows of a bucket (`prefill_one` for a
    row alone, as the engine chooses), lowered and compiled for the chip."""
    S = shape_on(one_chip)
    program = eng._prefill_one if W == 1 else eng._prefill_many
    lowered = program.lower(on(one_chip, params), S((W, bucket), jnp.int32),
                            S((W,), jnp.int32))
    return lowered, lowered.compile()


def paged_call(one_chip, B, H, Hkv, pool_pages, table_pages, q_dtype,
               writes, sm_scale=None):
    """The paged kernel compiled alone: pages of 64 tokens, heads of 128,
    bfloat16 pools; with the step's rows handed in (`writes`: the pools
    donated, as the engine donates them) or read-only."""
    S = shape_on(one_chip)
    pool = S((pool_pages, Hkv, 64, 128), jnp.bfloat16)
    new = S((B, Hkv, 128), q_dtype)

    def call(q, k_pool, v_pool, tables, lengths, *rows):
        return paged_attention.paged_decode_attention_batch(
            q, k_pool, v_pool, tables, lengths, sm_scale=sm_scale,
            **dict(zip(("k_new", "v_new"), rows)))

    return jax.jit(call, donate_argnums=(1, 2) if writes else ()).lower(
        S((B, H, 128), q_dtype), pool, pool, S((B, table_pages), jnp.int32),
        S((B,), jnp.int32), *((new, new) if writes else ())).compile()


def tiles_seen(monkeypatch) -> list:
    """(rows, groups, tiles) of every call of the grouped product's kernel
    (`ops/grouped_matmul._grouped_call`) made while a program is traced,
    in order: what `_tiles` chose, that the rows are float32 rows, and
    that a routed layer is a pair of calls that move their own rows: the
    first takes each sorted pair's row by its id and stores `silu(a) * b`,
    the second takes those as they lie and writes each where it belongs."""
    from ray_tpu.ops import grouped_matmul

    seen, real = [], grouped_matmul._grouped_call

    def call(x, w, sizes, src, dst, *, tiles, gated, **kw):
        assert x.dtype == jnp.float32 and w.dtype == jnp.bfloat16
        first = len(seen) % 2 == 0
        assert (src is not None, dst is None, gated) == (first,) * 3
        seen.append(((src if first else x).shape[0], w.shape[0], tiles))
        return real(x, w, sizes, src, dst, tiles=tiles, gated=gated, **kw)

    monkeypatch.setattr(grouped_matmul, "_grouped_call", call)
    return seen
