"""Test configuration.

The suite runs on the CPU: this file pins JAX to it before any test
imports jax's backend, so no test opens a chip. Sharding/multi-chip
tests run on a virtual 8-device CPU mesh (XLA flags set here, before
the lazy backend initialization). The chip is reached only through
``python chip_smoke.py`` on a machine that has one; tests that compile
for a chip describe its topology without one (tests/test_tpu_compile.py).
"""

import asyncio
import contextlib
import inspect
import os
import signal
import sys

# XLA_FLAGS must be in place before the (lazy) backend initialization
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from drand_tpu.testing import quiesce  # noqa: E402
from drand_tpu.utils.jit_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

import pytest  # noqa: E402

pytest_plugins = ["pytester"]

# Per-test time limit (no pytest-timeout in the image): 180 s, 900 s in
# `device` modules (about twice the slowest cold compile-bound test), or
# what a `timeout(seconds)` marker on the test or module says. SIGALRM
# (xdist runs tests on each worker's main thread) fails the test with
# its stack; a C-level XLA compile is not interrupted, so the alarm
# then fires when the compile returns.
DEFAULT_LIMIT_S, DEVICE_LIMIT_S = 180.0, 900.0


def _limit_s(item) -> float:
    marker = item.get_closest_marker("timeout")
    if marker is not None:
        return float(marker.args[0])
    return DEVICE_LIMIT_S if item.get_closest_marker("device") \
        else DEFAULT_LIMIT_S


@contextlib.contextmanager
def _time_limit(item):
    limit = _limit_s(item)

    def expire(signum, frame):
        pytest.fail(f"{item.nodeid} exceeded its {limit:g} s time limit")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item):
        yield


# Units of work for xdist: the tests of each group share compiled device
# graphs (an engine fixture, or one process's jit caches), so a group
# runs whole on one worker and no second worker compiles the same graph.
# Groups go out first, in this order (longest cold compile first); every
# other test is its own unit, in collection order.
GRAPH_GROUPS = (
    ("test_batch_engine.py",),
    ("test_rlc_verify.py", "test_eval_commits.py"),
    # the two tests that need the per-item wire graph; the rest of the
    # wire-RLC suite compiles the wire-RLC graph meanwhile, and the
    # fallback test then reads that from the persistent compile cache
    ("test_h2c_device.py::test_engine_wire_prep",
     "test_wire_rlc.py::TestWireRLC::test_bad_signature"),
    ("test_ops_golden.py",),
    ("test_wire_rlc.py",),
    ("test_pippenger.py",),
    ("test_zz_timelock_serve.py::test_device_round_open",),
    ("test_bl_curve.py",),
    ("test_tpu_compile.py",),
    ("test_bl_h2c.py",),
    ("test_pallas_field.py",),
    ("test_h2c_device.py",),
    ("test_lazy_reduction.py",),
)


def _graph_group(nodeid: str) -> str | None:
    path, sep, rest = nodeid.partition("::")
    name = os.path.basename(path) + sep + rest
    for rank, group in enumerate(GRAPH_GROUPS):
        if name.startswith(group):
            return f"group-{rank:02d}"
    return None


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    if config.getoption("dist") != "load":
        return None
    from xdist.scheduler import LoadScopeScheduling

    class GraphGroupScheduling(LoadScopeScheduling):
        def _split_scope(self, nodeid: str) -> str:
            return _graph_group(nodeid) or nodeid

        def _assign_work_unit(self, node) -> None:
            if not getattr(self, "_ranked", False):
                # the groups to the front, in GRAPH_GROUPS order
                groups = sorted(s for s in self.workqueue
                                if s.startswith("group-"))
                for scope in reversed(groups):
                    self.workqueue.move_to_end(scope, last=False)
                self._ranked = True
            held = self.assigned_work.get(node, {})
            if any(scope.startswith("group-") and not all(done.values())
                   for scope, done in held.items()):
                # xdist tops a worker up before its last tests run: a
                # group's tail gets a single test behind it, never a
                # group that an idle worker could start now
                single = next((s for s in self.workqueue
                               if not s.startswith("group-")), None)
                if single is not None:
                    self.workqueue.move_to_end(single, last=False)
            super()._assign_work_unit(node)

    return GraphGroupScheduling(config, log)


@pytest.fixture(scope="session")
def engine4():
    """The single-device engine at pairing bucket 4 that the engine,
    RLC, eval-commit and timelock tests share (tests that change its
    knobs do so through monkeypatch)."""
    from drand_tpu.ops.engine import BatchedEngine

    return BatchedEngine(buckets=(4,))


@pytest.fixture(scope="session")
def wire_engine4():
    """The wire-prep engine (device hash-to-curve and decompression) at
    bucket 4 that the wire-RLC and device h2c tests share."""
    from drand_tpu.ops.engine import BatchedEngine

    return BatchedEngine(buckets=(4,), wire_prep=True)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on a fresh event loop (no pytest-asyncio
    in the image); the inert @pytest.mark.asyncio markers stay readable.
    The loop's default executor counts worker-thread jobs, so a fake
    clock waits for them before it moves (drand_tpu/testing/quiesce.py)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        with asyncio.Runner() as runner:
            quiesce.install(runner.get_loop())
            runner.run(fn(**kwargs))
        return True
    return None


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: async test (run by conftest)")
    config.addinivalue_line(
        "markers",
        "device: device-compile-heavy test (multi-minute XLA/Mosaic "
        "compiles on a small host)")
    config.addinivalue_line(
        "markers",
        "slow: wall-clock-heavy test (multi-process e2e, 100-round "
        "scale runs, device graphs whose cold compile takes most of "
        "the tier-1 run)")
    config.addinivalue_line(
        "markers",
        "timeout(seconds): this test's time limit (default 180 s, "
        "900 s for device tests)")


# Markers live with the code they describe: device-compile-heavy modules
# (pairing/h2c/MSM graph compiles, minutes each) carry
# `pytestmark = pytest.mark.device`; multi-process/scale tests and the
# device graphs too slow for tier 1 carry `pytest.mark.slow`. The
# documented fast path (README) is `-m "not device and not slow"`.


def sample_count(registry, fam_name: str, **labels) -> float:
    """Sum of _count/_total samples of a metric family matching the
    given labels — shared by the metrics and tracing suites."""
    total = 0.0
    for fam in registry.collect():
        if fam.name != fam_name:
            continue
        for s in fam.samples:
            if not (s.name.endswith("_count") or s.name.endswith("_total")):
                continue
            if all(s.labels.get(k) == v for k, v in labels.items()):
                total += s.value
    return total
