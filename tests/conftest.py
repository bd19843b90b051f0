import os

# 8 virtual devices for mesh tests; must be set before jax initializes backends
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# The tests check answers, not speed, and XLA:CPU compilation is most of the
# suite's CPU time: LLVM at -O0 takes a third off it (ISSUE 25, step 4). An
# environment variable and not jax.config, so that child processes get it too.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
# The XLA-CPU executable serializer segfaults writing some window kernels
# while worker threads execute concurrently (observed deterministically in
# full-suite runs; compile itself is fine). The on-disk cache only buys
# cross-process reuse — tests rely on the in-memory kernel cache — so keep
# it off here; bench/driver runs (TPU backend, different serializer) use it.
os.environ.setdefault("SPARK_RAPIDS_TPU_NO_PERSISTENT_CACHE", "1")

import jax

# Tests run on the CPU backend; the chip is exercised by chip_smoke.py.
jax.config.update("jax_platforms", "cpu")

import faulthandler  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

#: every test's limit, in seconds: the longest honest test takes 120 of them
#: with six workers on eight cores, and the driver's whole run is cut at 1470
TEST_LIMIT_S = 300


@pytest.fixture(autouse=True)
def _test_limit(request):
    """Fail a test by name, with every thread's stack, once it has run
    TEST_LIMIT_S: a hang then costs one test and not the run's clock. The
    handler runs when the interpreter next has control, so a native call
    that never returns is not ended by it."""

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(
            f"{request.node.nodeid} ran past its limit of {TEST_LIMIT_S} s\n"
            f"{stacks}",
            pytrace=False,
        )

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def pytest_runtest_logreport(report):
    """The suite's one self-measurement: with SRT_TEST_DURATIONS=<file>,
    append ``nodeid, phase, seconds, outcome, worker`` (tab-separated) as
    each phase of each test ends, so a run that is killed keeps what it
    measured. Written by the process that gathers the reports."""
    path = os.environ.get("SRT_TEST_DURATIONS")
    if not path or os.environ.get("PYTEST_XDIST_WORKER"):
        return
    node = getattr(report, "node", None)
    worker = node.gateway.id if node is not None else "main"
    with open(path, "a") as f:
        f.write(
            f"{report.nodeid}\t{report.when}\t{report.duration:.3f}\t"
            f"{report.outcome}\t{worker}\n"
        )


#: the files that take longest, in the order they go out (seconds per file:
#: CHANGES.md, PR 25), each with the number of consecutive cases in one shard
#: of it, 0 for a file that stays whole; every other file is one scope, after
#: these. A file left out of here costs only balance at the run's end.
_LONG_FILES = {
    # whole: its 150 cases share most kernels, 288 s in one process against
    # 1,055 core-seconds in shards of 15
    "tests/test_qa_generated.py": 0,
    "tests/test_tpcds.py": 9,
    "tests/test_tpch.py": 0,
    "tests/test_window.py": 0,
    "tests/test_golden.py": 0,
    "tests/test_distributed.py": 0,
}


@pytest.hookimpl(optionalhook=True)  # unknown under -p no:xdist
def pytest_xdist_make_scheduler(config, log):
    """One worker per file, the long files first. Kernels compiled for a
    file stay in the worker that runs it until ``_bound_jit_code_size``
    clears them at the file's end; under ``--dist load`` all six workers
    compiled every file's kernels, and tests queued behind a long one while
    other workers idled."""
    from xdist.scheduler.loadscope import LoadScopeScheduling

    rank = {path: i for i, path in enumerate(_LONG_FILES)}

    class FileScheduling(LoadScopeScheduling):
        def _split_scope(self, nodeid):
            path = nodeid.split("::", 1)[0]
            case = nodeid[nodeid.rfind("[") + 1 : -1]
            if _LONG_FILES.get(path) and case.isdigit():
                return f"{path}#{int(case) // _LONG_FILES[path]:02d}"
            return path

        def _assign_work_unit(self, node):
            first = min(
                self.workqueue,
                key=lambda scope: rank.get(scope.split("#")[0], len(rank)),
            )
            self.workqueue.move_to_end(first, last=False)
            super()._assign_work_unit(node)

    return FileScheduling(config, log)


@pytest.fixture(scope="session")
def session():
    from spark_rapids_tpu import TpuSession

    return TpuSession()


@pytest.fixture(scope="module")
def serve_leak_guard():
    """Thread/fd leak detector for the serve suites (ISSUE 7): snapshot
    live threads and open fds at module start, assert both return to
    baseline after the module's servers stop. Declared module-scoped in
    conftest so each serve test module opts in with a tiny autouse
    wrapper that pytest sets up BEFORE (and finalizes AFTER) the module's
    server rig.

    The comparison polls: worker threads unwind asynchronously after a
    cancel, and CPython closes sockets on GC — a few seconds of grace is
    part of the contract, an unbounded leak is not. Long-lived engine
    singletons that may be LAZILY created mid-module (watchdog scanner,
    jax runtime threads) are excluded by name."""
    import gc
    import threading
    import time as _time

    _IGNORE = ("srt-watchdog", "srt-compile-deadline", "pjrt", "jax")

    def fd_count() -> int:
        try:
            return len(os.listdir("/proc/self/fd"))
        except OSError:
            return 0

    def live_threads():
        return {
            t
            for t in threading.enumerate()
            if t.is_alive()
            and not any(t.name.startswith(p) for p in _IGNORE)
        }

    before_threads = live_threads()
    before_fds = fd_count()
    yield
    gc.collect()
    deadline = _time.monotonic() + 15.0
    while _time.monotonic() < deadline:
        leaked = live_threads() - before_threads
        fds = fd_count()
        if not leaked and fds <= before_fds + 2:
            return
        _time.sleep(0.1)
        gc.collect()
    leaked = live_threads() - before_threads
    fds = fd_count()
    assert not leaked and fds <= before_fds + 2, (
        f"serve module leaked: threads={[t.name for t in leaked]} "
        f"fds {before_fds} -> {fds}"
    )


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_code_size():
    """Release compiled XLA:CPU executables between test modules.

    The full suite compiles thousands of kernels into one process; past a
    few GB of JITed code the CPU backend segfaults inside
    backend_compile_and_load (LLVM relocation-range class of failure —
    observed deterministically near the end of full runs, never in module
    isolation). Real sessions never accumulate hundreds of distinct query
    shapes, and the TPU backend doesn't use the LLVM JIT at all."""
    yield
    _release_jit_code()


def _release_jit_code():
    import jax

    from spark_rapids_tpu import kernels as K

    K.clear()
    jax.clear_caches()


def _memory_mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _mappings_allowed() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


@pytest.fixture(autouse=True)
def _bound_jit_mappings():
    """Release compiled XLA:CPU executables inside a long module too, once
    the process holds half the memory mappings the kernel allows it.

    Every executable the CPU backend's JIT loads keeps mappings of its own
    (2,500 to 3,500 a TPC-DS query); at ``vm.max_map_count`` (65,530) the
    next ``mmap`` fails and the process dies inside backend_compile_and_load.
    ``test_tpcds.py`` compiles 99 queries into one process: 17 of them hold
    42,000 mappings, and 62,000 since the planes of a batch are stacked
    (ops/gather.py), whose programs hold more, smaller kernels on this
    backend. The TPU backend loads no such code."""
    yield
    if _memory_mappings() > _mappings_allowed() // 2:
        _release_jit_code()
        import gc

        gc.collect()


#: tier-1 suites that exercise the engine's real multi-thread interleavings
#: (concurrent admissions, serve workers, pipeline producers) — they run
#: under the lockwatch harness; chaos-marked tests ride it too (ISSUE 10)
_LOCKWATCH_MODULES = {"test_scheduler", "test_serve", "test_live"}

#: suites that run under the reswatch resource-balance harness (ISSUE 15):
#: same armed set as lockwatch — the suites whose tests acquire and must
#: return permits, spans, flocks, threads, and fds
_RESWATCH_MODULES = _LOCKWATCH_MODULES


@pytest.fixture(autouse=True)
def _reswatch_harness(request):
    """Resource-balance harness (spark_rapids_tpu/analysis/reswatch.py):
    snapshot every registered resource kind at test entry — permit pools,
    device semaphore slots, scheduler admission registries, spill-catalog
    buffers, open span/ledger/flock scopes, the fault-injector refcount,
    live engine threads, open fds — and assert at teardown that the test
    put every one of them back. The runtime complement of the static
    resource-lifecycle pass: what the CFG calls an ownership transfer
    must still balance here.

    Gating: armed for the scheduler/serve tier-1 suites and every
    chaos-marked test; SRT_RESWATCH=1 arms it for EVERY test,
    SRT_RESWATCH=0 disables it entirely (plain pytest runs stay cheap —
    unarmed tests pay nothing)."""
    env = os.environ.get("SRT_RESWATCH", "")
    if env in ("0", "off", "false"):
        yield
        return
    module = getattr(request.node, "module", None)
    name = getattr(module, "__name__", "").rsplit(".", 1)[-1]
    armed = (
        env in ("1", "on", "true", "all")
        or name in _RESWATCH_MODULES
        or request.node.get_closest_marker("chaos") is not None
    )
    if not armed:
        yield
        return
    from spark_rapids_tpu.analysis import reswatch

    reswatch.install()  # idempotent; assertions are snapshot-relative
    snap = reswatch.snapshot()
    yield
    rep = reswatch.report(snap)
    assert rep.ok, rep.describe()


@pytest.fixture(autouse=True)
def _lockwatch_harness(request):
    """Lock-order race harness (spark_rapids_tpu/analysis/lockwatch.py):
    instrument every engine-created Lock/RLock/Condition for the duration
    of the test, record real acquisition orderings into the process-wide
    order graph, and assert that no cycle and no declared-hierarchy
    inversion was EVER observed — the dynamic teeth of the static
    lock-order pass. Observations accumulate across tests on purpose:
    an inversion is a property of the engine, not of one test."""
    module = getattr(request.node, "module", None)
    name = getattr(module, "__name__", "").rsplit(".", 1)[-1]
    armed = (
        name in _LOCKWATCH_MODULES
        or request.node.get_closest_marker("chaos") is not None
    )
    if not armed:
        yield
        return
    from spark_rapids_tpu.analysis import lockwatch

    lockwatch.install()
    try:
        yield
    finally:
        lockwatch.uninstall()
    report = lockwatch.report()
    assert report.ok, report.describe()
