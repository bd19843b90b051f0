"""Deterministic fault injection — the chaos harness behind the resilience
layer's tests.

The reference validates its failure paths against mocked transports and
forced RMM allocation failures (RapidsShuffleClientSuite.scala,
DeviceMemoryEventHandlerSuite); PJRT offers no alloc hook to force, so the
TPU engine injects faults at its own seams instead: compiled-kernel launches
(kernels.GuardedJit), first-touch compiles, disk-tier spill IO
(mem/spill.py), and outgoing shuffle DATA frames (shuffle/tcp.py). Every
point is counter-driven ("every Nth event") from one seeded config, so a
chaos run replays bit-identically — assertions can demand that results under
injected faults equal the fault-free run.

All points are inert (one ``is None`` check) unless a ``FaultConfig`` is
installed, either by ``scoped()`` (tests) or by the session when
``spark.rapids.tpu.faults.enabled`` is set.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from contextlib import contextmanager
from typing import Optional


class InjectedFault(RuntimeError):
    """A synthetic failure raised by an injection point. The message mimics
    the real error class (RESOURCE_EXHAUSTED for OOM, UNAVAILABLE for
    transient compiles) so classification paths treat it like the real
    thing; ``kind`` lets tests assert on the injection itself."""

    def __init__(self, kind: str, msg: str):
        super().__init__(msg)
        self.kind = kind


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """One chaos scenario (all counters per-process, deterministic)."""

    seed: int = 0
    device_oom_every_n: int = 0  # GuardedJit launches
    oom_above_bytes: int = 0  # splittable-operator launches over this size
    kernel_error_every_n: int = 0  # splittable-operator launches (non-OOM)
    compile_fail_every_n: int = 0  # first-touch compiles
    spill_write_error_every_n: int = 0  # host→disk spill writes
    spill_read_error_every_n: int = 0  # disk→host re-materializations
    tcp_drop_every_n: int = 0  # outgoing shuffle DATA frames
    tcp_delay_every_n: int = 0
    tcp_delay_ms: float = 0.0
    tcp_corrupt_every_n: int = 0  # flip a byte in outgoing DATA frames
    kernel_stall_every_n: int = 0  # stall (not fail) compiled-kernel launches
    kernel_stall_ms: float = 0.0
    compile_delay_every_n: int = 0  # delay first-touch compiles
    compile_delay_ms: float = 0.0
    # compile-cache (cache/xla_store.py) damage points — every way an
    # on-disk entry can lie to a later boot
    cache_truncate_every_n: int = 0  # torn write surviving the rename
    cache_corrupt_every_n: int = 0  # payload bit flip after CRC stamp
    cache_stale_version_every_n: int = 0  # header from a "different engine"
    cache_crash_before_rename_every_n: int = 0  # die between temp and rename
    cache_lock_holder_every_n: int = 0  # wedged peer holds the entry flock
    cache_lock_holder_hold_ms: float = 0.0
    # recovery-layer points (resilience/lineage.py era)
    map_output_loss_every_n: int = 0  # drop a committed shuffle map output
    stall_partition: int = -1  # straggle this partition id (first attempt)
    stall_partition_s: float = 2.0


class FaultInjector:
    """Counters + the decision logic for one installed FaultConfig."""

    def __init__(self, config: FaultConfig):
        self.config = config
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self.injected: dict[str, int] = {}

    def _tick(self, point: str, every_n: int) -> bool:
        if every_n <= 0:
            return False
        with self._lock:
            n = self._counters.get(point, 0) + 1
            self._counters[point] = n
            if n % every_n:
                return False
            self.injected[point] = self.injected.get(point, 0) + 1
            return True

    def _record(self, point: str) -> None:
        from . import retry as R

        R.record("faults_injected")

    # ── injection points ────────────────────────────────────────────────
    def on_kernel_launch(self) -> None:
        """Every compiled-kernel call (kernels.GuardedJit.__call__)."""
        if self._tick("kernel_launch", self.config.device_oom_every_n):
            self._record("kernel_launch")
            raise InjectedFault(
                "oom", "RESOURCE_EXHAUSTED: injected device OOM (fault injection)"
            )

    def on_batch_launch(self, size_bytes: int) -> None:
        """Every splittable-operator launch, with the batch size known
        (resilience/retry.py — the seam the split state machine watches)."""
        c = self.config
        if c.oom_above_bytes and size_bytes > c.oom_above_bytes:
            with self._lock:
                self.injected["oom_above_bytes"] = (
                    self.injected.get("oom_above_bytes", 0) + 1
                )
            self._record("oom_above_bytes")
            raise InjectedFault(
                "oom",
                f"RESOURCE_EXHAUSTED: injected OOM — batch of {size_bytes} B "
                f"exceeds the injected device budget of {c.oom_above_bytes} B",
            )
        if self._tick("batch_launch", c.kernel_error_every_n):
            self._record("batch_launch")
            raise InjectedFault(
                "kernel",
                "INTERNAL: injected XlaRuntimeError — device kernel failed "
                "(fault injection)",
            )

    def on_kernel_stall(self) -> None:
        """Stall (not fail) a compiled-kernel launch — the wedged-device
        simulation the progress watchdog must notice. Unlike the OOM
        point this fires on EVERY launch (no recovery scope: nothing
        recovers a stall; the watchdog's cancel is the recovery)."""
        c = self.config
        if self._tick("kernel_stall", c.kernel_stall_every_n) and c.kernel_stall_ms > 0:
            self._record("kernel_stall")
            time.sleep(c.kernel_stall_ms / 1e3)

    def on_kernel_compile(self) -> None:
        """First-touch compiles (kernels.GuardedJit._first_call)."""
        c = self.config
        if self._tick("compile_delay", c.compile_delay_every_n) and c.compile_delay_ms > 0:
            self._record("compile_delay")
            time.sleep(c.compile_delay_ms / 1e3)
        if self._tick("kernel_compile", c.compile_fail_every_n):
            self._record("kernel_compile")
            raise InjectedFault(
                "compile",
                "UNAVAILABLE: injected compile failure (fault injection)",
            )

    def on_spill_write(self) -> None:
        if self._tick("spill_write", self.config.spill_write_error_every_n):
            self._record("spill_write")
            raise InjectedFault("io", "injected spill-disk write IO error")

    def on_spill_read(self) -> None:
        if self._tick("spill_read", self.config.spill_read_error_every_n):
            self._record("spill_read")
            raise InjectedFault("io", "injected spill-disk read IO error")

    def on_tcp_data_frame(self) -> bool:
        """Returns True when the frame should be DROPPED; may also sleep
        (injected delay). Called only for DATA frames — control frames
        stay reliable, like a lossy link under a reliable RPC layer."""
        c = self.config
        if self._tick("tcp_delay", c.tcp_delay_every_n) and c.tcp_delay_ms > 0:
            time.sleep(c.tcp_delay_ms / 1e3)
        if self._tick("tcp_drop", c.tcp_drop_every_n):
            self._record("tcp_drop")
            return True
        return False

    def corrupt_tcp_data_frame(self) -> bool:
        """Whether to flip a payload byte in this outgoing DATA frame
        (AFTER its checksum is stamped — the receiver's CRC check is what
        must catch it)."""
        if self._tick("tcp_corrupt", self.config.tcp_corrupt_every_n):
            self._record("tcp_corrupt")
            return True
        return False

    def lose_map_output(self) -> bool:
        """Whether this exchange read should find its committed map output
        GONE (peer loss / blacklist simulation — the lineage layer must
        rebuild it instead of failing the query)."""
        if self._tick("map_output_loss", self.config.map_output_loss_every_n):
            self._record("map_output_loss")
            return True
        return False

    def on_task_attempt(self, partition_id: int, attempt: int,
                        token=None) -> None:
        """First attempt of the configured partition straggles: sleep in
        token-beating slices so the watchdog sees progress (a straggler is
        SLOW, not stalled — exactly what speculation, not the watchdog,
        must catch). Re-executed and speculative attempts run at full
        speed, so the duplicate attempt wins the race deterministically."""
        c = self.config
        if c.stall_partition < 0 or partition_id != c.stall_partition:
            return
        if attempt != 0 or c.stall_partition_s <= 0:
            return
        with self._lock:
            # one-shot: only the FIRST attempt ever observed straggles;
            # the speculative duplicate re-enters the retry loop at
            # attempt 0 too, and stalling it as well would leave no
            # attempt able to win the race
            if self.injected.get("stall_partition", 0):
                return
            self.injected["stall_partition"] = 1
        self._record("stall_partition")
        deadline = time.monotonic() + c.stall_partition_s
        while time.monotonic() < deadline:
            if token is not None:
                token.check()  # cancelled loser unwinds mid-straggle
            time.sleep(0.02)

    # ── compile-cache damage points (cache/xla_store.py) ────────────────
    def cache_stale_fence(self) -> bool:
        """Whether this entry's header should carry a perturbed format
        version (version-skew simulation — the load fence must
        silently miss it)."""
        if self._tick("cache_stale_version",
                      self.config.cache_stale_version_every_n):
            self._record("cache_stale_version")
            return True
        return False

    def cache_crash_before_rename(self) -> bool:
        """Whether this publish should 'crash' between its temp-file fsync
        and the rename, leaving an orphan staging file."""
        if self._tick("cache_crash_before_rename",
                      self.config.cache_crash_before_rename_every_n):
            self._record("cache_crash_before_rename")
            return True
        return False

    def cache_post_write_damage(self) -> Optional[str]:
        """Damage to apply to a just-published entry: 'truncate' (torn
        write) or 'corrupt' (payload bit flip), else None. The next load
        must quarantine either and rebuild fresh."""
        if self._tick("cache_truncate", self.config.cache_truncate_every_n):
            self._record("cache_truncate")
            return "truncate"
        if self._tick("cache_corrupt", self.config.cache_corrupt_every_n):
            self._record("cache_corrupt")
            return "corrupt"
        return None

    def cache_lock_holder_ms(self) -> float:
        """How long a simulated wedged peer should hold this entry's
        single-flight flock before the caller gets its turn (0 = no
        injection)."""
        c = self.config
        if c.cache_lock_holder_hold_ms > 0 and self._tick(
            "cache_lock_holder", c.cache_lock_holder_every_n
        ):
            self._record("cache_lock_holder")
            return c.cache_lock_holder_hold_ms
        return 0.0


_ACTIVE: Optional[FaultInjector] = None
_ACTIVE_COUNT = 0  # concurrent scoped() entries holding _ACTIVE installed
_SHADOWED: list = []  # [(injector, count)] scopes displaced by a newer one
_INSTALL_LOCK = threading.Lock()
_TLS = threading.local()


def active() -> Optional[FaultInjector]:
    return _ACTIVE


@contextmanager
def recoverable():
    """Marks the dynamic extent of a launch that has inline OOM recovery
    above it (resilience/retry.py run_once/run_with_retry, spill.py
    with_oom_retry). ``deviceOomEveryN`` fires ONLY inside this scope:
    injecting a synthetic OOM at a launch nothing recovers would only
    assert that unrecoverable failures fail — every covered launch instead
    exercises the spill/split machinery deterministically."""
    depth = getattr(_TLS, "depth", 0)
    _TLS.depth = depth + 1
    try:
        yield
    finally:
        _TLS.depth = depth


def in_recoverable_scope() -> bool:
    return getattr(_TLS, "depth", 0) > 0


# Module-level fast paths: one attribute read when no injector is installed.
def on_kernel_launch() -> None:
    inj = _ACTIVE
    if inj is not None and in_recoverable_scope():
        inj.on_kernel_launch()


def on_batch_launch(size_bytes: int) -> None:
    inj = _ACTIVE
    if inj is not None:
        inj.on_batch_launch(size_bytes)


def on_kernel_compile() -> None:
    inj = _ACTIVE
    if inj is not None:
        inj.on_kernel_compile()


def on_spill_write() -> None:
    inj = _ACTIVE
    if inj is not None:
        inj.on_spill_write()


def on_spill_read() -> None:
    inj = _ACTIVE
    if inj is not None:
        inj.on_spill_read()


def drop_tcp_data_frame() -> bool:
    inj = _ACTIVE
    if inj is not None:
        return inj.on_tcp_data_frame()
    return False


def corrupt_tcp_data_frame() -> bool:
    inj = _ACTIVE
    if inj is not None:
        return inj.corrupt_tcp_data_frame()
    return False


def on_kernel_stall() -> None:
    inj = _ACTIVE
    if inj is not None:
        inj.on_kernel_stall()


def lose_map_output() -> bool:
    inj = _ACTIVE
    if inj is not None:
        return inj.lose_map_output()
    return False


def on_task_attempt(partition_id: int, attempt: int, token=None) -> None:
    inj = _ACTIVE
    if inj is not None:
        inj.on_task_attempt(partition_id, attempt, token)


def cache_stale_fence() -> bool:
    inj = _ACTIVE
    if inj is not None:
        return inj.cache_stale_fence()
    return False


def cache_crash_before_rename() -> bool:
    inj = _ACTIVE
    if inj is not None:
        return inj.cache_crash_before_rename()
    return False


def cache_post_write_damage() -> Optional[str]:
    inj = _ACTIVE
    if inj is not None:
        return inj.cache_post_write_damage()
    return None


def cache_lock_holder_ms() -> float:
    inj = _ACTIVE
    if inj is not None:
        return inj.cache_lock_holder_ms()
    return 0.0


@contextmanager
def scoped(config_or_injector):
    """Install a fault scenario process-wide for the duration of the block
    (no-op when None). Accepts a ``FaultConfig`` (fresh counters) or a
    ``FaultInjector`` (counters persist across scopes — the session reuses
    ONE injector for its lifetime so every-Nth counters accumulate across
    queries). The injector is global on purpose: partition tasks run on
    thread pools and the injection points must see it from any thread.

    Concurrent scopes are refcounted by injector identity: the serve path
    enters this from one worker thread PER query, all sharing the
    session's injector, and a plain save/restore would let interleaved
    exits resurrect a stale injector (thread A restores None while B
    still runs, B then restores A's injector — installed forever). The
    injector uninstalls only when the LAST holder exits. A scope with a
    different injector shadows the current one (tests nesting configs)
    and restores it when its own count drains."""
    global _ACTIVE, _ACTIVE_COUNT
    if config_or_injector is None:
        yield None
        return
    inj = (
        config_or_injector
        if isinstance(config_or_injector, FaultInjector)
        else FaultInjector(config_or_injector)
    )
    with _INSTALL_LOCK:
        if _ACTIVE is inj:
            _ACTIVE_COUNT += 1
        else:
            if _ACTIVE is not None:
                _SHADOWED.append((_ACTIVE, _ACTIVE_COUNT))
            _ACTIVE = inj
            _ACTIVE_COUNT = 1
    try:
        yield inj
    finally:
        with _INSTALL_LOCK:
            if _ACTIVE is inj:
                _ACTIVE_COUNT -= 1
                if _ACTIVE_COUNT <= 0:
                    if _SHADOWED:
                        _ACTIVE, _ACTIVE_COUNT = _SHADOWED.pop()
                    else:
                        _ACTIVE, _ACTIVE_COUNT = None, 0
            else:
                # exiting while shadowed (out-of-order exit across threads):
                # drain this injector's count on the shadow stack instead
                for i in range(len(_SHADOWED) - 1, -1, -1):
                    s, c = _SHADOWED[i]
                    if s is inj:
                        if c <= 1:
                            del _SHADOWED[i]
                        else:
                            _SHADOWED[i] = (s, c - 1)
                        break


def config_from_conf(conf) -> Optional[FaultConfig]:
    """FaultConfig from the spark.rapids.tpu.faults.* keys; None unless
    spark.rapids.tpu.faults.enabled."""
    from .. import config as cfg

    if not cfg.FAULTS_ENABLED.get(conf):
        return None
    return FaultConfig(
        seed=cfg.FAULTS_SEED.get(conf),
        device_oom_every_n=cfg.FAULTS_DEVICE_OOM_EVERY_N.get(conf),
        oom_above_bytes=cfg.FAULTS_OOM_ABOVE_BYTES.get(conf),
        kernel_error_every_n=cfg.FAULTS_KERNEL_ERROR_EVERY_N.get(conf),
        compile_fail_every_n=cfg.FAULTS_COMPILE_FAIL_EVERY_N.get(conf),
        spill_write_error_every_n=cfg.FAULTS_SPILL_WRITE_ERROR_EVERY_N.get(conf),
        spill_read_error_every_n=cfg.FAULTS_SPILL_READ_ERROR_EVERY_N.get(conf),
        tcp_drop_every_n=cfg.FAULTS_TCP_DROP_EVERY_N.get(conf),
        tcp_delay_every_n=cfg.FAULTS_TCP_DELAY_EVERY_N.get(conf),
        tcp_delay_ms=cfg.FAULTS_TCP_DELAY_MS.get(conf),
        tcp_corrupt_every_n=cfg.FAULTS_TCP_CORRUPT_EVERY_N.get(conf),
        kernel_stall_every_n=cfg.FAULTS_KERNEL_STALL_EVERY_N.get(conf),
        kernel_stall_ms=cfg.FAULTS_KERNEL_STALL_MS.get(conf),
        compile_delay_every_n=cfg.FAULTS_COMPILE_DELAY_EVERY_N.get(conf),
        compile_delay_ms=cfg.FAULTS_COMPILE_DELAY_MS.get(conf),
        cache_truncate_every_n=cfg.FAULTS_CACHE_TRUNCATE_EVERY_N.get(conf),
        cache_corrupt_every_n=cfg.FAULTS_CACHE_CORRUPT_EVERY_N.get(conf),
        cache_stale_version_every_n=(
            cfg.FAULTS_CACHE_STALE_VERSION_EVERY_N.get(conf)
        ),
        cache_crash_before_rename_every_n=(
            cfg.FAULTS_CACHE_CRASH_BEFORE_RENAME_EVERY_N.get(conf)
        ),
        cache_lock_holder_every_n=(
            cfg.FAULTS_CACHE_LOCK_HOLDER_EVERY_N.get(conf)
        ),
        cache_lock_holder_hold_ms=(
            cfg.FAULTS_CACHE_LOCK_HOLDER_HOLD_MS.get(conf)
        ),
        map_output_loss_every_n=(
            cfg.FAULTS_MAP_OUTPUT_LOSS_EVERY_N.get(conf)
        ),
        stall_partition=cfg.FAULTS_STALL_PARTITION.get(conf),
        stall_partition_s=cfg.FAULTS_STALL_PARTITION_S.get(conf),
    )
