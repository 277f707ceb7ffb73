"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests must run anywhere, chip or no chip, and must exercise multi-device
sharding; what runs on the TPU is chip_smoke.py, not the tests.  The
environment variable and the config flag are both set before the first
backend initialisation.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# fsync-per-commit is production behaviour; tests skip it for speed
# (dedicated durability tests re-enable via monkeypatching
# minio_tpu.storage.local.FSYNC_ENABLED)
os.environ.setdefault("MINIO_TPU_FSYNC", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# ------------------------------------------------------------ racecheck
# MINIO_TPU_RACECHECK=1 replays the whole run under the lockset race
# detector (minio_tpu/analysis/concurrency/racecheck.py): threading
# primitives created from here on are tracked and the designated
# shared-state surface (hotcache/brownout/MRF/replication/gateway-
# cache/drive-health counters) is watched.  Findings print at session
# end; MINIO_TPU_RACECHECK_STRICT=1 turns them into a session failure.
# The wiring must precede minio_tpu imports so product locks are the
# tracked kind.

if os.environ.get("MINIO_TPU_RACECHECK", "") == "1":
    from minio_tpu.analysis.concurrency import racecheck as _rc

    _rc.install()
    _rc.install_default_watches()


def _wire_sanitized_lib() -> None:
    """MINIO_TPU_SAN=asan|ubsan|tsan: build the sanitizer variant of the
    host library (csrc/Makefile `make <san>`) and point the loaders at
    it via MINIO_TPU_NATIVE_LIB — must run before the library is first
    loaded (ops/host.py lib_path reads the variable then).

    Loading a sanitized .so into a vanilla python needs the matching
    runtime LD_PRELOADed BEFORE process start, e.g.:

        LD_PRELOAD=$(g++ -print-file-name=libasan.so) \
            ASAN_OPTIONS=detect_leaks=0 MINIO_TPU_SAN=asan pytest ...

    Without the preload the CDLL load fails and the Python fallbacks
    silently take over — so we warn loudly rather than guess."""
    import shutil
    import subprocess
    import sys

    san = os.environ.get("MINIO_TPU_SAN", "").strip().lower()
    if not san:
        return
    if san not in ("asan", "ubsan", "tsan"):
        print(f"conftest: ignoring unknown MINIO_TPU_SAN={san!r}",
              file=sys.stderr)
        return
    csrc = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "csrc")
    lib = os.path.join(csrc, f"libminio_tpu_host_{san}.so")
    if shutil.which("make") is None or shutil.which("g++") is None:
        print(f"conftest: MINIO_TPU_SAN={san} set but no toolchain; "
              "native tiers will use the Python fallbacks",
              file=sys.stderr)
        return
    try:
        subprocess.run(["make", "-C", csrc, san], check=True,
                       capture_output=True, timeout=600)
    except Exception as e:
        print(f"conftest: sanitizer build failed ({e}); native tiers "
              "will use the Python fallbacks", file=sys.stderr)
        return
    os.environ["MINIO_TPU_NATIVE_LIB"] = lib
    runtime = {"asan": "libasan", "ubsan": "libubsan",
               "tsan": "libtsan"}[san]
    if runtime not in os.environ.get("LD_PRELOAD", ""):
        print(f"conftest: MINIO_TPU_SAN={san} but {runtime} is not in "
              "LD_PRELOAD — the sanitized library will fail to load "
              f"(run: LD_PRELOAD=$(g++ -print-file-name={runtime}.so) "
              "pytest ...)", file=sys.stderr)


_wire_sanitized_lib()

# Build the host library up front by the loader's own rule (a file named
# by a content hash of csrc/, built if missing), so no test pays for the
# compile and every test process opens the same file.
from minio_tpu.ops import host as _host  # noqa: E402

_host.lib_path()


# --------------------------------------------------------------- watchdog
# Per-test watchdog: a deadlocked admission queue (or any other hang)
# fails ONE test fast with a traceback instead of eating the whole
# 870 s tier-1 budget.  SIGALRM interrupts the main thread mid-test and
# the handler raises; pytest records the failure and moves on.  `slow`-
# marked tests are exempt; MINIO_TPU_TEST_TIMEOUT overrides the default
# (0 disables).

import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

_WATCHDOG_SECONDS = float(os.environ.get("MINIO_TPU_TEST_TIMEOUT", "300"))


class _WatchdogTimeout(Exception):
    pass


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`); sanitizer "
        "replays, chaos drills, long benches")
    config.addinivalue_line(
        "markers",
        "serial: latency-ceiling chaos drill; reordered to the END of "
        "the session and run in a fresh isolated pytest subprocess "
        "(no inherited background threads) — see conftest.py")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if (_WATCHDOG_SECONDS <= 0
            or item.get_closest_marker("slow") is not None
            or not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _fire(signum, frame):
        raise _WatchdogTimeout(
            f"watchdog: {item.nodeid} exceeded {_WATCHDOG_SECONDS:.0f}s "
            "(deadlock?)")

    old = signal.signal(signal.SIGALRM, _fire)
    signal.setitimer(signal.ITIMER_REAL, _WATCHDOG_SECONDS)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# ----------------------------------------------------------- serial drills
# The latency-ceiling chaos drills (tests/test_overload.py overload
# drill, tests/test_cli_integration.py chaos-healing cluster) measure
# wall clock against real deadlines; inside a full tier-1 run they were
# load-flaky: ~1400 earlier tests leave JIT caches, pool workers and
# service threads competing for this container's few cores, and a 3.0 s
# p99 ceiling loses to that noise a few percent of the time.  They
# always passed 3/3 in isolation — so tier-1 now RUNS them in
# isolation instead of documenting the flake: `serial`-marked items are
# reordered to the very end of the session and each executes in a
# fresh pytest subprocess (quiet interpreter, no inherited threads).
# MINIO_TPU_SERIAL_CHILD guards recursion; MINIO_TPU_SERIAL_ISOLATION=0
# restores in-process execution (debugging, pdb).

def _serial_isolation_enabled() -> bool:
    return os.environ.get("MINIO_TPU_SERIAL_ISOLATION", "1") != "0" \
        and not os.environ.get("MINIO_TPU_SERIAL_CHILD")


def _run_serial_isolated(item) -> None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["MINIO_TPU_SERIAL_CHILD"] = "1"
    # the child gets the whole remaining watchdog window minus a grace
    # for its own interpreter+jax startup being included in the parent's
    # SIGALRM budget
    budget = _WATCHDOG_SECONDS - 15 if _WATCHDOG_SECONDS > 0 else 870
    cmd = [sys.executable, "-m", "pytest", item.nodeid, "-q",
           "-p", "no:cacheprovider"]
    # the drills assert real-time latency ceilings (3 s budgets,
    # convergence windows) on a shared 2-core container whose load
    # varies run to run; a first attempt can start while the parent
    # suite's teardown is still paying CPU.  One VISIBLE retry in a
    # fresh child after a cooldown models the documented "passes in
    # isolation" contract — but ONLY when the failure matches a known
    # load-sensitive timing assertion: any other failure (a logic
    # regression, possibly racy) fails immediately rather than getting
    # a coin-flip second chance.
    load_shapes = ("blew the deadline", "statuses=",
                   "shed answered after", "not fully healed",
                   "never healed", "timed out")
    tails = []
    for attempt in (1, 2):
        try:
            proc = subprocess.run(cmd, cwd=repo, env=env, text=True,
                                  capture_output=True,
                                  timeout=max(60, budget))
        except subprocess.TimeoutExpired as ex:
            raise AssertionError(
                f"serial-isolated run of {item.nodeid} timed out after "
                f"{ex.timeout:.0f}s") from None
        if proc.returncode == 0:
            if tails:
                sys.stderr.write(
                    f"\n[serial-isolation] {item.nodeid}: attempt 1 "
                    "failed under residual load, attempt 2 passed in a "
                    "quiet child; attempt 1 tail:\n" + tails[0] + "\n")
            return
        tails.append("\n".join(
            (proc.stdout + "\n" + proc.stderr).strip().splitlines()[-40:]))
        if attempt == 1:
            if not any(p in tails[0] for p in load_shapes):
                break  # not a timing-ceiling failure: no retry
            time.sleep(5.0)  # let parent-suite teardown load settle
    raise AssertionError(
        f"serial-isolated run of {item.nodeid} failed"
        + (" twice" if len(tails) > 1 else "") + ":\n"
        + "\n\nretry:\n".join(tails))


# ------------------------------------------------------- fd leak check
# ISSUE 10 satellite: the shm/process sweep below catches leaked
# segments and workers; this catches leaked FILE DESCRIPTORS — the
# resource-lifecycle rule's dynamic counterpart.  Only fds opened onto
# regular files outside the interpreter/runtime are counted (pipes,
# sockets, eventfds and the interpreter's own files churn legitimately
# run to run); deleted-but-open staging files count too, they pin disk.

def _fd_table() -> dict[int, str]:
    out: dict[int, str] = {}
    try:
        for fd in os.listdir("/proc/self/fd"):
            try:
                out[int(fd)] = os.readlink(f"/proc/self/fd/{fd}")
            except (OSError, ValueError):
                pass
    except OSError:
        pass  # non-Linux: the check is a no-op
    return out


_FD_ALLOW_PREFIXES = tuple(p for p in (
    sys.prefix, getattr(sys, "base_prefix", ""),
    "/usr", "/proc", "/dev", "/sys",
    os.path.expanduser("~/.cache"),
) if p)


def _fd_is_leak(target: str) -> bool:
    deleted = target.endswith(" (deleted)")
    name = target[:-len(" (deleted)")] if deleted else target
    if not name.startswith("/"):
        return False  # pipe:[..], socket:[..], anon_inode:[..]
    if any(name.startswith(p) for p in _FD_ALLOW_PREFIXES):
        return False
    if deleted:
        return True  # open fd pinning an unlinked staging file
    return os.path.isfile(name)  # dirs / ptys are not data leaks


@pytest.fixture(scope="session", autouse=True)
def _fd_leak_check():
    before = _fd_table()
    yield
    import gc

    leaked: dict[int, str] = {}
    for _ in range(10):  # let closers/GC finish before judging
        gc.collect()
        # compare fd -> TARGET, not bare numbers: POSIX hands out the
        # lowest free fd, so a leak can land on a number the snapshot
        # already held (pointing somewhere else entirely)
        leaked = {fd: t for fd, t in _fd_table().items()
                  if before.get(fd) != t and _fd_is_leak(t)}
        if not leaked:
            return
        time.sleep(0.2)
    raise AssertionError(
        f"leaked file descriptors onto regular files: {leaked} — some "
        "test (or product close path) dropped an fd; see the "
        "resource-lifecycle rule for the usual shapes")


# ----------------------------------------------------- racecheck report
@pytest.fixture(scope="session", autouse=True)
def _racecheck_report():
    yield
    if os.environ.get("MINIO_TPU_RACECHECK", "") != "1":
        return
    from minio_tpu.analysis.concurrency import racecheck as _rc

    findings = _rc.TRACKER.findings()
    waived = _rc.TRACKER.waived()
    if waived:
        sys.stderr.write("\n[racecheck] waived locations:\n" + "".join(
            f"  {k}: {v}\n" for k, v in sorted(waived.items())))
    if findings:
        text = "\n".join(f"  {f!r}" for f in findings)
        sys.stderr.write(f"\n[racecheck] UNWAIVED FINDINGS:\n{text}\n")
        if os.environ.get("MINIO_TPU_RACECHECK_STRICT", "") == "1":
            raise AssertionError(
                f"racecheck: {len(findings)} unwaived lockset "
                f"finding(s):\n{text}")
    else:
        sys.stderr.write("\n[racecheck] clean: no unwaived lockset "
                         "findings\n")


# ------------------------------------------------------- shm leak check
# The multi-process data plane (minio_tpu/parallel/workers.py) creates
# named /dev/shm segments (mtpu-ring-<pid>-*) and spawns worker
# processes.  A test that leaks either would silently tax every later
# test (and a SIGKILL'd run would litter /dev/shm for the whole
# machine), so the session asserts both are gone at teardown — after
# shutting the plane down itself, which is also what guarantees the
# check runs even when a test forgot its own cleanup.  Only segments
# this process created count: tier-1 runs several pytest processes at
# once (xdist workers, the serial drills' children), and a session that
# read another's live rings as litter failed for no fault of its own
# and unlinked them under a running test.

@pytest.fixture(scope="session", autouse=True)
def _mp_plane_leak_check():
    yield
    from minio_tpu.parallel import workers as _workers

    _workers.shutdown_plane()
    try:
        leaked = sorted(f for f in os.listdir("/dev/shm")
                        if f.startswith(_workers.segment_prefix()))
    except OSError:
        leaked = []
    import multiprocessing as _mp

    kids = [p for p in _mp.active_children()
            if (p.name or "").startswith("mtpu-")]
    for p in kids:  # clean up so one failure doesn't cascade
        p.terminate()
    for f in leaked:
        try:
            os.unlink(os.path.join("/dev/shm", f))
        except OSError:
            pass
    assert not leaked, f"leaked shared-memory segments: {leaked}"
    assert not kids, ("leaked data-plane worker processes: "
                      f"{[p.name for p in kids]}")


def pytest_collection_modifyitems(config, items):
    if not _serial_isolation_enabled():
        return
    serial = [it for it in items
              if it.get_closest_marker("serial") is not None]
    if not serial:
        return
    rest = [it for it in items
            if it.get_closest_marker("serial") is None]
    items[:] = rest + serial
    for it in serial:
        # shadow Function.runtest on the instance: the call phase runs
        # the drill in its own subprocess instead of in-process
        it.runtest = (lambda _it=it: _run_serial_isolated(_it))
