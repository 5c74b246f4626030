"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding
(mesh/pjit/shard_map) is exercised without TPU hardware. Must run before jax
is imported anywhere.
"""
import os
import sys

# Default to the virtual 8-device CPU platform (multi-chip sharding without
# hardware). The engine's deterministic mode is the exact INTEGER spec
# (tpu/intscore.py), so its selections are bit-identical on every backend
# and the parity suites may also run with the device side on the chip:
#   NOMAD_TPU_TEST_PLATFORM=tpu python -m pytest tests/test_tpu_parity.py \
#       tests/test_intscore.py tests/test_preempt_kernels.py \
#       tests/test_system_engine.py
# The host pipeline stays pure Python, asserting plan parity ON the TPU.
_platform = os.environ.get("NOMAD_TPU_TEST_PLATFORM", "cpu")
if _platform != "cpu":
    # keep the CPU backend registered alongside the chip: the
    # cross-backend bit-equality test runs both in ONE process
    _platform = f"{_platform},cpu"
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if _platform == "cpu" and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# Concurrency hygiene (the -race / goroutine-leak analog this runtime can
# give): every Thread.start records its creation site; at session end any
# surviving thread is reported WITH the stack that started it, and leaked
# NON-daemon threads (which would hang interpreter exit) fail the run.
# faulthandler gives C-level stack dumps if the suite wedges.
# ---------------------------------------------------------------------------
import faulthandler as _faulthandler
import threading as _threading
import traceback as _traceback
import weakref as _weakref

_faulthandler.enable()

# weak keys: dead threads (and their target closures) must not be pinned
# for the whole session just to keep a leak report we will never print
_thread_origins = _weakref.WeakKeyDictionary()
_orig_thread_start = _threading.Thread.start


def _tracking_start(self):
    try:
        _thread_origins[self] = "".join(_traceback.format_stack(limit=6)[:-1])
    except Exception:
        pass
    return _orig_thread_start(self)


_threading.Thread.start = _tracking_start


def pytest_sessionfinish(session, exitstatus):
    import sys
    import time as _time

    _time.sleep(0.3)  # grace for teardown threads to wind down
    main = _threading.main_thread()
    leaked = [
        t for t in _threading.enumerate()
        if t is not main and t.is_alive()
    ]
    non_daemon = [t for t in leaked if not t.daemon]
    if leaked:
        print(f"\n[thread-hygiene] {len(leaked)} thread(s) alive at session "
              f"end ({len(non_daemon)} non-daemon):", file=sys.stderr)
        for t in leaked[:10]:
            origin = _thread_origins.get(t, "  <origin unknown>\n")
            print(f"  - {t.name} (daemon={t.daemon})\n{origin}",
                  file=sys.stderr)
    if non_daemon:
        # a non-daemon leak blocks interpreter exit: that is a real bug
        session.exitstatus = 1
        print("[thread-hygiene] FAILING: non-daemon threads leaked",
              file=sys.stderr)
