"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware isn't available in CI; sharding correctness is
validated on a virtual 8-device CPU backend (the driver separately dry-runs
the multi-chip path via __graft_entry__.dryrun_multichip). Env must be set
before jax initializes, hence module scope here.
"""

import os
import sys

# Tests run on the virtual 8-device CPU backend deterministically, whatever
# the shell exported; the env must be in place before the first jax import.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from kube_batch_tpu.envutil import apply_cpu_env  # noqa: E402

# Honor a developer-supplied device count (e.g. XLA_FLAGS=...count=2 pytest
# to reproduce a 2-device sharding bug); default to the 8-device mesh.
_has_count = "--xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
apply_cpu_env(n_devices=None if _has_count else 8)
# Tests persist no compiles: server entry points the suite drives call
# enable_persistent_compilation_cache(), which would otherwise point the
# whole pytest process (and every child) at the checkout's .jax_cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import tempfile  # noqa: E402

# Flight-recorder dumps (obs/recorder.py) triggered by tests — budget
# sheds, guard trips, duplicate binds — must never land in the checkout;
# route them to a per-session temp dir unless a test overrides the knob.
os.environ.setdefault(
    "KB_TRACE_DIR", tempfile.mkdtemp(prefix="kb-flight-test-")
)

import pytest  # noqa: E402

# Run the whole suite under the lockdep runtime lock-order validator (the
# `go test -race` analog, kube_batch_tpu/analysis/lockdep.py): instrumented
# locks in cache/, cmd/server, k8s/watch and metrics/ record the
# acquisition-order graph while the ordinary tests execute; inversions or
# blocking-under-lock fail the run. Disable with KBT_LOCKDEP=0.
pytest_plugins = ["kube_batch_tpu.analysis.pytest_plugin"]


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: scale tests (seconds-long solves); always run in CI"
    )


@pytest.fixture(autouse=True)
def _no_thread_leaks(monkeypatch):
    """Worker-shutdown discipline (kbt tier D's runtime sibling): no NEW
    non-daemon thread may survive a test.  Every worker this codebase
    starts — writeback pool, status/dispatch pools, batcher, publisher
    encode, follower pull, prewarm, admin-http — has a bounded join on its
    shutdown path; the assert below verifies those joins actually reap
    everything.  Caches and schedulers the test constructed but never
    stopped are reaped here first (their stop()/close() are idempotent, so
    tests that do shut down pay nothing) — the discipline this fixture
    enforces is "every worker's owner has a working bounded join", not
    "every test calls stop()".  Daemon threads are exempt (they cannot
    block interpreter exit), and a short grace window absorbs workers that
    are mid-exit when the test body returns."""
    import threading
    import weakref
    import time as _time

    from kube_batch_tpu.cache.cache import SchedulerCache
    from kube_batch_tpu.scheduler import Scheduler

    caches, scheds = [], []
    orig_cache_init = SchedulerCache.__init__
    orig_sched_init = Scheduler.__init__

    def _cache_init(self, *a, **kw):
        orig_cache_init(self, *a, **kw)
        caches.append(weakref.ref(self))

    def _sched_init(self, *a, **kw):
        orig_sched_init(self, *a, **kw)
        scheds.append(weakref.ref(self))

    monkeypatch.setattr(SchedulerCache, "__init__", _cache_init)
    monkeypatch.setattr(Scheduler, "__init__", _sched_init)

    before = set(threading.enumerate())
    yield
    # reap schedulers before caches: a draining writeback may still
    # dispatch binds through the cache's pools
    for ref in scheds:
        s = ref()
        if s is not None:
            try:
                s.close()
            except Exception:
                pass  # the leak assert below still catches unreaped threads
    for ref in caches:
        c = ref()
        if c is not None:
            try:
                c.stop()
            except Exception:
                pass
    deadline = _time.monotonic() + 2.0
    leaked = []
    while True:
        leaked = [
            t for t in threading.enumerate()
            if t.is_alive() and not t.daemon and t not in before
        ]
        if not leaked or _time.monotonic() > deadline:
            break
        _time.sleep(0.05)
    assert not leaked, (
        "non-daemon thread(s) leaked by this test: "
        f"{sorted(t.name for t in leaked)} — every worker must be joined "
        "(bounded) on the owning object's stop()/close()"
    )


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Free each module's compiled executables at its teardown.  Every live
    XLA:CPU executable holds memory mappings, and the ~800 tests of the
    single-process gate otherwise accumulate past ``vm.max_map_count``
    (65,530 here: 63.9k mappings by test ~750, then XLA aborts inside
    whichever compile comes next).  With this the gate peaks near 21.5k.
    Test-harness only — fewer programs per process (ROADMAP D1) is the
    structural fix."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    return jax.devices()
