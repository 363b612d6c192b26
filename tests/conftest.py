import os

# 8 virtual CPU devices for multi-chip sharding tests (the chip run of the
# same path is chip_smoke.py's multichip phase). XLA_FLAGS must be set
# before the CPU backend initialises. The suite is held to the CPU whatever
# the environment says, so a bare `pytest` on a TPU host never takes the
# chip.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import time  # noqa: E402

import pytest  # noqa: E402

# Tier-1 runs with the dynamic handle ledger ON (wrapped at rpc._load
# time, so this must be set before any native test touches rpc): every
# native test is gated on zero NET leaked handles by the autouse fixture
# below.  Creation-stack capture is sampled (the RACECHECK knob — the
# race harness itself stays off) so the ledger's per-call cost is dict
# bookkeeping, not stack formatting; live COUNTS stay exact.  Export
# BRPC_TPU_HANDLECHECK=0 to opt the whole run out.
os.environ.setdefault("BRPC_TPU_HANDLECHECK", "1")
os.environ.setdefault("BRPC_TPU_RACECHECK_SAMPLE", "32")

# Test modules that need the native core (cpp/ -> libbrpc_tpu_c.so) end to
# end; without a cmake/ninja toolchain they SKIP with a reason instead of
# erroring at the first rpc.Server(). Individual tests elsewhere opt in
# with @pytest.mark.needs_native.
_NATIVE_TEST_FILES = {
    "test_native_rpc.py",
    "test_ps_remote.py",
    "test_naming_py.py",
    "test_ps_device.py",
}

_native_state = None  # (available: bool, reason: str), probed once


def _native_core():
    global _native_state
    if _native_state is None:
        from brpc_tpu import rpc
        try:
            rpc._load()
            _native_state = (True, "")
        except rpc.NativeCoreUnavailable as e:
            _native_state = (False, str(e).splitlines()[0])
    return _native_state


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "needs_native: test requires the native cpp core "
        "(skipped when cmake/ninja can't build it)")
    config.addinivalue_line(
        "markers",
        "allow_handle_leak: exempt this test from the per-test "
        "zero-net-leaked-handles gate (deliberate leak fixtures)")


def _is_native_item(item) -> bool:
    return item.fspath.basename in _NATIVE_TEST_FILES \
        or "needs_native" in item.keywords


@pytest.fixture(autouse=True)
def _handle_leak_gate(request):
    """The tier-1 leak gate: every native test must end with zero NET
    leaked native handles — the dynamic ledger's live counts per kind
    may not grow across the test.  Teardown that completes
    asynchronously (stream close handshakes, the socket-failure
    receiver teardown) gets a bounded drain window before the verdict;
    a failure prints the leaked handles WITH their creation stacks.
    Opt a deliberate-leak fixture out with
    ``@pytest.mark.allow_handle_leak``."""
    item = request.node
    if not _is_native_item(item) or \
            "allow_handle_leak" in item.keywords or \
            not _native_core()[0]:
        yield
        return
    from brpc_tpu.analysis import handles
    if not handles.enabled():
        yield
        return
    before = handles.live_counts()
    yield
    deadline = time.monotonic() + 2.0
    while True:
        leaked = {k: v - before.get(k, 0)
                  for k, v in handles.live_counts().items()
                  if v > before.get(k, 0)}
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    if leaked:
        stacks = "\n\n".join(
            r.format() for r in handles.live()
            if leaked.get(r.kind, 0) > 0)
        pytest.fail(
            f"test leaked native handles (net growth {leaked}); every "
            f"brt_* handle must be released before the test ends "
            f"(close/join/abort), or mark a deliberate leak with "
            f"@pytest.mark.allow_handle_leak\n{stacks}",
            pytrace=False)


def pytest_collection_modifyitems(config, items):
    needy = [item for item in items
             if item.fspath.basename in _NATIVE_TEST_FILES
             or "needs_native" in item.keywords]
    if not needy:
        return
    available, why = _native_core()
    if available:
        return
    skip = pytest.mark.skip(reason=f"native core unavailable: {why}")
    for item in needy:
        item.add_marker(skip)
