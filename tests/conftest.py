"""Test environment: force JAX onto a virtual 8-device CPU platform so
multi-chip sharding tests run anywhere (the driver separately dry-runs the
multi-chip path), and give every test a scratch dir."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Tests compile small CPU programs in six xdist workers at once: no
# shared persistent cache under the checkout (dbeel_tpu/device.py
# places one for the server, the bench and the smoke).
jax.config.update("jax_enable_compilation_cache", False)

import asyncio  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager  # noqa: E402

import pytest  # noqa: E402

from dbeel_tpu import flow_events  # noqa: E402

flow_events.enable()


# ----------------------------------------------------------------------
# Per-test watchdog: a stalled test (a hung node, a long compile) must
# fail THAT test in under two minutes instead of wedging the whole suite
# (pytest-timeout is not in the image; SIGALRM interrupts blocking
# syscalls via EINTR, and Python runs the handler before retrying them,
# PEP 475).  Override with DBEEL_TEST_TIMEOUT_S (0 disables).
# ----------------------------------------------------------------------

_TEST_TIMEOUT_S = int(os.environ.get("DBEEL_TEST_TIMEOUT_S", "110"))


@contextmanager
def _alarm(phase, item):
    if _TEST_TIMEOUT_S <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def handler(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} {phase} exceeded the {_TEST_TIMEOUT_S}s "
            f"suite watchdog"
        )

    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _alarm("setup", item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _alarm("call", item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _alarm("teardown", item):
        return (yield)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-horizon harnesses (chaos soak smoke) excluded "
        "from tier-1 by -m 'not slow'",
    )


@pytest.fixture
def tmp_dir():
    d = tempfile.mkdtemp(prefix="dbeel_tpu_test_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


def run(coro, timeout: float = 10.0):
    """Run a test coroutine under a global timeout (the reference bounds
    every harness run at 10s, test_utils/src/lib.rs:20,74)."""
    async def _wrapped():
        return await asyncio.wait_for(coro, timeout)

    return asyncio.run(_wrapped())


@pytest.fixture
def arun():
    return run


def write_sstable_fixture(dir_path, idx, entries):
    """Shared test fixture writer: a raw sorted sstable (data+index)
    from (key, value, ts) triples — the on-disk layout in one place."""
    import numpy as np

    from dbeel_tpu.storage.entry import (
        DATA_FILE_EXT,
        INDEX_FILE_EXT,
        encode_entry,
        file_name,
    )

    data = b"".join(encode_entry(k, v, ts) for k, v, ts in entries)
    index = np.zeros(
        len(entries),
        dtype=np.dtype(
            [("offset", "<u8"), ("key_size", "<u4"), ("full_size", "<u4")]
        ),
    )
    off = 0
    for i, (k, v, ts) in enumerate(entries):
        index[i] = (off, len(k), 16 + len(k) + len(v))
        off += 16 + len(k) + len(v)
    with open(f"{dir_path}/{file_name(idx, DATA_FILE_EXT)}", "wb") as f:
        f.write(data)
    with open(f"{dir_path}/{file_name(idx, INDEX_FILE_EXT)}", "wb") as f:
        f.write(index.tobytes())
