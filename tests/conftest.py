"""Test harness configuration.

JAX tests run on a virtual 8-device CPU mesh (the driver separately validates
the multi-chip path via ``__graft_entry__.dryrun_multichip``).  Server tests
run against an in-memory SQLite database.

Notes:
- Tests must never take the chip (one process at a time may hold it, and a
  test run is not that process): the CPU is forced.  ``JAX_PLATFORMS=cpu``
  in the environment is enough on its own; the ``jax.config.update`` below
  does the same for a bare ``pytest`` started without it.
- ``XLA_FLAGS`` is read at CPU-client creation, so setting it here (before the
  first backend use) is sufficient.
- pytest-asyncio is not in the image; coroutine tests are run via
  ``asyncio.run`` from a ``pytest_pyfunc_call`` hook.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio
import inspect

import jax
import pytest

jax.config.update("jax_platforms", "cpu")


def pytest_pyfunc_call(pyfuncitem):
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        sig = inspect.signature(func)
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in sig.parameters
            if name in pyfuncitem.funcargs
        }

        async def _run():
            try:
                await func(**kwargs)
            finally:
                # close this loop's cached aiohttp session (agent clients
                # keep one per loop; the loop dies with this test)
                from dstack_tpu.server.services.runner import client

                await client.close_sessions()

        asyncio.run(_run())
        return True
    return None


@pytest.fixture
def cpu_devices():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must provide 8 virtual CPU devices"
    return devices
