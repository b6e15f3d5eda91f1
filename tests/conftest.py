"""Test harness: 8 virtual CPU devices (the reference's ``local[N]`` mode,
SURVEY.md §4).

The tests are CPU tests — correctness, counts and structure; nothing
they time is a device number — so they pin the CPU backend whatever the
host has (``jax.config.update`` after import, plus ``XLA_FLAGS`` set
in-process before any backend initializes).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8 and devs[0].platform == "cpu", devs
    return devs
