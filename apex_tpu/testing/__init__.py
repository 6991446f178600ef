"""Test utilities (reference: apex/testing/common_utils.py:1-22 — the
ROCm skip machinery; here the platform conditionals are TPU/CPU)."""

from __future__ import annotations

import functools
import os

__all__ = ["TEST_WITH_TPU", "skipIfNoTpu", "skipIfCpu"]

TEST_WITH_TPU = os.environ.get("APEX_TPU_TEST_WITH_TPU", "0") == "1"


def _platform() -> str:
    import jax

    return jax.default_backend()


def skipIfNoTpu(fn):
    """Skip unless a real TPU backend is attached (the ``skipIfRocm``
    shape, inverted for our platform)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        import unittest

        if _platform() != "tpu":
            raise unittest.SkipTest("test requires a TPU backend")
        return fn(*args, **kwargs)

    return wrapper


def skipIfCpu(fn):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        import unittest

        if _platform() == "cpu":
            raise unittest.SkipTest("test skipped on CPU")
        return fn(*args, **kwargs)

    return wrapper
