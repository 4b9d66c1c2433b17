"""Shared fixtures.  NOTE: XLA_FLAGS / device-count tricks are strictly
confined to launch/dryrun.py and subprocess-based tests — the main test
process must see the real single CPU device."""
import numpy as np
import pytest

from repro.core.problem import Layer, Workload


@pytest.fixture(scope="session")
def tiny_workload() -> Workload:
    return Workload(layers=(
        Layer.conv(64, 64, 3, 56, name="c1"),
        Layer.matmul(512, 1024, 768, name="m1"),
        Layer.conv(128, 256, 3, 28, stride=2, name="c2"),
    ), name="tiny")


@pytest.fixture(scope="session")
def rng() -> np.random.Generator:
    return np.random.default_rng(1234)
