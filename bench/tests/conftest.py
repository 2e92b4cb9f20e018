"""Tests of the benchmark itself: run with ``JAX_PLATFORMS=cpu python -m
pytest bench/tests``.  They use tiny configurations on the CPU."""

import copy
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def tiny(name: str) -> dict:
    """A configuration cut to a dozen ports, for the CPU."""
    from harness import registry

    c = copy.deepcopy(registry.config(name))
    c["trace"].update(num_coflows=120, num_machines=16)
    c["num_ports"] = 12
    c["cut"]["num_coflows"] = 60
    c["first_coflow"] = 0 if "service" in c else 4
    c["num_coflows"] = 60 if "service" in c else 12
    c["lp_iters"] = 200
    if "service" in c:
        c["service"]["lp_iters_warm"] = 60
    return c


@pytest.fixture
def tiny_config():
    return tiny
