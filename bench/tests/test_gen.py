"""The benchmark's copy of the trace generator gives each configuration
the instance the program's generator gave it when the benchmark was
defined (flows and demand recorded from it), and a seed only relabels
ports."""

import numpy as np
import pytest

from harness import gen, registry

# (flows, demand sum, weight sum) of each configuration's instance, and
# of the stream's 48 earliest coflows, from `repro.traffic.instances.
# sample_instance` with the configuration's cut, release-ordered slice.
FROZEN = {
    "fb150_k2": (5303, 495.5284179640032, 76.12245247652311),
    "fb48_k4": (14914, 5077.9811430400805, 1103.599014015941),
}
STREAM48 = (3925, 522.9822039929356)


@pytest.mark.parametrize("name", sorted(FROZEN))
def test_instance_fingerprint(name):
    inst = gen.config_instance(registry.config(name))
    flows, demand, weights = FROZEN[name]
    assert inst.num_flows == flows
    assert float(inst.demands.sum()) == demand
    assert float(inst.weights.sum()) == weights


def test_stream_arrivals_fingerprint():
    inst = gen.first_by_release(
        gen.config_instance(registry.config("fb48_k4")), 48)
    assert (inst.num_flows, float(inst.demands.sum())) == STREAM48


def test_seed_relabels_ports_only():
    base = gen.config_instance(registry.config("fb150_k2"))
    a, b = gen.relabel_ports(base, 7), gen.relabel_ports(base, 2**31 + 5)
    for x in (a, b):
        assert np.array_equal(np.sort(x.demands, axis=None),
                              np.sort(base.demands, axis=None))
        assert np.array_equal(x.weights, base.weights)
        assert np.array_equal(x.releases, base.releases)
    assert not np.array_equal(a.demands, b.demands)
    assert np.array_equal(gen.relabel_ports(base, 7).demands, a.demands)
