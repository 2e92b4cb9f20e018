"""Instances of a configuration, made from the configuration and a seed.

A configuration names its generator (``"gen"``), a module
``bench/gens/<gen>.py`` whose ``instance(config)`` builds the instance
the configuration states; ``--seed`` only relabels the racks onto ports
(`relabel_ports`), so every seed gives the same sizes, releases and
weights in another port order.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Instance:
    """A coflow instance as plain arrays (original coflow indexing)."""

    demands: np.ndarray  # (M, N, N) float64
    weights: np.ndarray  # (M,)
    releases: np.ndarray  # (M,)
    rates: np.ndarray  # (K,)
    delta: float

    @property
    def num_coflows(self) -> int:
        return self.demands.shape[0]

    @property
    def num_ports(self) -> int:
        return self.demands.shape[1]

    @property
    def num_cores(self) -> int:
        return self.rates.shape[0]

    @property
    def num_flows(self) -> int:
        return int(np.count_nonzero(self.demands))

    def subset(self, idx) -> "Instance":
        idx = np.asarray(idx)
        return dataclasses.replace(
            self, demands=self.demands[idx], weights=self.weights[idx],
            releases=self.releases[idx],
        )


def config_instance(config: dict) -> Instance:
    """The configuration's instance, before any seed, from its generator."""
    from harness import registry

    return registry.generator(config["gen"]).instance(config)


def relabel_ports(inst: Instance, seed: int) -> Instance:
    """The same instance with its racks mapped onto ports by a permutation
    drawn from ``seed`` (ingress and egress alike)."""
    perm = np.random.default_rng(seed).permutation(inst.num_ports)
    demands = np.zeros_like(inst.demands)
    demands[:, perm[:, None], perm[None, :]] = inst.demands
    return dataclasses.replace(inst, demands=demands)


def first_by_release(inst: Instance, count: int) -> Instance:
    """The ``count`` earliest-released coflows (the stream's arrivals)."""
    order = np.argsort(inst.releases, kind="stable")
    return inst.subset(order[:count])
