"""The paper's Sec. V-A default setting as an ensemble of sampled
instances, stacked into one instance.

Member ``i`` samples ``num_ports`` racks and ``num_coflows`` coflows from
the FB2010 trace's stand-in with sample seed ``first_member_seed + i``
(`fb_trace`'s copied trace and sampler), and sits at coflow rows
``[num_coflows * i, num_coflows * (i + 1))`` of the stacked instance; all
members share the rates and delta.  The ensemble driver
(``bench/drivers/ensemble.py``) splits the stack back into its members.
"""

from __future__ import annotations

import numpy as np

from harness import registry
from harness.gen import Instance


def members(config: dict) -> list[Instance]:
    """The configuration's members, each one sampled instance."""
    fb = registry.generator("fb_trace")
    tr = config["trace"]
    trace = fb.synthesize_facebook_like(
        num_coflows=tr["num_coflows"], num_machines=tr["num_machines"],
        seed=tr["trace_seed"], mean_interarrival_ms=tr["mean_interarrival_ms"],
    )
    first = config["first_member_seed"]
    return [
        fb.sample_instance(
            trace, num_ports=config["num_ports"],
            num_coflows=config["num_coflows"], rates=config["rates"],
            delta=config["delta"], seed=first + i, release=config["release"],
        )
        for i in range(config["members"])
    ]


def instance(config: dict) -> Instance:
    """The members stacked along the coflow axis, in member order."""
    ms = members(config)
    return Instance(
        demands=np.concatenate([m.demands for m in ms]),
        weights=np.concatenate([m.weights for m in ms]),
        releases=np.concatenate([m.releases for m in ms]),
        rates=ms[0].rates, delta=ms[0].delta,
    )
