"""Sharded-sweep parity: 8 forced host devices vs the single-device run.

`XLA_FLAGS=--xla_force_host_platform_device_count=N` must precede jax
init, so the sharded run executes in a fresh interpreter (the dry-run
smoke's pattern).  The subprocess runs the same mixed-shape ensemble —
with bucket sizes that do NOT divide the device count — through
`sweep()` and `sweep(mesh=make_local_mesh())` and asserts bit-identical
per-coflow CCTs, LP objectives, and byte-identical JSON/CSV row
artifacts: once on the calendar the CPU picks (the host one), and once
with the device calendar forced, so its member-sharded program
(`batch_circuit._run_calendar_pairs_sharded`) runs over the 8 devices.
"""

import os
import subprocess
import sys

_SCRIPT = r"""
import json, os, sys
import numpy as np
import jax

assert len(jax.devices()) == 8, jax.devices()

device_calendar = sys.argv[1:] == ["device-calendar"]
if device_calendar:
    from repro.pipeline import batch_circuit as bc

    bc._engine = lambda: "kernel"
    sharded_calendar = bc._run_calendar_pairs_sharded
    calls = []
    bc._run_calendar_pairs_sharded = (
        lambda *a, **k: calls.append(a) or sharded_calendar(*a, **k)
    )

from repro.experiments import sweep
from repro.launch.mesh import data_axis_size, make_local_mesh
from repro.traffic.instances import random_instance

# Two shape buckets — (8, 8) x3 and (16, 8) x2 under the default
# quantum=8 bucketing — so per-bucket member-axis round-up is exercised
# twice; neither bucket size divides the 8-way data axis.
ens = (
    [random_instance(num_coflows=8, num_ports=4, seed=s) for s in range(3)]
    + [random_instance(num_coflows=10, num_ports=3, seed=9 + s)
       for s in range(2)]
)
from repro.experiments import build_buckets
assert sorted(len(b) for b in build_buckets(ens)) == [2, 3]
metas = [{"seed": i} for i in range(len(ens))]

mesh = make_local_mesh()
assert data_axis_size(mesh) == 8

res_single = sweep(ens, lp_iters=150, metas=metas)
res_sharded = sweep(ens, lp_iters=150, metas=metas, mesh=mesh)

for a, b in zip(res_single.records, res_sharded.records):
    assert a.lp.objective == b.lp.objective
    assert np.array_equal(a.lp.completion, b.lp.completion)
    for s in a.results:
        assert np.array_equal(a.results[s].ccts, b.results[s].ccts), s
        assert (
            a.results[s].total_weighted_cct
            == b.results[s].total_weighted_cct
        ), s

j0, c0 = res_single.save("parity_single")
j1, c1 = res_sharded.save("parity_sharded")
with open(j0, "rb") as f:
    single_json = f.read()
with open(j1, "rb") as f:
    sharded_json = f.read()
assert single_json == sharded_json, "JSON rows diverged"
with open(c0, "rb") as f:
    single_csv = f.read()
with open(c1, "rb") as f:
    sharded_csv = f.read()
assert single_csv == sharded_csv, "CSV rows diverged"
if device_calendar:
    assert calls, "the sharded sweep ran no sharded device calendar"
print("SHARDED-PARITY-OK")
"""


def _run(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, *args],
        capture_output=True,
        text=True,
        timeout=900,
        # Inherit the environment: a minimal env (no HOME) can stall CPython
        # startup for minutes on some hosts (see test_dryrun_smoke history).
        env={
            **os.environ,
            "PYTHONPATH": "src",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "REPRO_RESULTS": str(tmp_path),
        },
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED-PARITY-OK" in proc.stdout


def test_sharded_sweep_bit_identical_subprocess(tmp_path):
    _run(tmp_path)


def test_sharded_device_calendar_bit_identical_subprocess(tmp_path):
    _run(tmp_path, "device-calendar")
