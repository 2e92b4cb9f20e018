"""Content-addressed sweep cache tests (ISSUE acceptance criteria).

The cache is a pure memo over sweep cells: a cell key is the canonical
hash of (instance bits, scheme spec, pipeline/engine config, code
fingerprint), a hit short-circuits the batched pipeline entirely, and
cached runs must export **byte-identical** artifacts to fresh ones.

  * key sensitivity — identical specs collide, any perturbation of
    demands/weights/releases/rates/delta, scheme, config knob or code
    fingerprint separates;
  * sweep integration — replay computes zero cells (hit counters
    asserted), perturbing one instance recomputes exactly that
    instance's cells, adding a scheme recomputes only the new column;
  * persistence — the manifest survives a restart (new `SweepCache` on
    the same root serves hits), and missing object files self-heal as
    misses;
  * byte identity — JSON + CSV files written from cached rows equal the
    fresh run's bytes exactly.
"""

import json
import os

import numpy as np
import pytest

from repro.experiments import SweepCache, code_fingerprint, sweep
from repro.experiments.cache import (
    canonical_digest,
    cell_key,
    instance_digest,
    scheme_digest,
)
from repro.experiments.results import save_rows
from repro.traffic.instances import random_instance


def _ens(n=3, seed0=40):
    return [
        random_instance(
            num_coflows=8 + 2 * s, num_ports=4, num_cores=2, seed=seed0 + s
        )
        for s in range(n)
    ]


_KW = dict(schemes=("ours", "wspt_order"), lp_method="exact", validate=False)


class TestDigests:
    def test_instance_digest_deterministic(self):
        a, b = random_instance(seed=5), random_instance(seed=5)
        assert instance_digest(a) == instance_digest(b)

    @pytest.mark.parametrize(
        "field", ["demands", "weights", "releases", "rates", "delta"]
    )
    def test_instance_digest_sensitive(self, field):
        import dataclasses

        inst = random_instance(seed=5)
        if field == "delta":
            other = dataclasses.replace(inst, delta=inst.delta + 1.0)
        else:
            arr = np.array(getattr(inst, field), copy=True)
            arr.flat[0] += 1.0
            other = dataclasses.replace(inst, **{field: arr})
        assert instance_digest(inst) != instance_digest(other)

    def test_scheme_digest_separates_schemes(self):
        assert scheme_digest("ours") != scheme_digest("wspt_order")

    def test_config_digest_sensitive(self):
        base = dict(
            lp_method="exact", lp_iters=100, m_quantum=8, p_quantum=8,
            discipline="greedy", alloc="batch", circuit="batch",
            certify=False,
        )
        d0 = canonical_digest(base)
        assert d0 == canonical_digest(dict(base))
        for k, v in [("lp_iters", 200), ("discipline", "reserving"),
                     ("circuit", "loop"), ("certify", True)]:
            assert canonical_digest({**base, k: v}) != d0

    def test_cell_key_mixes_all_parts(self):
        parts = ["i", "s", "c", "f"]
        k0 = cell_key(*parts)
        for j in range(4):
            p = list(parts)
            p[j] = "x"
            assert cell_key(*p) != k0
        assert len(k0) == 64  # sha256 hex

    def test_code_fingerprint_stable_in_process(self):
        assert code_fingerprint() == code_fingerprint()


class TestStore:
    def test_put_get_round_trip(self, tmp_path):
        c = SweepCache(tmp_path)
        payload = {"total_weighted_cct": 12.5, "ccts": [1.0, 2.5]}
        c.put("k" * 64, payload)
        c.flush()
        assert SweepCache(tmp_path).get("k" * 64) == payload

    def test_get_missing_is_none(self, tmp_path):
        assert SweepCache(tmp_path).get("a" * 64) is None

    def test_missing_object_self_heals(self, tmp_path):
        c = SweepCache(tmp_path)
        c.put("b" * 64, {"x": 1})
        c.flush()
        obj = next((tmp_path / "objects").rglob("*.json"))
        obj.unlink()
        assert SweepCache(tmp_path).get("b" * 64) is None

    def test_manifest_merges_concurrent_writers(self, tmp_path):
        # Two handles on one root (the sharded-runner pattern): both
        # flush; neither clobbers the other's entries.
        c1, c2 = SweepCache(tmp_path), SweepCache(tmp_path)
        c1.put("c" * 64, {"x": 1})
        c2.put("d" * 64, {"y": 2})
        c1.flush()
        c2.flush()
        c3 = SweepCache(tmp_path)
        assert c3.get("c" * 64) == {"x": 1}
        assert c3.get("d" * 64) == {"y": 2}


class TestGc:
    """`SweepCache.gc`: LRU eviction + self-healing manifest rewrite."""

    @staticmethod
    def _fill(c, n, size=100):
        # Distinct keys with strictly increasing LRU stamps (the wall
        # clock's 1 s resolution would tie within a fast test run).
        keys = [format(i, "x") * 32 for i in range(n)]
        for i, k in enumerate(keys):
            c.put(k, {"blob": "x" * size})
            c._manifest[k]["created"] = f"2026-01-01T00:00:{i:02d}Z"
        return keys

    def test_noop_under_budget(self, tmp_path):
        c = SweepCache(tmp_path)
        keys = self._fill(c, 3)
        stats = c.gc(max_bytes=10**9)
        assert stats["evicted"] == 0 and stats["kept"] == 3
        assert all(c.get(k) is not None for k in keys)

    def test_evicts_oldest_first(self, tmp_path):
        c = SweepCache(tmp_path)
        keys = self._fill(c, 4)
        sz = os.path.getsize(c._object_path(keys[0]))
        stats = c.gc(max_bytes=2 * sz)
        assert stats["evicted"] == 2 and stats["bytes"] <= 2 * sz
        assert c.get(keys[0]) is None and c.get(keys[1]) is None
        assert c.get(keys[2]) is not None and c.get(keys[3]) is not None
        assert not os.path.exists(c._object_path(keys[0]))

    def test_hit_refreshes_lru_rank(self, tmp_path):
        c = SweepCache(tmp_path)
        keys = self._fill(c, 3)
        assert c.get(keys[0]) is not None  # stamps "accessed" = now
        sz = os.path.getsize(c._object_path(keys[0]))
        c.gc(max_bytes=sz)
        # keys[1] (oldest untouched) went first; the re-read oldest
        # cell was promoted to most-recent and survives to the end.
        assert c.get(keys[0]) is not None
        assert c.get(keys[1]) is None and c.get(keys[2]) is None

    def test_max_cells_budget(self, tmp_path):
        c = SweepCache(tmp_path)
        keys = self._fill(c, 5)
        stats = c.gc(max_cells=2)
        assert stats["kept"] == 2
        assert [k for k in keys if c.get(k) is not None] == keys[3:]

    def test_heals_dangling_entries(self, tmp_path):
        c = SweepCache(tmp_path)
        keys = self._fill(c, 3)
        os.remove(c._object_path(keys[1]))
        stats = c.gc()  # no budgets: pure self-heal pass
        assert stats == {
            "scanned": 3, "kept": 2, "evicted": 0, "healed": 1,
            "freed_bytes": 0, "bytes": stats["bytes"],
        }
        doc = json.loads((tmp_path / "manifest.json").read_text())
        assert sorted(doc["cells"]) == sorted([keys[0], keys[2]])

    def test_eviction_survives_restart(self, tmp_path):
        # The rewrite must NOT merge with the stale on-disk manifest:
        # evicted cells stay gone for a fresh handle on the same root.
        c = SweepCache(tmp_path)
        keys = self._fill(c, 4)
        c.flush()
        c.gc(max_cells=1)
        c2 = SweepCache(tmp_path)
        assert len(c2) == 1
        assert c2.get(keys[3]) is not None
        assert all(c2.get(k) is None for k in keys[:3])

    def test_gc_merges_unflushed_disk_entries(self, tmp_path):
        # Another worker's flushed cells are visible to gc even when this
        # handle never loaded them.
        other = SweepCache(tmp_path)
        other.put("e" * 64, {"x": 1})
        other.flush()
        c = SweepCache(tmp_path)
        c._manifest = {}  # simulate a handle opened before other's flush
        stats = c.gc(max_bytes=10**9)
        assert stats["kept"] == 1


class TestSweepIntegration:
    def test_replay_computes_zero_cells(self, tmp_path):
        ens = _ens()
        fresh = sweep(ens, cache=str(tmp_path), **_KW)
        assert fresh.cache_stats["computed"] == fresh.cache_stats["cells"] == 6
        replay = sweep(ens, cache=str(tmp_path), **_KW)
        assert replay.cache_stats["computed"] == 0
        assert replay.cache_stats["hits"] == 6

    def test_restart_serves_hits(self, tmp_path):
        ens = _ens()
        sweep(ens, cache=SweepCache(tmp_path), **_KW)
        replay = sweep(ens, cache=SweepCache(tmp_path), **_KW)
        assert replay.cache_stats["computed"] == 0

    def test_perturbed_instance_recomputes_only_its_cells(self, tmp_path):
        import dataclasses

        ens = _ens()
        sweep(ens, cache=str(tmp_path), **_KW)
        w = np.array(ens[1].weights, copy=True)
        w[0] += 1.0
        ens[1] = dataclasses.replace(ens[1], weights=w)
        res = sweep(ens, cache=str(tmp_path), **_KW)
        # 2 schemes x 1 perturbed instance.
        assert res.cache_stats == {
            "cells": 6, "hits": 4, "misses": 2, "computed": 2
        }

    def test_added_scheme_recomputes_only_new_column(self, tmp_path):
        ens = _ens()
        sweep(ens, cache=str(tmp_path), **_KW)
        res = sweep(
            ens,
            cache=str(tmp_path),
            **{**_KW, "schemes": ("ours", "wspt_order", "load_only")},
        )
        assert res.cache_stats["hits"] == 6
        assert res.cache_stats["computed"] == 3

    def test_config_change_invalidates(self, tmp_path):
        ens = _ens(2)
        sweep(ens, cache=str(tmp_path), **_KW)
        res = sweep(ens, cache=str(tmp_path), **{**_KW, "discipline": "reserving"})
        assert res.cache_stats["hits"] == 0

    def test_fingerprint_change_invalidates(self, tmp_path):
        ens = _ens(2)
        sweep(ens, cache=str(tmp_path), **_KW)
        stale = SweepCache(tmp_path, fingerprint="deadbeef")
        res = sweep(ens, cache=stale, **_KW)
        assert res.cache_stats["hits"] == 0
        assert res.cache_stats["computed"] == 4

    def test_rows_byte_identical(self, tmp_path):
        ens = _ens()
        plain = sweep(ens, **_KW)
        fresh = sweep(ens, cache=str(tmp_path), **_KW)
        replay = sweep(ens, cache=str(tmp_path), **_KW)
        blobs = {
            json.dumps(r.rows(), default=float)
            for r in (plain, fresh, replay)
        }
        assert len(blobs) == 1

    def test_artifact_files_byte_identical(self, tmp_path, monkeypatch):
        ens = _ens(2)
        out = tmp_path / "results"
        monkeypatch.setenv("REPRO_RESULTS", str(out))
        plain = sweep(ens, **_KW)
        save_rows("parity_fresh", plain.rows())
        replay = sweep(ens, cache=str(tmp_path / "cache"), **_KW)
        replay = sweep(ens, cache=str(tmp_path / "cache"), **_KW)
        assert replay.cache_stats["computed"] == 0
        save_rows("parity_replay", replay.rows())
        for ext in ("json", "csv"):
            a = (out / f"parity_fresh.{ext}").read_bytes()
            b = (out / f"parity_replay.{ext}").read_bytes()
            assert a.replace(b"parity_fresh", b"X") == b.replace(
                b"parity_replay", b"X"
            )

    def test_certified_sweep_caches_cert_fields(self, tmp_path):
        ens = _ens(2)
        kw = dict(schemes=("ours",), lp_method="exact", validate=False,
                  certify=True)
        fresh = sweep(ens, cache=str(tmp_path), **kw)
        replay = sweep(ens, cache=str(tmp_path), **kw)
        assert replay.cache_stats["computed"] == 0
        assert json.dumps(fresh.rows(), default=float) == json.dumps(
            replay.rows(), default=float
        )
        for row in replay.rows():
            if row["scheme"] == "ours":
                assert row["approx_ratio"] <= row["bound"] + 1e-9

    def test_certify_without_ours_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            sweep(
                _ens(1),
                cache=str(tmp_path),
                schemes=("wspt_order",),
                lp_method="exact",
                certify=True,
            )
