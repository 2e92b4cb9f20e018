"""Content-addressed result cache for instance sweeps.

The experiment fabric's memory: every sweep *cell* — one (instance,
scheme) pair under a fixed pipeline configuration — is keyed by a
canonical SHA-256 over

  * the **instance digest** (demand/weight/release/rate array bytes plus
    the reconfiguration delta),
  * the **scheme digest** (the registered `SchemeSpec`, as data — a
    re-registered scheme invalidates its cells),
  * the **config digest** (lp_method, lp_iters, bucket quanta,
    discipline, alloc/circuit paths, certify), and
  * the **code fingerprint** (repro package version + SHA-256 of every
    result-determining source file), so editing a stage implementation
    invalidates every cached cell without any manual versioning.

A hit short-circuits the batched pipeline entirely: `sweep(cache=...)`
solves the LP and runs order → alloc → circuit only for cells that miss,
and re-running an identical sweep computes *zero* cells.  Payloads hold
exactly the per-cell absolutes the row export reads
(``total_weighted_cct``, the realized CCT vector, ``lp_objective``, and
the certificate fields for certified OURS cells); normalized ratios are
derived at export time, so JSON/CSV artifacts are byte-identical whether
rows came from cache or fresh compute (floats round-trip exactly through
JSON).

On disk the cache is psim-shaped: ``objects/<k[:2]>/<key>.json`` payload
files plus a ``manifest.json`` index that survives process restarts and
merges on flush, so concurrent shard workers sharing one cache directory
(`repro.experiments.runner`) interleave safely — identical keys carry
identical content by construction.

Caveat: with collapse-to-ensemble-max bucketing (``m_quantum=None`` /
``p_quantum=None``) the LP's padded shape depends on the *ensemble*, not
the instance, so a cell's bits can depend on which instances it was
swept with; cache keys capture the quanta but not co-members.  The fixed
default quanta make padding per-instance and composition-independent.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Mapping

import numpy as np

__all__ = [
    "CachedLP",
    "CachedCertificate",
    "CachedScheduleResult",
    "SweepCache",
    "CacheStats",
    "canonical_digest",
    "instance_digest",
    "scheme_digest",
    "code_fingerprint",
    "cell_key",
]

_MANIFEST_SCHEMA = "sweep-cache-manifest-v1"


def _utcnow() -> str:
    """ISO-8601 UTC second-resolution stamp — the manifest's LRU clock."""
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


# --------------------------------------------------------------- digests
def _canonical(obj: Any) -> Any:
    """Reduce `obj` to a JSON-stable structure for hashing.

    Arrays become (shape, dtype, content-hash) triples; dict keys are
    sorted by the JSON encoder; floats rely on ``repr`` round-tripping.
    """
    if isinstance(obj, np.ndarray):
        arr = np.ascontiguousarray(obj)
        return {
            "__ndarray__": list(arr.shape),
            "dtype": str(arr.dtype),
            "sha256": hashlib.sha256(arr.tobytes()).hexdigest(),
        }
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, Mapping):
        return {str(k): _canonical(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _canonical(dataclasses.asdict(obj))
    raise TypeError(f"cannot canonicalize {type(obj).__name__} for hashing")


def canonical_digest(obj: Any) -> str:
    """SHA-256 hex digest of the canonical JSON form of `obj`."""
    payload = json.dumps(
        _canonical(obj), sort_keys=True, separators=(",", ":")
    )
    return hashlib.sha256(payload.encode()).hexdigest()


def instance_digest(instance) -> str:
    """Digest of one `CoflowInstance`'s problem data."""
    return canonical_digest(
        {
            "demands": np.asarray(instance.demands),
            "weights": np.asarray(instance.weights),
            "releases": np.asarray(instance.releases),
            "rates": np.asarray(instance.rates),
            "delta": float(instance.delta),
        }
    )


def scheme_digest(scheme: str) -> str:
    """Digest of the *registered spec* behind a scheme key (not the name:
    re-registering a scheme with different stages invalidates its cells)."""
    from repro.pipeline.spec import get_scheme

    return canonical_digest(dataclasses.asdict(get_scheme(scheme)))


_FINGERPRINT_DIRS = (
    "core",
    "pipeline",
    "kernels",
    "experiments",
    "streaming",
    "traffic",
)
_FINGERPRINT_CACHE: str | None = None


def code_fingerprint() -> str:
    """Repro package version + digest of result-determining sources.

    Hashes every ``.py`` under the `repro` subpackages whose code can
    change a sweep cell's value, in sorted relative-path order, so any
    source edit — a solver tweak, a calendar fix — invalidates the whole
    cache without manual version bumps.  Computed once per process.
    """
    global _FINGERPRINT_CACHE
    if _FINGERPRINT_CACHE is not None:
        return _FINGERPRINT_CACHE
    import repro

    root = os.path.dirname(os.path.abspath(repro.__file__))
    h = hashlib.sha256()
    h.update(getattr(repro, "__version__", "0").encode())
    for sub in _FINGERPRINT_DIRS:
        base = os.path.join(root, sub)
        if not os.path.isdir(base):
            continue
        for dirpath, _dirnames, filenames in sorted(os.walk(base)):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    _FINGERPRINT_CACHE = h.hexdigest()
    return _FINGERPRINT_CACHE


def cell_key(
    inst_digest: str, schm_digest: str, config_digest: str, fingerprint: str
) -> str:
    """The cache key of one sweep cell: hash of the four digests."""
    h = hashlib.sha256()
    for part in (inst_digest, schm_digest, config_digest, fingerprint):
        h.update(part.encode())
        h.update(b"|")
    return h.hexdigest()


# ----------------------------------------------------- cached stand-ins
@dataclasses.dataclass(frozen=True)
class CachedLP:
    """Stand-in for `lp.LPSolution` reconstructed from a cache payload
    (row export only reads ``objective``)."""

    objective: float
    method: str = "cached"


@dataclasses.dataclass(frozen=True)
class CachedCertificate:
    """Stand-in for `theory.CertificateReport` (row export reads
    ``approx_ratio``, ``bound`` and ``ok()``)."""

    approx_ratio: float
    bound: float
    certified: bool = True

    def ok(self, tol: float = 1e-6) -> bool:
        return self.certified


@dataclasses.dataclass(frozen=True)
class CachedScheduleResult:
    """Stand-in for `scheduler.ScheduleResult` reconstructed from a cache
    payload: exactly the absolutes the row export reads.  Circuits,
    orders and allocations are not cached — a hit means nobody re-reads
    them."""

    scheme: str
    total_weighted_cct: float
    ccts: np.ndarray

    @property
    def from_cache(self) -> bool:
        return True


# ------------------------------------------------------------ the cache
@dataclasses.dataclass
class CacheStats:
    """Cumulative counters over one `SweepCache` object's lifetime."""

    hits: int = 0
    misses: int = 0
    stored: int = 0

    def as_dict(self) -> dict[str, int]:
        return dataclasses.asdict(self)


class SweepCache:
    """Content-addressed sweep-cell store with a restart-surviving manifest.

    ``root`` defaults to ``$REPRO_CACHE`` or ``<results_dir>/cache``.
    ``fingerprint`` overrides `code_fingerprint` (tests use this to
    simulate source edits; multi-host launches may pin one fingerprint
    for a heterogeneous fleet).
    """

    def __init__(self, root: str | None = None, fingerprint: str | None = None):
        self.root = root or self.default_root()
        self.fingerprint = fingerprint or code_fingerprint()
        self.stats = CacheStats()
        self._manifest: dict[str, dict] = {}
        self._dirty = False
        self._load_manifest()

    @staticmethod
    def default_root() -> str:
        from repro.experiments.results import results_dir

        return os.environ.get(
            "REPRO_CACHE", os.path.join(results_dir(), "cache")
        )

    # -- manifest -------------------------------------------------------
    @property
    def manifest_path(self) -> str:
        return os.path.join(self.root, "manifest.json")

    def _load_manifest(self) -> None:
        if os.path.exists(self.manifest_path):
            with open(self.manifest_path) as f:
                doc = json.load(f)
            if doc.get("schema") != _MANIFEST_SCHEMA:
                raise ValueError(
                    f"unknown cache manifest schema {doc.get('schema')!r} "
                    f"at {self.manifest_path}"
                )
            self._manifest = doc.get("cells", {})

    def flush(self) -> str:
        """Atomically persist the manifest, merging entries another worker
        may have flushed since we loaded (shared-directory shard runs)."""
        if not self._dirty and os.path.exists(self.manifest_path):
            return self.manifest_path
        os.makedirs(self.root, exist_ok=True)
        merged = {}
        if os.path.exists(self.manifest_path):
            with open(self.manifest_path) as f:
                merged = json.load(f).get("cells", {})
        merged.update(self._manifest)
        self._manifest = merged
        doc = {"schema": _MANIFEST_SCHEMA, "cells": merged}
        tmp = self.manifest_path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, self.manifest_path)
        self._dirty = False
        return self.manifest_path

    def __len__(self) -> int:
        return len(self._manifest)

    # -- keys -----------------------------------------------------------
    def key(self, instance, scheme: str, config: Mapping[str, Any]) -> str:
        """Cell key for (instance, scheme) under `config` — the one-stop
        API; `sweep` precomputes the digests to hash each array once."""
        return cell_key(
            instance_digest(instance),
            scheme_digest(scheme),
            canonical_digest(dict(config)),
            self.fingerprint,
        )

    # -- objects --------------------------------------------------------
    def _object_path(self, key: str) -> str:
        return os.path.join(self.root, "objects", key[:2], f"{key}.json")

    def get(self, key: str) -> dict | None:
        """Payload for `key`, or None (counts a hit/miss).  A manifest
        entry whose object file vanished self-heals to a miss.  Hits
        stamp the entry's ``accessed`` time — the LRU clock `gc` evicts
        by (falling back to ``created`` for never-re-read cells)."""
        entry = self._manifest.get(key)
        if entry is not None:
            path = self._object_path(key)
            if os.path.exists(path):
                with open(path) as f:
                    self.stats.hits += 1
                    entry["accessed"] = _utcnow()
                    self._dirty = True
                    return json.load(f)
            del self._manifest[key]
            self._dirty = True
        self.stats.misses += 1
        return None

    def put(self, key: str, payload: Mapping[str, Any],
            meta: Mapping[str, Any] | None = None) -> None:
        path = self._object_path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, default=float)
        os.replace(tmp, path)
        self._manifest[key] = {
            "file": os.path.relpath(path, self.root),
            "created": _utcnow(),
            **({} if meta is None else dict(meta)),
        }
        self._dirty = True
        self.stats.stored += 1

    # -- eviction -------------------------------------------------------
    def gc(
        self,
        max_bytes: int | None = None,
        max_cells: int | None = None,
    ) -> dict[str, int]:
        """LRU eviction over the object store, with a self-healing rewrite.

        Reconciles the in-memory manifest with disk first (merging entries
        other workers flushed, dropping entries whose object file
        vanished), then evicts least-recently-used cells — ordered by the
        manifest's ``accessed`` timestamp (``created`` for cells never
        re-read; key as the deterministic tie-break) — until the store
        holds at most ``max_bytes`` of object payloads and ``max_cells``
        entries.  Evicted object files are deleted and the manifest is
        rewritten from scratch (NOT merge-on-flush: eviction must not be
        resurrected by a stale on-disk copy).

        Run it quiesced: an object another worker wrote but has not yet
        flushed a manifest entry for is invisible here and survives, but
        concurrent eviction of a cell mid-read in another process would
        self-heal there as a miss, not corrupt it.

        Returns counters: ``scanned`` / ``kept`` / ``evicted`` /
        ``healed`` (dangling manifest entries dropped), ``freed_bytes``
        and the surviving ``bytes``.
        """
        # Reconcile with whatever is on disk before deciding evictions.
        merged: dict[str, dict] = {}
        if os.path.exists(self.manifest_path):
            with open(self.manifest_path) as f:
                merged = json.load(f).get("cells", {})
        merged.update(self._manifest)

        sizes: dict[str, int] = {}
        healed = 0
        for key in list(merged):
            try:
                sizes[key] = os.path.getsize(self._object_path(key))
            except OSError:
                del merged[key]  # dangling entry: object file is gone
                healed += 1
        scanned = len(merged) + healed
        total = sum(sizes.values())

        # Oldest-first LRU queue; evict until both budgets hold.
        def stamp(item):
            key, entry = item
            return (entry.get("accessed", entry.get("created", "")), key)

        queue = sorted(merged.items(), key=stamp)
        evicted = 0
        freed = 0
        for key, _entry in queue:
            over_bytes = max_bytes is not None and total > max_bytes
            over_cells = max_cells is not None and len(merged) > max_cells
            if not (over_bytes or over_cells):
                break
            path = self._object_path(key)
            try:
                os.remove(path)
            except OSError:
                pass
            try:  # drop the 2-hex prefix dir when it just emptied
                os.rmdir(os.path.dirname(path))
            except OSError:
                pass
            del merged[key]
            total -= sizes[key]
            freed += sizes[key]
            evicted += 1

        # Full rewrite (no merge): the surviving cells ARE the manifest.
        self._manifest = merged
        os.makedirs(self.root, exist_ok=True)
        doc = {"schema": _MANIFEST_SCHEMA, "cells": merged}
        tmp = self.manifest_path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, self.manifest_path)
        self._dirty = False
        return dict(
            scanned=scanned, kept=len(merged), evicted=evicted,
            healed=healed, freed_bytes=freed, bytes=total,
        )
