"""Persistent, LRU-bounded plan cache for the serving path.

Planning is the expensive, bitstring-independent part of an amplitude
query: a path search over the circuit structure, optional
slice-and-reconfigure, and the hoist split. This cache persists exactly
that — ``{path, slicing, hoist split, executor config}`` as plain JSON
(never pickle: a corrupted or adversarial entry must degrade to a
replan, not arbitrary code) — keyed by a **structure digest** of the
network's flat leaves (legs + bond dims), which every bitstring of a
circuit shares. A repeat circuit therefore performs zero pathfinding
(no ``plan.find_path`` span), and because the rebuilt
:class:`~tnc_tpu.ops.program.ContractionProgram` has the same
signature, a warm process-level jit cache also skips compilation.

Discipline (shared with the other on-disk artifact stores):

- digests come from the one canonical helper
  (:func:`tnc_tpu.utils.digest.stable_digest` — also behind
  ``resilience.checkpoint.signature_hash`` and
  ``benchmark.cache.cache_key``), stable across hash seeds and dict
  ordering;
- every entry records ``program_sig`` = the rebuilt program's
  ``signature_digest()``, validated after rebuild — a plan whose
  compiler output drifted (planner/compiler version change) is
  invalidated rather than trusted;
- writes are atomic (temp file + ``os.replace``);
- the cache is LRU-bounded by entry count (mtime = last use; loads
  touch it), with corrupted entries deleted and counted, never raised.

**Shared store.** The directory is safe to share between N serving
replicas (processes, containers mounting one volume): entries are
content-addressed by the structure digest, every writer publishes
through its own uniquely named temp file + atomic ``os.replace`` (two
replicas racing on one key leave whichever complete entry landed last —
never an interleaved file), and readers are lock-free (``os.replace``
guarantees a reader sees either the old or the new complete entry).
N replicas of one fleet therefore plan each structure **once**: the
first replica to finish planning publishes, every other replica's
lookup is a planner-span-free hit. :func:`PlanCache.entry_fingerprint`
gives replicas a cheap change probe — the background replanner's
improved plans (published through the same store) become visible to
every replica, and a
:class:`~tnc_tpu.serve.replan.SharedCacheWatcher` adopts them into a
running service.
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
import uuid
from dataclasses import dataclass
from pathlib import Path

from tnc_tpu import obs
from tnc_tpu.contractionpath.contraction_path import ContractionPath
from tnc_tpu.contractionpath.slicing import Slicing
from tnc_tpu.tensornetwork.tensor import CompositeTensor
from tnc_tpu.utils.digest import stable_digest

logger = logging.getLogger(__name__)

FORMAT_VERSION = 1


def network_structure_digest(
    tn: CompositeTensor, target_size: float | None = None
) -> str:
    """Stable digest of the network's contraction-relevant structure:
    every flat leaf's (legs, dims), in slot order. Bitstring-independent
    by construction — bra *values* never enter the digest — so all
    2^n amplitude networks of one circuit share a key.

    ``target_size`` (the caller's peak-memory budget) is part of the
    key: a plan is only reusable under the budget it was made for — an
    unsliced plan cached without a budget must never answer a
    budget-constrained lookup (it would OOM the device the budget
    modeled). Planner *identity* is deliberately not keyed: a cache
    directory is assumed to serve one planner configuration, like the
    benchmark plan cache's scheme prefix."""
    from tnc_tpu.ops.program import flat_leaf_tensors

    leaves = flat_leaf_tensors(tn)
    return stable_digest(
        "tnc-plan-v%d" % FORMAT_VERSION,
        tuple((tuple(t.legs), tuple(t.bond_dims)) for t in leaves),
        float(target_size) if target_size is not None else None,
    )


class PlanCache:
    """On-disk plan store: ``<dir>/<structure-digest>.json`` entries.

    >>> import tempfile
    >>> cache = PlanCache(tempfile.mkdtemp(), max_entries=2)
    >>> plan = {"version": 1, "pairs": [[0, 1]], "program_sig": "x"}
    >>> cache.store("k1", plan)
    >>> cache.load("k1")["pairs"]
    [[0, 1]]
    >>> cache.load("missing") is None
    True
    """

    def __init__(
        self, directory: str | Path, max_entries: int = 256,
        read_only: bool = False,
    ):
        self.directory = Path(directory)
        # a fleet's shared copy: loads never touch, delete or write an
        # entry, and store/invalidate refuse
        self.read_only = bool(read_only)
        if not self.read_only:
            self.directory.mkdir(parents=True, exist_ok=True)
        self.max_entries = max(1, int(max_entries))
        # explicit per-key hit counts (process-local; the on-disk LRU
        # touch only *implies* heat via mtime): what the background
        # replanner reads to pick which entries deserve hyper-time
        self._hits: dict[str, int] = {}
        self._hits_lock = threading.Lock()
        # process-local event counters mirroring the obs families —
        # stats() and the service's /metrics surface read these, so
        # cache efficacy is observable with obs tracing off
        self._counts = {
            k: 0
            for k in (
                "hit", "miss", "store", "evicted", "corrupt",
                "invalidated", "store_failed",
            )
        }

    def _count(self, key: str) -> None:
        with self._hits_lock:
            self._counts[key] = self._counts.get(key, 0) + 1
        obs.counter_add(f"serve.plan_cache.{key}")

    def stats(self) -> dict:
        """Process-local cache efficacy: event counts (hit / miss /
        store / evicted / corrupt / invalidated / store_failed) plus
        the current on-disk entry count."""
        with self._hits_lock:
            counts = dict(self._counts)
        return {"counts": counts, "entries": len(self)}

    def _path(self, key: str) -> Path:
        return self.directory / f"{key}.json"

    def key_for_network(
        self, tn: CompositeTensor, target_size: float | None = None
    ) -> str:
        return network_structure_digest(tn, target_size)

    # -- entries -----------------------------------------------------------

    def record_for(
        self,
        path: ContractionPath,
        program,
        slicing: Slicing | None = None,
        sliced_program=None,
        executor: dict | None = None,
        flops: float | None = None,
        peak: float | None = None,
        finder: str | None = None,
        target_size: float | None = None,
        predicted_seconds: float | None = None,
    ) -> dict:
        """Build the JSON plan record for a freshly planned structure:
        path pairs, optional slicing + hoist split (computed from
        ``sliced_program`` when given), executor config, and the
        program-signature digest the entry is validated against.
        ``finder``/``predicted_seconds`` record plan *provenance* — the
        background replanner only spends hyper-optimizer time on entries
        a fast greedy planner produced, and swaps strictly on a
        predicted-cost win."""
        plan: dict = {
            "version": FORMAT_VERSION,
            "pairs": path.to_obj(),
            "slicing": slicing.to_obj() if slicing is not None else None,
            "hoist": None,
            "executor": dict(executor) if executor else None,
            "program_sig": program.signature_digest(),
            "created_at": time.time(),
            "finder": finder,
            "target_size": (
                float(target_size) if target_size is not None else None
            ),
        }
        if predicted_seconds is not None:
            plan["predicted_seconds"] = float(predicted_seconds)
        if sliced_program is not None:
            from tnc_tpu.ops.hoist import hoist_split_counts

            plan["hoist"] = hoist_split_counts(sliced_program)
            plan["sliced_sig"] = sliced_program.signature_digest()
        if flops is not None:
            plan["flops"] = float(flops)
        if peak is not None:
            plan["peak"] = float(peak)
        return plan

    def validate(self, plan: dict, program) -> bool:
        """True when ``program`` (rebuilt from the cached path) matches
        the signature the plan was stored with."""
        return plan.get("program_sig") == program.signature_digest()

    @staticmethod
    def plan_path(plan: dict) -> ContractionPath:
        return ContractionPath.from_obj(plan["pairs"])

    @staticmethod
    def plan_slicing(plan: dict) -> Slicing | None:
        obj = plan.get("slicing")
        return Slicing.from_obj(obj) if obj else None

    # -- storage -----------------------------------------------------------

    def load(self, key: str) -> dict | None:
        """The cached plan, or None (absent / corrupt / wrong version —
        corruption is deleted and counted, never raised: a bad entry
        degrades to a replan)."""
        target = self._path(key)
        try:
            with open(target, "r", encoding="utf-8") as fh:
                plan = json.load(fh)
            if (
                not isinstance(plan, dict)
                or plan.get("version") != FORMAT_VERSION
                or not isinstance(plan.get("pairs"), list)
            ):
                raise ValueError(f"unusable plan entry: {plan!r:.80}")
        except FileNotFoundError:
            self._count("miss")
            return None
        except Exception as exc:  # noqa: BLE001 — any corruption → replan
            logger.warning(
                "plan cache entry %s unreadable (%s: %s); dropping it",
                target, type(exc).__name__, exc,
            )
            self._count("corrupt")
            self._count("miss")
            if not self.read_only:
                try:
                    target.unlink(missing_ok=True)
                except OSError:
                    pass
            return None
        self._count("hit")
        with self._hits_lock:
            self._hits[key] = self._hits.get(key, 0) + 1
        if not self.read_only:
            try:  # LRU touch: mtime records last use
                os.utime(target)
            except OSError:
                pass
        return plan

    def hits(self, key: str) -> int:
        """Process-local hit count for ``key`` (successful loads)."""
        with self._hits_lock:
            return self._hits.get(key, 0)

    def hot_keys(self, limit: int = 8) -> list[str]:
        """Keys by descending hit count — the explicit heat ranking the
        LRU mtimes only imply. The single-structure
        :class:`~tnc_tpu.serve.replan.BackgroundReplanner` gates on
        per-key :meth:`hits` (``min_hits``); this ranking is the hook
        for multi-structure deployments and dashboards.

        >>> import tempfile
        >>> c = PlanCache(tempfile.mkdtemp())
        >>> c.store("a", {"version": 1, "pairs": []})
        >>> _ = c.load("a"); _ = c.load("a"); _ = c.load("missing")
        >>> c.hot_keys()
        ['a']
        """
        with self._hits_lock:
            ranked = sorted(self._hits.items(), key=lambda kv: (-kv[1], kv[0]))
        return [k for k, n in ranked[: max(limit, 0)] if n > 0]

    def entry_fingerprint(self, key: str) -> str | None:
        """Cheap content probe for ``key``'s on-disk entry: a digest of
        the entry's raw bytes, or ``None`` when absent/unreadable.
        Replicas poll this to notice another replica's publish (a
        background replanner's swap, a fresh plan) without parsing the
        JSON — the read is lock-free (``os.replace`` publishes whole
        files, so the bytes are always one complete entry)."""
        try:
            with open(self._path(key), "rb") as fh:
                return stable_digest("plan-bytes", fh.read())
        except OSError:
            return None

    def store(self, key: str, plan: dict) -> None:
        """Atomic write + LRU eviction down to ``max_entries``.

        Best-effort, mirroring :meth:`load`: the cache is an
        optimization, so a write failure (disk full, permissions, dir
        removed) is logged and counted — never raised. The caller holds
        the freshly planned program in memory either way.

        Safe under concurrent writers (N replicas sharing the
        directory): the temp file is uniquely named per writer (pid +
        random suffix), so two replicas racing on one key can never
        interleave bytes — the last complete ``os.replace`` wins."""
        self._writable("store")
        target = self._path(key)
        tmp = target.with_name(
            f"{key}.{os.getpid()}.{uuid.uuid4().hex[:8]}.json.tmp"
        )
        try:
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(plan, fh)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, target)
        except OSError as exc:
            logger.warning(
                "plan cache store of %s failed (%s: %s); serving from "
                "the in-memory plan", target, type(exc).__name__, exc,
            )
            self._count("store_failed")
            try:  # don't strand the partial temp file
                tmp.unlink(missing_ok=True)
            except OSError:
                pass
            return
        self._count("store")
        self._evict()

    def invalidate(self, key: str) -> None:
        self._writable("invalidate")
        try:
            self._path(key).unlink(missing_ok=True)
        except OSError:
            pass
        with self._hits_lock:
            self._hits.pop(key, None)
        self._count("invalidated")

    def _writable(self, what: str) -> None:
        if self.read_only:
            raise PermissionError(
                f"plan store {self.directory} is read-only: no {what}"
            )

    def _entries(self) -> list[Path]:
        return [
            p for p in self.directory.glob("*.json") if p.is_file()
        ]

    def _evict(self) -> None:
        # reap orphaned temp files a crashed writer left behind (never
        # fresh ones — another replica may be mid-publish right now)
        now = time.time()
        for orphan in self.directory.glob("*.json.tmp"):
            try:
                if now - orphan.stat().st_mtime > 3600.0:
                    orphan.unlink(missing_ok=True)
            except OSError:
                continue
        entries = self._entries()
        if len(entries) <= self.max_entries:
            return
        def mtime(p: Path) -> float:
            try:
                return p.stat().st_mtime
            except OSError:
                return 0.0
        entries.sort(key=mtime)
        for victim in entries[: len(entries) - self.max_entries]:
            try:
                victim.unlink(missing_ok=True)
                self._count("evicted")
                logger.info("plan cache evicted %s (LRU)", victim.name)
            except OSError:
                continue
            # heat follows the entry out: hits()/hot_keys() must not
            # rank keys the cache no longer holds, and the dict must
            # not grow one entry per structure ever served
            with self._hits_lock:
                self._hits.pop(victim.stem, None)

    def __len__(self) -> int:
        return len(self._entries())


class PlanStoreMiss(LookupError):
    """The store holds no plan for this network's structure and budget,
    or one made for another structure: a fleet never plans at run
    time, so the caller fails instead."""


@dataclass
class StoredPlan:
    """A plan read from a shared store and rebuilt for one network."""

    key: str
    path: ContractionPath
    slicing: Slicing
    sliced: object  # SlicedProgram
    sig_drift: bool  # the rebuilt program's signature is not the stored one
    slice_peak_log2: float  # largest intermediate of one slice, log2 elements


def load_stored_plan(
    directory: str | Path, tn: CompositeTensor, target_size: float
) -> StoredPlan:
    """The sliced plan a fleet made once and shares, for ``tn``: looked
    up by structure digest in a read-only :class:`PlanCache` and rebuilt
    from its stored pairs and slicing, under the phase ``plan.store``
    (``hit``, ``sig_drift``, ``bytes``, ``legs``, ``num_slices``).

    What is pinned is the PLAN, not the program built from it: where the
    rebuilt program's signature differs from the stored ``program_sig``
    (a later change to how steps are built) the stored pairs and slicing
    still run, and the drift is recorded, never a reason to replan. A
    missing entry, or one stored for another structure, raises
    :class:`PlanStoreMiss`. Sets the gauge ``plan.slice_peak_log2``: the
    largest intermediate of one slice, log2 of its elements."""
    from tnc_tpu.ops.sliced import build_sliced_program

    with obs.phase("plan.store", directory=str(directory)) as span:
        cache = PlanCache(directory, read_only=True)
        key = cache.key_for_network(tn, target_size)
        plan = cache.load(key)
        if plan is None:
            span.set(hit=0)
            raise PlanStoreMiss(
                f"plan store {directory} has no entry {key}.json for this "
                f"network at target {target_size:.6g}"
            )
        if plan.get("structure") != key:
            span.set(hit=0)
            raise PlanStoreMiss(
                f"plan store entry {key}.json was stored for structure "
                f"{plan.get('structure')!r}, not this network's {key!r}"
            )
        path = cache.plan_path(plan)
        slicing = cache.plan_slicing(plan) or Slicing((), ())
        sliced = build_sliced_program(tn, path, slicing)
        drift = not cache.validate(plan, sliced.program) or plan.get(
            "sliced_sig"
        ) not in (None, sliced.signature_digest())
        if drift:
            logger.warning(
                "plan store entry %s rebuilds a program of another "
                "signature; running its stored pairs and slicing", key,
            )
        span.set(hit=1, sig_drift=int(drift), legs=len(slicing.legs),
                 num_slices=slicing.num_slices)
        span.add(bytes=cache._path(key).stat().st_size)
    peak = math.log2(max(
        (math.prod(st.out_store) for st in sliced.program.steps), default=1
    ))
    obs.gauge_set("plan.slice_peak_log2", peak)
    return StoredPlan(key, path, slicing, sliced, drift, peak)
