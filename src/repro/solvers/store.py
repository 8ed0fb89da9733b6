"""Content-addressed factor store: cached one-time factorizations.

The paper's cost split — an expensive b-INDEPENDENT ``prepare`` (Gram
Cholesky factors, preconditioners) followed by cheap per-RHS iterations —
is exactly what serve traffic amortizes.  This module makes that explicit:
``FactorStore`` is the ONE way any driver, benchmark, or example obtains
factors.

    store = FactorStore(capacity=8, directory="/ckpt/factors")
    factors = store.factors(solvers.get("apc"), sys, **params)
    store.stats            # hits / disk_hits / misses / evictions / ...

Systems are fingerprinted by a sha256 over the A-blocks' CONTENT, the
partition (m, p, n), the dtype, the solver name, and the resolved
parameters — so a hit is bit-equivalent to re-running ``prepare``, never a
lookup on an object identity that might alias a different system.

Two tiers:

  * memory — an LRU of device factors (capacity entries, per-store);
  * disk (optional) — every miss is persisted using the checkpoint
    layout from ``repro.checkpoint.ckpt`` (tmp dir -> leaf_*.npy +
    manifest.json + COMMIT marker -> atomic ``os.replace``), so
    factorizations survive restarts and a cold process warm-starts from
    disk.  The manifest is validated on load (solver / partition / dtype /
    leaf shapes) and drift fails LOUDLY — a silently-cast factor makes a
    resumed solve diverge from the uninterrupted one.

Factors obtained here round-trip both backends: the mesh path accepts
host factors (``Solver.mesh_factors`` strips host-only fields before
placement) and the redundant layer replicates them itself.

Kernel path: ``factors(..., use_kernel=True)`` augments the cached entry
with the pinv precomputation ONCE (``Solver.kernel_factors`` is
idempotent — it detects already-augmented factors) and writes the
augmented factors back into the cache slot, so repeated kernel solves on
a hit never re-run the augmentation.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
import json
import logging
import os
import shutil
from collections import OrderedDict
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import COMMIT
from repro.core.partition import BlockSystem
from repro.runtime.spans import span

log = logging.getLogger("repro.solvers.store")


def fingerprint(solver_name: str, sys: BlockSystem,
                params: Dict[str, Any], precision: str = "default") -> str:
    """Content hash identifying (A-blocks, partition, solver, params).

    Everything ``prepare`` can depend on is in the digest; b is NOT — the
    factorization is b-independent by the lifecycle contract, so one entry
    serves every right-hand side of the same system.

    Sparse systems additionally hash their structure tag and column
    support: ``prepare`` consumes the compressed ``sys.A_op`` operand
    there, so a sparse system and its densified twin hold the SAME values
    but different factor pytrees — they must never share a slot.  Dense
    digests are byte-identical to what they always were.

    A non-default ``precision`` (mixed bf16 tile streams) enters the
    digest too — a cast entry must never serve a full-precision request —
    while ``precision="default"`` adds NOTHING, keeping every existing
    fingerprint byte-stable.
    """
    with span("repro.store.fingerprint"):
        A = np.asarray(jax.device_get(sys.A_blocks))
        h = hashlib.sha256()
        h.update(f"solver={solver_name}".encode())
        h.update(f"partition={tuple(A.shape)}".encode())
        h.update(f"dtype={A.dtype}".encode())
        for k in sorted(params):
            try:
                # normalize numeric types: 1.3, np.float64(1.3) and a jax
                # scalar must hash identically or cross-call-path lookups
                # (auto-tuned vs hand-passed params) silently always miss
                v = repr(float(params[k]))
            except (TypeError, ValueError):
                v = repr(params[k])
            h.update(f"param:{k}={v}".encode())
        h.update(np.ascontiguousarray(A).tobytes())
        if sys.is_sparse:
            cols = np.asarray(jax.device_get(sys.cols))
            h.update(b"structure=sparse")
            h.update(f"support={tuple(cols.shape)}".encode())
            h.update(np.ascontiguousarray(cols).tobytes())
        if precision != "default":
            h.update(f"precision={precision}".encode())
        return h.hexdigest()


@dataclasses.dataclass
class StoreStats:
    """Running counters; ``hits``/``disk_hits`` vs ``misses`` is the
    serve-traffic amortization the benchmarks report."""
    hits: int = 0           # in-memory LRU hits
    disk_hits: int = 0      # restored from the disk tier
    misses: int = 0         # full ``prepare`` re-runs
    evictions: int = 0      # LRU drops (memory tier only)
    disk_writes: int = 0    # entries persisted
    resume_misses: int = 0  # misses during a warm-start resume (visible
                            # cost that used to be silent — see api.solve)
    block_hits: int = 0     # per-block reuses (``blockwise_factors``)
    block_misses: int = 0   # per-block refactorizations

    @property
    def total_hits(self) -> int:
        return self.hits + self.disk_hits


class BlockReuse(NamedTuple):
    """What a ``blockwise_factors`` assembly reused vs refactorized —
    the number the elastic runtime reports after a repartition."""
    reused: int
    prepared: int


def block_fingerprint(solver_name: str, A_block: np.ndarray,
                      params: Dict[str, Any],
                      precision: str = "default") -> str:
    """Content hash of ONE row block's factorization inputs.

    Mirrors :func:`fingerprint` at block granularity: solver, the block's
    partition slice shape (p, n), dtype, resolved params, the block's
    bytes, and a non-default precision.  Two partitions that happen to
    cut identical (content, shape) blocks therefore share entries — that
    is the point: a worker rejoining a previously-seen partition reuses
    every unchanged block's factors instead of re-preparing them.
    """
    A_block = np.asarray(A_block)
    h = hashlib.sha256()
    h.update(f"block-solver={solver_name}".encode())
    h.update(f"slice={tuple(A_block.shape)}".encode())
    h.update(f"dtype={A_block.dtype}".encode())
    for k in sorted(params):
        try:
            v = repr(float(params[k]))
        except (TypeError, ValueError):
            v = repr(params[k])
        h.update(f"param:{k}={v}".encode())
    h.update(np.ascontiguousarray(A_block).tobytes())
    if precision != "default":
        h.update(f"precision={precision}".encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Pytree (de)serialization for the disk tier.  Factor pytrees are
# NamedTuples / tuples / dicts of arrays with optional None fields; the
# structure is recorded in the manifest so a COLD process can restore an
# entry without re-running ``prepare`` to obtain a template.
# ---------------------------------------------------------------------------


def _encode(node: Any, leaves: list) -> Any:
    if node is None:
        return {"kind": "none"}
    if hasattr(node, "_fields"):                       # NamedTuple
        cls = type(node)
        return {"kind": "namedtuple",
                "cls": f"{cls.__module__}:{cls.__qualname__}",
                "fields": [[f, _encode(getattr(node, f), leaves)]
                           for f in node._fields]}
    if isinstance(node, dict):
        return {"kind": "dict",
                "items": [[k, _encode(v, leaves)]
                          for k, v in sorted(node.items())]}
    if isinstance(node, (list, tuple)):
        return {"kind": "list" if isinstance(node, list) else "tuple",
                "items": [_encode(v, leaves) for v in node]}
    leaves.append(np.asarray(jax.device_get(node)))
    return {"kind": "leaf", "index": len(leaves) - 1}


def _decode(spec: Any, leaves: list) -> Any:
    kind = spec["kind"]
    if kind == "none":
        return None
    if kind == "leaf":
        return jnp.asarray(leaves[spec["index"]])
    if kind == "namedtuple":
        mod, qual = spec["cls"].split(":")
        cls: Any = importlib.import_module(mod)
        for part in qual.split("."):
            cls = getattr(cls, part)
        return cls(**{f: _decode(s, leaves) for f, s in spec["fields"]})
    if kind == "dict":
        return {k: _decode(s, leaves) for k, s in spec["items"]}
    if kind in ("list", "tuple"):
        items = [_decode(s, leaves) for s in spec["items"]]
        return items if kind == "list" else tuple(items)
    raise ValueError(f"unknown factor-structure node kind {kind!r}")


class FactorStore:
    """Content-addressed cache of b-independent solver factorizations.

    ``factors(solver, sys, **params)`` is the one entry point; drivers
    pass the store down via ``Solver.solve(..., store=...)``.
    """

    def __init__(self, capacity: int = 8,
                 directory: Optional[str] = None,
                 block_capacity: int = 256) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if block_capacity < 1:
            raise ValueError(
                f"block_capacity must be >= 1, got {block_capacity}")
        self.capacity = capacity
        self.block_capacity = block_capacity
        self.directory = directory
        self.stats = StoreStats()
        self._mem: "OrderedDict[str, Any]" = OrderedDict()
        self._block_mem: "OrderedDict[str, Any]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._mem)

    def __contains__(self, key: str) -> bool:
        return key in self._mem

    def clear(self) -> None:
        """Drop the memory tier (the disk tier, if any, is untouched)."""
        self._mem.clear()

    # ----- keys ------------------------------------------------------------
    @staticmethod
    def _as_solver(solver):
        if isinstance(solver, str):
            from .registry import get
            return get(solver)
        return solver

    def key(self, solver, sys: BlockSystem, *, precision: str = "default",
            **params) -> str:
        """The content-addressed key a ``factors`` call would use."""
        solver = self._as_solver(solver)
        prm = solver.resolve_params(sys, **params)
        return fingerprint(solver.name, sys, prm, precision)

    # ----- the one way to obtain factors ------------------------------------
    def factors(self, solver, sys: BlockSystem, *, use_kernel: bool = False,
                resume: bool = False, key: Optional[str] = None,
                precision: str = "default", **params):
        """Cached ``solver.prepare(sys.A_op, params)``.

        Lookup order: memory LRU -> disk tier -> full ``prepare`` (counted
        as a miss; persisted when a ``directory`` is configured).  Pass a
        precomputed ``key`` (from ``self.key``) to skip re-hashing A on
        hot serving paths.  ``resume=True`` marks the call as part of a
        warm-start resume so a miss there is counted separately — resume
        cost should be visible, not silent.

        ``precision="mixed"`` entries live under their OWN fingerprint and
        cache the already-cast factors (prepare and the pinv augmentation
        still run in full precision on a miss; the cast happens last).
        """
        solver = self._as_solver(solver)
        prm = solver.resolve_params(sys, **params)
        if key is None:
            key = fingerprint(solver.name, sys, prm, precision)
        factors = self.lookup(solver, sys, key=key, use_kernel=use_kernel,
                              precision=precision, **prm)
        if factors is None:
            with span("repro.store.prepare"):
                factors = self.insert(solver, sys,
                                      solver.prepare(sys.A_op, prm),
                                      resume=resume, key=key,
                                      use_kernel=use_kernel,
                                      precision=precision, **prm)
                # wait for the factors here, so that their device time is
                # charged to the prepare and not to whatever next waits on
                # the device; a hit never blocks, a miss (set-up, or the
                # first batch after an eviction) holds its caller
                jax.block_until_ready(factors)
        return factors

    def _augment(self, solver, key: str, factors):
        """Kernel-path augmentation, ONCE per cache slot: later hits get
        the augmented factors back and ``kernel_factors`` detects them
        (idempotent), so the pinv precomputation never re-runs."""
        augmented = solver.kernel_factors(factors)
        if augmented is not factors and key in self._mem:
            self._mem[key] = augmented
        return augmented

    def lookup(self, solver, sys: BlockSystem, *,
               key: Optional[str] = None, use_kernel: bool = False,
               precision: str = "default", **params):
        """Memory/disk lookup that does NOT prepare on a miss (returns
        None instead).  Backends whose factorization should not run on
        the host (the mesh backend prepares on-mesh under shard_map) use
        this + ``insert`` so a miss is repaid THEIR way while hits and
        persistence still flow through the store.  ``use_kernel=True``
        augments a hit with the pinv factors and writes the augmentation
        back into the slot — the same once-per-entry contract as
        ``factors`` — so the mesh-side split gets it too."""
        solver = self._as_solver(solver)
        if key is None:
            prm = solver.resolve_params(sys, **params)
            key = fingerprint(solver.name, sys, prm, precision)
        factors = self._mem.get(key)
        if factors is not None:
            self._mem.move_to_end(key)
            self.stats.hits += 1
            return self._augment(solver, key, factors) if use_kernel \
                else factors
        factors = self._disk_load(key, solver, sys)
        if factors is not None:
            self.stats.disk_hits += 1
            self._insert(key, factors)
            return self._augment(solver, key, factors) if use_kernel \
                else factors
        return None

    def insert(self, solver, sys: BlockSystem, factors, *,
               resume: bool = False, key: Optional[str] = None,
               use_kernel: bool = False, precision: str = "default",
               **params):
        """Record a caller-prepared factorization: counts the miss the
        caller just repaid, persists to the disk tier, and caches it.
        ``use_kernel=True`` ensures the cached entry carries the pinv
        augmentation (a no-op when the caller's prepare — e.g. the
        on-mesh kernel ``mesh_prepare`` — already computed it); a
        non-default ``precision`` casts the tile streams LAST, so the
        cached entry is the cast one (``cast_factors`` is idempotent)."""
        solver = self._as_solver(solver)
        prm = solver.resolve_params(sys, **params)
        if key is None:
            key = fingerprint(solver.name, sys, prm, precision)
        if use_kernel:
            factors = solver.kernel_factors(factors)
        if precision != "default":
            factors = solver.cast_factors(factors, precision)
        self.stats.misses += 1
        if resume:
            self.stats.resume_misses += 1
            log.warning(
                "factor-store MISS during warm-start resume: re-running "
                "the full b-independent prepare for solver %r (configure "
                "a disk tier to amortize resumes across processes)",
                solver.name)
        self._disk_store(key, solver, sys, prm, factors)
        self._insert(key, factors)
        return factors

    def _insert(self, key: str, factors: Any) -> None:
        self._mem[key] = factors
        self._mem.move_to_end(key)
        while len(self._mem) > self.capacity:
            self._mem.popitem(last=False)
            self.stats.evictions += 1

    # ----- block tier (per-block reuse across repartitions) -----------------
    # A repartition (elastic join/rejoin) changes the system fingerprint,
    # so the whole-system tiers above always miss — but any block whose
    # (content, slice shape, dtype, solver, params) is unchanged has the
    # SAME factorization.  ``blockwise_factors`` assembles the full factor
    # pytree from cached per-block slices plus ONE stacked ``prepare``
    # over the missing blocks, and reports reuse vs refactorization.
    # Valid only for solvers whose ``prepare`` is per-block independent
    # and whose factor leaves all carry a leading worker axis
    # (``supports_block_store`` — the projection family).

    def blockwise_factors(self, solver, sys: BlockSystem, *,
                          use_kernel: bool = False,
                          precision: str = "default", **params):
        """``(factors, BlockReuse)`` for ``sys`` with per-block caching.

        Counts one ``block_hit`` per reused block and one ``block_miss``
        per refactorized one; the missing blocks are prepared in ONE
        stacked ``solver.prepare`` call (they are just fewer worker
        blocks).  The assembled full-system entry is also written to the
        whole-system tiers, so later same-partition solves hit there.
        """
        solver = self._as_solver(solver)
        if not getattr(solver, "supports_block_store", False):
            raise ValueError(
                f"solver {solver.name!r} does not declare a per-block-"
                f"independent prepare (supports_block_store=False); "
                f"blockwise reuse would assemble wrong factors")
        if sys.is_sparse:
            raise ValueError(
                "blockwise factor reuse is dense-only: sparse operands "
                "carry a shared column support that a per-block cache "
                "cannot slice; densify() or use the whole-system tiers")
        prm = solver.resolve_params(sys, **params)
        A = np.asarray(jax.device_get(sys.A_blocks))
        keys = [block_fingerprint(solver.name, A[i], prm, precision)
                for i in range(sys.m)]
        blocks: Dict[int, Any] = {}
        for i, bk in enumerate(keys):
            blk = self._block_lookup(bk)
            if blk is not None:
                blocks[i] = blk
        missing = [i for i in range(sys.m) if i not in blocks]
        self.stats.block_hits += sys.m - len(missing)
        self.stats.block_misses += len(missing)
        if missing:
            sub = solver.prepare(jnp.asarray(A[np.array(missing)]), prm)
            for j, i in enumerate(missing):
                blk = jax.tree.map(lambda leaf: leaf[j], sub)
                self._block_insert(keys[i], solver, prm, blk)
                blocks[i] = blk
        factors = jax.tree.map(
            lambda *leaves: jnp.stack(leaves, axis=0),
            *[blocks[i] for i in range(sys.m)])
        reuse = BlockReuse(reused=sys.m - len(missing),
                           prepared=len(missing))
        # seed the whole-system tiers so same-partition callers hit there
        # (NOT through ``insert`` — an assembly is neither a system-level
        # hit nor a miss; only the per-block counters moved).  Transforms
        # apply to the RETURNED factors on every path, seeded or not.
        sys_key = fingerprint(solver.name, sys, prm, precision)
        if use_kernel:
            factors = (self._augment(solver, sys_key, factors)
                       if sys_key in self._mem
                       else solver.kernel_factors(factors))
        if precision != "default":
            factors = solver.cast_factors(factors, precision)
        if sys_key not in self._mem:
            self._disk_store(sys_key, solver, sys, prm, factors)
            self._insert(sys_key, factors)
        return factors, reuse

    def _block_lookup(self, key: str):
        blk = self._block_mem.get(key)
        if blk is not None:
            self._block_mem.move_to_end(key)
            return blk
        blk = self._block_disk_load(key)
        if blk is not None:
            self._block_mem[key] = blk
            self._trim_blocks()
        return blk

    def _block_insert(self, key: str, solver, prm: Dict[str, Any],
                      blk: Any) -> None:
        self._block_mem[key] = blk
        self._block_mem.move_to_end(key)
        self._trim_blocks()
        self._block_disk_store(key, solver, prm, blk)

    def _trim_blocks(self) -> None:
        while len(self._block_mem) > self.block_capacity:
            self._block_mem.popitem(last=False)
            self.stats.evictions += 1

    def _block_dir(self, key: str) -> str:
        return os.path.join(self.directory, "blocks", key)

    def _block_disk_store(self, key: str, solver, prm: Dict[str, Any],
                          blk: Any) -> None:
        if self.directory is None:
            return
        root = os.path.join(self.directory, "blocks")
        os.makedirs(root, exist_ok=True)
        tmp = os.path.join(root, f"tmp.{key}")
        final = self._block_dir(key)
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        leaves: list = []
        structure = _encode(blk, leaves)
        manifest = {
            "key": key,
            "solver": solver.name,
            "params": {k: float(v) for k, v in prm.items()},
            "structure": structure,
            "leaves": [{"shape": list(l.shape), "dtype": str(l.dtype)}
                       for l in leaves],
        }
        for i, leaf in enumerate(leaves):
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), leaf)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(tmp, COMMIT), "w") as f:
            f.write("ok")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        self.stats.disk_writes += 1

    def _block_disk_load(self, key: str) -> Any:
        if self.directory is None:
            return None
        path = self._block_dir(key)
        if not os.path.exists(os.path.join(path, COMMIT)):
            return None
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        leaves = []
        for i, ref in enumerate(manifest["leaves"]):
            arr = np.load(os.path.join(path, f"leaf_{i:05d}.npy"))
            if list(arr.shape) != list(ref["shape"]) \
                    or str(arr.dtype) != ref["dtype"]:
                raise ValueError(
                    f"factor-store block entry corrupt at {path}: leaf "
                    f"{i} is {arr.shape}/{arr.dtype}, manifest says "
                    f"{ref['shape']}/{ref['dtype']}")
            leaves.append(arr)
        return _decode(manifest["structure"], leaves)

    # ----- disk tier --------------------------------------------------------
    def _entry_dir(self, key: str) -> str:
        return os.path.join(self.directory, key)

    def _disk_store(self, key: str, solver, sys: BlockSystem,
                    prm: Dict[str, Any], factors: Any) -> None:
        if self.directory is None:
            return
        os.makedirs(self.directory, exist_ok=True)
        tmp = os.path.join(self.directory, f"tmp.{key}")
        final = self._entry_dir(key)
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        leaves: list = []
        structure = _encode(factors, leaves)
        manifest = {
            "key": key,
            "solver": solver.name,
            "partition": [sys.m, sys.p, sys.n],
            "system_structure": sys.structure,
            "dtype": str(np.asarray(sys.A_blocks).dtype),
            "params": {k: float(v) for k, v in prm.items()},
            "structure": structure,
            "leaves": [{"shape": list(l.shape), "dtype": str(l.dtype)}
                       for l in leaves],
        }
        for i, leaf in enumerate(leaves):
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), leaf)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(tmp, COMMIT), "w") as f:
            f.write("ok")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        self.stats.disk_writes += 1

    def _disk_load(self, key: str, solver, sys: BlockSystem) -> Any:
        """Restore a committed entry, failing LOUDLY on manifest drift."""
        if self.directory is None:
            return None
        path = self._entry_dir(key)
        if not os.path.exists(os.path.join(path, COMMIT)):
            return None
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        want_part = [sys.m, sys.p, sys.n]
        want_dtype = str(np.asarray(sys.A_blocks).dtype)
        if manifest.get("solver") != solver.name:
            raise ValueError(
                f"factor-store manifest drift at {path}: entry was written "
                f"by solver {manifest.get('solver')!r}, requested "
                f"{solver.name!r}")
        if manifest.get("system_structure", "dense") != sys.structure:
            raise ValueError(
                f"factor-store manifest drift at {path}: entry holds "
                f"{manifest.get('system_structure', 'dense')!r} factors, "
                f"requested {sys.structure!r} — the fingerprint should "
                f"have separated these; entry may be corrupt")
        if list(manifest.get("partition", [])) != want_part:
            raise ValueError(
                f"factor-store manifest drift at {path}: partition "
                f"{manifest.get('partition')} != running {want_part} — was "
                f"the system re-partitioned since the entry was written?")
        if manifest.get("dtype") != want_dtype:
            raise ValueError(
                f"factor-store manifest drift at {path}: dtype "
                f"{manifest.get('dtype')} != running {want_dtype} — was the "
                f"x64 flag changed since the entry was written?")
        leaves = []
        for i, ref in enumerate(manifest["leaves"]):
            arr = np.load(os.path.join(path, f"leaf_{i:05d}.npy"))
            if list(arr.shape) != list(ref["shape"]) \
                    or str(arr.dtype) != ref["dtype"]:
                raise ValueError(
                    f"factor-store entry corrupt at {path}: leaf {i} is "
                    f"{arr.shape}/{arr.dtype}, manifest says "
                    f"{ref['shape']}/{ref['dtype']}")
            leaves.append(arr)
        return _decode(manifest["structure"], leaves)
