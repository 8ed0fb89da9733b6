"""Public jit'd wrappers around the Pallas projection-family kernels.

Handles what the raw kernels do not: shape padding to hardware-aligned
tiles, the BN tile-size choice (measured autotune, cached per (p, n,
dtype)), multi-RHS row-batch layout, and vmapping over the worker axis.

The ops here are the fused iteration engine for the whole projection
family (``use_kernel=True`` on apc / consensus / cimmino, both backends):

  * ``block_projection(A, B, x, xbar, gamma)`` — the fused APC/consensus
    worker update y = x + γ·P(x̄ − x); x/x̄ may carry a leading (k,) RHS
    batch, which streams through ONE VMEM residency of each A/B tile.
  * ``proj_gather`` / ``proj_scatter`` — the same two passes split so the
    mesh backend can psum the (k, p) gather result over column shards
    between them (B_loc u needs the FULL u = A d).
  * ``cimmino_update(A, B, b, xbar)`` — the fused block-Cimmino row
    projection r = B(b − A x̄), split the same way into
    ``cimmino_gather`` / ``cimmino_scatter``.

Every op accepts 1-D row vectors (plain solve) or (k, n) batches
(``solve_many`` / ``LinsysServer``) and pads k / p / n to the (8, 8, 128)
MXU-aligned tile internally — zero rows/cols are exact (zero-padded A rows
produce zero U entries; zero-padded B columns ignore them).

Tile autotune: ``pick_tiles`` measures candidate (BN, BP, BK) tiles on the
actual gather+scatter pair — BN lane tiles via ``pick_bn`` (cached per
(p, n_pad, dtype), the original search), then p-/k-sublane tiles staged at
the winning BN (cached per (k, p, n, dtype)).  The measurement runs where
the kernels actually compile (skipped in interpret mode — interpret
timings say nothing about HBM traffic).  Only tiles the TPU compiler
accepts are ever candidates or defaults: blocks obey the (8, 128) rule
and fit ``VMEM_BUDGET`` (``tile_vmem_bytes``), so where the whole p axis
does not fit, the default is the largest fitting p tile.  Force the
measurement with ``REPRO_KERNEL_AUTOTUNE=1``, disable it with ``=0``, or
pin tiles outright with ``REPRO_KERNEL_BN=256`` / ``REPRO_KERNEL_BP=128``
/ ``REPRO_KERNEL_BK=8``.

Sparse systems get the same fused engine over the compressed support:
``sparse_proj_update`` / ``sparse_cimmino_update`` run the (p, w) vals /
(w, p) Bvals tiles through the identical Pallas contractions (lane axis =
padded support width) with the support gather/scatter-add in XLA around
them, and return the gather result ``u`` alongside the update — the
fused-residual source (no second read of A per iteration).
"""
from __future__ import annotations

import functools
import logging
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.spans import span

from . import block_projection as bp
from . import ref

log = logging.getLogger("repro.kernels")

BN_ENV = "REPRO_KERNEL_BN"
BP_ENV = "REPRO_KERNEL_BP"
BK_ENV = "REPRO_KERNEL_BK"
AUTOTUNE_ENV = "REPRO_KERNEL_AUTOTUNE"

# (p_pad, n_pad, dtype-name) -> measured (or heuristic) BN tile
_BN_CACHE: dict = {}
# (k_pad, p_pad, n_pad, dtype-name) -> measured (bp, bk) sublane tiles
_TILE_CACHE: dict = {}
# candidate lane tiles, measured in this order; the heuristic fallback is
# the FIRST fitting candidate dividing n_pad (512 wherever it fits)
BN_CANDIDATES = (bp.DEFAULT_BN, 1024, 256, 128)
# candidate p-/k-tiles below the default.  A TPU block's last two dims
# are multiples of (8, 128) or span the whole axis: p is the lane dim of
# the B and U blocks, so a cut p tile is a 128-multiple; k is a sublane
# dim, so a cut k tile is an 8-multiple
BP_CANDIDATES = (512, 256, 128)
BK_CANDIDATES = (32, 16, 8)
# scoped VMEM one kernel's pipelined blocks may take: the v5e compiler's
# default limit is 16 MiB, less a margin for its internal scratch
VMEM_BUDGET = 14 * 2**20


def _pad_axis(a, axis: int, mult: int):
    size = a.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return a, size
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, rem)
    return jnp.pad(a, pads), size


def _pad_to(size: int, mult: int) -> int:
    return size + (-size) % mult


def _rows(x):
    """Lift (n,) to the (1, n) kernel row layout; remember to squeeze."""
    x = jnp.asarray(x)
    if x.ndim == 1:
        return x[None, :], True
    return x, False


def _pad_rows(x):
    """Pad the RHS-batch axis to the 8-sublane tile (k == 1 stays 1 — the
    single-RHS layout the kernels always supported)."""
    if x.shape[0] == 1:
        return x
    return _pad_axis(x, 0, 8)[0]


def bn_cache_clear() -> None:
    """Drop every cached BN choice (tests / re-tuning)."""
    _BN_CACHE.clear()


def bn_cache() -> dict:
    """The live {(p_pad, n_pad, dtype): bn} autotune cache (read-only use)."""
    return dict(_BN_CACHE)


def tile_vmem_bytes(bn: int, bp_: int, bk: int, dtype) -> int:
    """VMEM one grid step of the larger kernel of a pair (the APC
    scatter) holds: every block double-buffered — the (BN, BP) A/B tile
    in the stored dtype, plus the x, x̄, output (BK, BN) rows and the
    (BK, BP) U block in the compute dtype (f32 at least) — and one
    working copy of two row blocks and of U for the full-precision MXU
    passes, each padded to the (8, 128) tile.  Checked against the v5e
    compiler: every shape it counts within ``VMEM_BUDGET`` compiled."""
    a = np.dtype(dtype).itemsize
    c = max(a, 4)
    rows, lanes = _pad_to(bk, 8), _pad_to(bp_, 128)
    blocks = bn * lanes * a + 3 * rows * bn * c + rows * lanes * c
    return 2 * blocks + 2 * rows * bn * c + rows * lanes * c


def tile_fits(bn: int, bp_: int, bk: int, dtype) -> bool:
    return tile_vmem_bytes(bn, bp_, bk, dtype) <= VMEM_BUDGET


def _p_tiles(p_pad: int):
    """Legal p tiles, largest first: the whole axis, then every
    128-multiple that divides it."""
    return [p_pad] + [t for t in range((p_pad - 1) // 128 * 128, 0, -128)
                      if p_pad % t == 0]


def _k_tiles(k_pad: int):
    """Legal k tiles, largest first: the whole batch, then the smaller
    8-multiple candidates that divide it."""
    return [k_pad] + [c for c in BK_CANDIDATES
                      if c < k_pad and k_pad % c == 0]


def default_tiles(bn: int, p_pad: int, k_pad: int, dtype):
    """The (bp, bk) schedule without measurement: whole axes where they
    fit VMEM, else the whole batch with the largest fitting p tile, else
    cut k too.  None when nothing fits at this BN."""
    for bk in _k_tiles(k_pad):
        for bpp in _p_tiles(p_pad):
            if tile_fits(bn, bpp, bk, dtype):
                return bpp, bk
    return None


def _autotune_enabled(interpret: bool) -> bool:
    env = os.environ.get(AUTOTUNE_ENV)
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "off")
    # interpret-mode timings measure the python interpreter, not HBM
    # traffic — default to the heuristic there
    return not interpret


def _measure_bn(p_pad: int, n_pad: int, dtype, cands, interpret: bool) -> int:
    """Time the gather+scatter pair per candidate lane tile, each at its
    default (bp, bk) for an 8-row batch; fastest wins (ties: earliest)."""
    times = {bn: _measure_pair(p_pad, n_pad, 8, dtype, bn,
                               *default_tiles(bn, p_pad, 8, dtype),
                               interpret)
             for bn in cands}
    best = min(cands, key=times.__getitem__)
    log.debug("autotuned BN=%d for (p=%d, n=%d, %s) in %d candidates",
              best, p_pad, n_pad, np.dtype(dtype).name, len(cands))
    return best


def pick_bn(n_pad: int, p_pad: int = 8, dtype=jnp.float32, *,
            interpret: bool = True) -> int:
    """The lane-axis tile for a (p, n) block: env pin > cache > measure.

    Called at trace time (shapes are static), so the measured choice is
    resolved once per (p, n, dtype) and the kernel grid is fixed from it.
    """
    env = os.environ.get(BN_ENV)
    if env:
        bn = int(env)
        if n_pad % bn:
            raise ValueError(
                f"{BN_ENV}={bn} does not divide the padded n={n_pad} "
                f"(n pads to a multiple of 128; pick a 128-multiple tile "
                f"that divides it)")
        return bn
    key = (int(p_pad), int(n_pad), np.dtype(dtype).name)
    hit = _BN_CACHE.get(key)
    if hit is not None:
        return hit
    # only tiles some legal (bp, bk) schedule fits in VMEM — a candidate
    # the compiler refuses is never measured, never the default
    cands = [c for c in BN_CANDIDATES if n_pad % c == 0
             and default_tiles(c, p_pad, 1, dtype) is not None] or [128]
    if len(cands) == 1 or not _autotune_enabled(interpret):
        bn = cands[0]
    else:
        with span("repro.ops.autotune", what="bn"):
            bn = _measure_bn(key[0], key[1], np.dtype(dtype), cands,
                             interpret)
    _BN_CACHE[key] = bn
    return bn


def tile_cache_clear() -> None:
    """Drop every cached (bp, bk) sublane-tile choice (tests / re-tuning)."""
    _TILE_CACHE.clear()


def tile_cache() -> dict:
    """The live {(k_pad, p_pad, n_pad, dtype): (bp, bk)} cache (read-only)."""
    return dict(_TILE_CACHE)


def _env_tile(env_name: str, axis_pad: int, axis: str):
    """An env-pinned sublane tile, validated against the padded axis."""
    env = os.environ.get(env_name)
    if not env:
        return None
    t = int(env)
    if axis_pad % t:
        raise ValueError(
            f"{env_name}={t} does not divide the padded {axis}={axis_pad} "
            f"({axis} pads to a multiple of 8; pick an 8-multiple tile "
            f"that divides it)")
    return t


def _measure_pair(p_pad, n_pad, k_pad, dtype, bn, bpp, bk, interpret):
    """Time the gather+scatter pair once at a (bn, bp, bk) tiling."""
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((p_pad, n_pad)), dtype)
    B = jnp.asarray(rng.standard_normal((n_pad, p_pad)), dtype)
    x = jnp.asarray(rng.standard_normal((k_pad, n_pad)), dtype)
    g = jnp.ones((1, 1), dtype)

    def run():
        u = bp.apc_gather(A, x, x, bn=bn, bp=bpp, bk=bk,
                          interpret=interpret)
        return bp.apc_scatter(B, x, x, u, g, bn=bn, bp=bpp, bk=bk,
                              interpret=interpret)
    jax.block_until_ready(run())            # compile + warm
    t0 = time.perf_counter()
    for _ in range(3):
        out = run()
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def _measure_tiles(k_pad, p_pad, n_pad, dtype, bn, default, interpret):
    """Staged (bp, bk) search at the already-chosen BN: measure the
    default, then the smaller fitting p-tile candidates at its k tile,
    then the smaller k-tile candidates at the winning p tile —
    O(|BP| + |BK|) timings instead of the full cross product."""
    best_bp, best_bk = default
    best_t = _measure_pair(p_pad, n_pad, k_pad, dtype, bn, best_bp, best_bk,
                           interpret)
    for c in [c for c in BP_CANDIDATES if c < best_bp and p_pad % c == 0
              and tile_fits(bn, c, best_bk, dtype)]:
        t = _measure_pair(p_pad, n_pad, k_pad, dtype, bn, c, best_bk,
                          interpret)
        if t < best_t:
            best_bp, best_t = c, t
    for c in [c for c in _k_tiles(k_pad)[1:] if c < best_bk
              and tile_fits(bn, best_bp, c, dtype)]:
        t = _measure_pair(p_pad, n_pad, k_pad, dtype, bn, best_bp, c,
                          interpret)
        if t < best_t:
            best_bk, best_t = c, t
    log.debug("autotuned (bp=%d, bk=%d) at bn=%d for (k=%d, p=%d, n=%d, %s)",
              best_bp, best_bk, bn, k_pad, p_pad, n_pad,
              np.dtype(dtype).name)
    return best_bp, best_bk


def pick_tiles(n_pad: int, p_pad: int = 8, k_pad: int = 1,
               dtype=jnp.float32, *, interpret: bool = True):
    """The (bn, bp, bk) tiling for a (k, p, n) kernel call.

    BN comes from ``pick_bn`` (env pin > cache > measurement — the
    original lane-tile search, cache format unchanged); the p-/k-sublane
    tiles resolve env pin (``REPRO_KERNEL_BP`` / ``REPRO_KERNEL_BK``) >
    cache > staged measurement at the winning BN > ``default_tiles``
    (whole axes wherever they fit VMEM — the original single-residency
    schedule).  Called at trace time, so the choice is baked into each
    compiled executor.
    """
    bn = pick_bn(n_pad, p_pad, dtype, interpret=interpret)
    bpp = _env_tile(BP_ENV, p_pad, "p")
    bk = _env_tile(BK_ENV, k_pad, "k")
    if bpp is not None and bk is not None:
        return bn, bpp, bk
    key = (int(k_pad), int(p_pad), int(n_pad), np.dtype(dtype).name)
    hit = _TILE_CACHE.get(key)
    if hit is None:
        # nothing fits only at a BN too wide for VMEM (pinned, or the
        # last-resort 128): keep the whole axes; the compiler says so
        default = (default_tiles(bn, key[1], key[0], dtype)
                   or (key[1], key[0]))
        if _autotune_enabled(interpret) and (p_pad > 8 or k_pad > 8):
            with span("repro.ops.autotune", what="tiles"):
                hit = _measure_tiles(key[0], key[1], key[2],
                                     np.dtype(dtype), bn, default, interpret)
        else:
            hit = default
        _TILE_CACHE[key] = hit
    return bn, (bpp if bpp is not None else hit[0]), \
        (bk if bk is not None else hit[1])


# ---------------------------------------------------------------------------
# Engine autotune: "unfused" is a candidate too
# ---------------------------------------------------------------------------
#
# The tile autotune above assumes the fused kernel is the right engine and
# only picks its lane tile.  That is false in one measured corner: the
# Cimmino kernel LOSES to the plain XLA step at batch 1 (0.88x in
# BENCH_PR5.json — the single-RHS row projection has no A/B-tile reuse to
# amortize, so the kernel's padding + two-pass overhead is pure cost).
# ``use_fused`` extends the measured autotune with the unfused step as a
# candidate per (family, p, n, k, dtype): the projection-family dispatch
# consults it at TRACE time (shapes are static) and falls back to the
# unfused step when fused loses, so ``use_kernel=True`` always means "the
# faster engine", never "the fused engine even where it regresses".
#
# What "unfused" runs depends on the family.  For dense ``apc`` (APC and
# consensus) it is the XLA pinv step: the factors of ``use_kernel=True``
# carry B = Aᵀ G⁻¹, so the fallback contracts against B (one pass over A,
# one over B) and solves nothing.  For ``cimmino`` and the ``*_sparse``
# families it is the Cholesky step, two triangular solves per worker.  The
# measurement times the same step the dispatch runs.
#
# ``REPRO_KERNEL_ENGINE=fused|unfused`` pins the choice (benchmarks use it
# to measure the raw fused path); where measurement is off (interpret mode
# without REPRO_KERNEL_AUTOTUNE=1) the decision comes from the measured
# BENCH trend itself: fused everywhere EXCEPT cimmino below a full
# 8-sublane RHS batch.

ENGINE_ENV = "REPRO_KERNEL_ENGINE"
# the *_sparse families measure the compressed-support kernels against the
# unfused SparseBlocks step; their cache keys carry the padded support
# width w (the contraction axis) alongside the global n
ENGINE_FAMILIES = ("apc", "cimmino", "apc_sparse", "cimmino_sparse")
# (family, p_pad, n_pad, k_pad, dtype-name) -> bool (True = fused wins);
# sparse families key as (family, p_pad, n_pad, k_pad, w_pad, dtype-name)
_ENGINE_CACHE: dict = {}


def engine_cache_clear() -> None:
    """Drop every cached engine choice (tests / re-tuning)."""
    _ENGINE_CACHE.clear()


def engine_cache() -> dict:
    """The live engine-choice cache (read-only use)."""
    return dict(_ENGINE_CACHE)


_MEAS_WORKERS = 2   # dummy worker axis the engine measurement vmaps over
# the probe times the bare kernel pair, but the dispatched step wraps it
# in glue (fused residual harvest, state bookkeeping, consensus psum)
# that burdens the fused path more than the unfused one — so a fused
# "win" inside this margin is measurement noise, not a real win
_ENGINE_MARGIN = 0.85


def _engine_candidates(family: str, p_pad: int, n_pad: int, k_pad: int,
                       dtype, interpret: bool, w: Optional[int] = None
                       ) -> Dict[str, Callable[[], Any]]:
    """The two engines ``_measure_engine`` times for one (p, n, k) shape,
    as zero-argument calls on dummy operands: ``fused`` (the kernel pair)
    and ``unfused`` (the XLA step the dispatch falls back to — the pinv
    step for ``apc`` and ``apc_sparse``, the Cholesky step for Cimmino).
    Each is jitted and ``vmap``-ed over a small dummy worker axis
    (``_MEAS_WORKERS``), the way the solvers dispatch them.  Sparse
    families run the compressed-support fused op and the unfused
    SparseBlocks-style step on a random w-column support."""
    rng = np.random.default_rng(0)
    mw = _MEAS_WORKERS
    if family.endswith("_sparse"):
        w = int(w)
        cols = jnp.asarray(np.stack(
            [np.sort(rng.choice(n_pad, size=w, replace=False))
             for _ in range(mw)]), jnp.int32)                  # (mw, w)
        vals = jnp.asarray(rng.standard_normal((mw, p_pad, w)), dtype)
        G = (jnp.einsum("mpw,mqw->mpq", vals, vals)
             + 1e-3 * jnp.eye(p_pad, dtype=dtype))
        L = jnp.linalg.cholesky(G)
        bvals = jax.vmap(
            lambda vi, Li: jax.scipy.linalg.cho_solve((Li, True), vi).T)(
                vals, L)                                       # (mw, w, p)
        x = jnp.asarray(rng.standard_normal((k_pad, n_pad)), dtype)
        xbar = jnp.asarray(rng.standard_normal((k_pad, n_pad)), dtype)
        b = jnp.asarray(rng.standard_normal((mw, k_pad, p_pad)), dtype)

        if family == "cimmino_sparse":
            fused_v = jax.jit(jax.vmap(
                lambda vi, ci, bvi, bi: sparse_cimmino_update(
                    vi, ci, bvi, bi, xbar, interpret=interpret)))

            def fused():
                return fused_v(vals, cols, bvals, b)

            def _unf(vi, ci, bvi, bi):
                u = xbar[:, ci] @ vi.T
                c = (bi - u) @ bvi.T
                return jnp.zeros_like(xbar).at[:, ci].add(c)
            unfused_v = jax.jit(jax.vmap(_unf))

            def unfused():
                return unfused_v(vals, cols, bvals, b)
        else:
            fused_v = jax.jit(jax.vmap(
                lambda vi, ci, bvi: sparse_proj_update(
                    vi, ci, bvi, x, xbar, 1.0, interpret=interpret)))

            def fused():
                return fused_v(vals, cols, bvals)

            def _unf(vi, ci, bvi):
                d = xbar - x
                u = d[:, ci] @ vi.T
                return (x + d).at[:, ci].add(-(u @ bvi.T))
            unfused_v = jax.jit(jax.vmap(_unf))

            def unfused():
                return unfused_v(vals, cols, bvals)
    else:
        A = jnp.asarray(rng.standard_normal((mw, p_pad, n_pad)), dtype)
        G = (jnp.einsum("mpn,mqn->mpq", A, A)
             + 1e-3 * jnp.eye(p_pad, dtype=dtype))
        L = jnp.linalg.cholesky(G)
        Bm = jax.vmap(
            lambda Ai, Li: jax.scipy.linalg.cho_solve((Li, True), Ai).T)(
                A, L)                                          # (mw, n, p)
        x = jnp.asarray(rng.standard_normal((k_pad, n_pad)), dtype)
        xbar = jnp.asarray(rng.standard_normal((k_pad, n_pad)), dtype)
        b = jnp.asarray(rng.standard_normal((mw, k_pad, p_pad)), dtype)

        if family == "cimmino":
            fused_v = jax.jit(jax.vmap(
                lambda Ai, Bi, bi: cimmino_update(Ai, Bi, bi, xbar,
                                                  interpret=interpret)))

            def fused():
                return fused_v(A, Bm, b)

            def _unf(Ai, Li, bi):
                w_ = jax.scipy.linalg.cho_solve((Li, True),
                                                (bi - xbar @ Ai.T).T).T
                return w_ @ Ai
            unfused_v = jax.jit(jax.vmap(_unf))

            def unfused():
                return unfused_v(A, L, b)
        else:
            fused_v = jax.jit(jax.vmap(
                lambda Ai, Bi: block_projection(Ai, Bi, x, xbar, 1.0,
                                                interpret=interpret)))

            def fused():
                return fused_v(A, Bm)

            def _unf(Ai, Bi):
                d = xbar - x
                return x + (d - (d @ Ai.T) @ Bi.T)
            unfused_v = jax.jit(jax.vmap(_unf))

            def unfused():
                return unfused_v(A, Bm)
    return {"fused": fused, "unfused": unfused}


def _measure_engine(family: str, p_pad: int, n_pad: int, k_pad: int,
                    dtype, interpret: bool, w: Optional[int] = None) -> bool:
    """Time the fused kernel pair against the unfused XLA step for the
    SAME (p, n, k) shape (``_engine_candidates``).  Both run vmapped over
    a dummy worker axis: the per-step dispatch IS ``vmap(worker)`` over
    the m blocks, and batching a pallas_call — above all through the
    interpreter — costs far more than batching the equivalent XLA step,
    so a lone un-vmapped call flatters the fused engine and mis-routes
    the verdict.  Faster engine wins.  Best-of-5 after a compile warmup
    (same protocol as ``_measure_bn``)."""
    runs = _engine_candidates(family, p_pad, n_pad, k_pad, dtype, interpret,
                              w=w)
    # true best-of-5: min over separately timed runs, so one scheduler
    # hiccup inside a candidate's window cannot flip the verdict (a
    # summed window did exactly that on loaded single-core CI hosts)
    times = {}
    for name, run in runs.items():
        jax.block_until_ready(run())             # compile + warm
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            best = min(best, time.perf_counter() - t0)
        times[name] = best
    fused_wins = times["fused"] <= _ENGINE_MARGIN * times["unfused"]
    log.debug("engine autotune %s (p=%d, n=%d, k=%d, %s): fused %.1fus "
              "unfused %.1fus -> %s", family, p_pad, n_pad, k_pad,
              np.dtype(dtype).name, times["fused"] * 1e6,
              times["unfused"] * 1e6,
              "fused" if fused_wins else "unfused")
    return fused_wins


def use_fused(family: str, p: int, n: int, k: int = 1,
              dtype=jnp.float32, *, w: Optional[int] = None,
              interpret: Optional[bool] = None) -> bool:
    """Should this (family, p, n, k, dtype) shape run the fused kernels?

    Resolution order: ``REPRO_KERNEL_ENGINE`` pin > cache > measured
    fused-vs-unfused comparison (where the autotune measures — see
    ``_autotune_enabled``) > the BENCH-trend heuristic (fused everywhere
    except cimmino below a full 8-row RHS batch).  Called at trace time by
    the projection-family ``step``/``step_many`` dispatch, so the choice
    is baked into each compiled executor — zero steady-state retraces.

    The ``*_sparse`` families require ``w`` (the support width — the
    contraction axis the compressed kernels actually stream) and key the
    cache on it alongside the global n.
    """
    if family not in ENGINE_FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"expected one of {ENGINE_FAMILIES}")
    sparse = family.endswith("_sparse")
    if sparse and w is None:
        raise ValueError(f"family {family!r} requires the support width w")
    env = os.environ.get(ENGINE_ENV)
    if env:
        choice = env.strip().lower()
        if choice not in ("fused", "unfused"):
            raise ValueError(f"{ENGINE_ENV}={env!r}: expected 'fused' or "
                             "'unfused'")
        return choice == "fused"
    if interpret is None:
        interpret = bp.default_interpret()
    p_pad = _pad_to(int(p), 8)
    n_pad = _pad_to(int(n), 128)
    k_pad = 1 if int(k) == 1 else _pad_to(int(k), 8)
    if sparse:
        key = (family, p_pad, n_pad, k_pad, int(w), np.dtype(dtype).name)
    else:
        key = (family, p_pad, n_pad, k_pad, np.dtype(dtype).name)
    hit = _ENGINE_CACHE.get(key)
    if hit is not None:
        return hit
    if _autotune_enabled(interpret):
        with span("repro.ops.autotune", what="engine"):
            fused = _measure_engine(family, p_pad, n_pad, k_pad,
                                    np.dtype(dtype), interpret,
                                    w=(int(w) if sparse else None))
    else:
        # the measured trend (BENCH_PR5/PR6): the fused engine wins
        # wherever the RHS batch fills the 8-sublane tile or the APC
        # pinv step removes per-iteration Gram solves; the lone loser is
        # the sub-batch cimmino row projection (dense or sparse)
        fused = not (family.startswith("cimmino") and k_pad < 8)
    _ENGINE_CACHE[key] = fused
    return fused


# ---------------------------------------------------------------------------
# APC / consensus: the two projection passes, split and fused
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def proj_gather(A, x, xbar, *, interpret: Optional[bool] = None):
    """u = A (x̄ − x) for one worker.   A (p, n); x/x̄ (n,) or (k, n).

    Returns (p,) / (k, p).  The mesh backend psums this over column
    shards before handing it to ``proj_scatter``.
    """
    if interpret is None:
        interpret = bp.default_interpret()
    p, n = A.shape
    A2, _ = _pad_axis(A, 0, 8)
    A2, _ = _pad_axis(A2, 1, 128)
    x2, squeeze = _rows(x)
    xb2, _ = _rows(xbar)
    k = x2.shape[0]
    x2 = _pad_rows(_pad_axis(x2, 1, 128)[0])
    xb2 = _pad_rows(_pad_axis(xb2, 1, 128)[0])
    n_pad = A2.shape[1]
    bn, bpp, bk = pick_tiles(n_pad, A2.shape[0], x2.shape[0], A.dtype,
                             interpret=interpret)
    u = bp.apc_gather(A2, x2, xb2, bn=bn, bp=bpp, bk=bk,
                      interpret=interpret)
    u = u[:k, :p]
    return u[0] if squeeze else u


@functools.partial(jax.jit, static_argnames=("interpret",))
def proj_scatter(B, x, xbar, u, gamma, *, interpret: Optional[bool] = None):
    """y = x + γ(d − B u) for one worker.   B (n, p); u (p,) or (k, p)."""
    if interpret is None:
        interpret = bp.default_interpret()
    n, p = B.shape
    B2, _ = _pad_axis(B, 1, 8)
    B2, _ = _pad_axis(B2, 0, 128)
    x2, squeeze = _rows(x)
    xb2, _ = _rows(xbar)
    u2, _ = _rows(u)
    k = x2.shape[0]
    x2 = _pad_rows(_pad_axis(x2, 1, 128)[0])
    xb2 = _pad_rows(_pad_axis(xb2, 1, 128)[0])
    u2 = _pad_rows(_pad_axis(u2, 1, 8)[0])
    n_pad = B2.shape[0]
    bn, bpp, bk = pick_tiles(n_pad, B2.shape[1], x2.shape[0], B.dtype,
                             interpret=interpret)
    g = jnp.asarray(gamma, x2.dtype).reshape(1, 1)
    y = bp.apc_scatter(B2, x2, xb2, u2, g, bn=bn, bp=bpp, bk=bk,
                       interpret=interpret)
    y = y[:k, :n]
    return y[0] if squeeze else y


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_projection(A, B, x, xbar, gamma, *,
                     interpret: Optional[bool] = None):
    """y = x + γ (d − B (A d)), d = x̄ − x, via the two fused Pallas passes.

    A (p, n), B (n, p); x/x̄ either (n,) — a plain solve — or (k, n), the
    multi-RHS batch whose k rows share ONE read of every A/B tile.  Pads
    k to a multiple of 8 (batched), p to a multiple of 8 and n to a
    multiple of 128 (zero rows/cols are exact: zero-padded A rows produce
    zero u entries; zero-padded B columns ignore them).

    ``interpret=None`` defers to ``block_projection.default_interpret()``:
    compiled on a real TPU, interpret mode elsewhere, env-overridable via
    ``REPRO_PALLAS_INTERPRET``.
    """
    if interpret is None:
        interpret = bp.default_interpret()
    p, n = A.shape
    A2, _ = _pad_axis(A, 0, 8)
    A2, _ = _pad_axis(A2, 1, 128)
    B2, _ = _pad_axis(B, 1, 8)
    B2, _ = _pad_axis(B2, 0, 128)
    x2, squeeze = _rows(x)
    xb2, _ = _rows(xbar)
    k = x2.shape[0]
    x2 = _pad_rows(_pad_axis(x2, 1, 128)[0])
    xb2 = _pad_rows(_pad_axis(xb2, 1, 128)[0])
    n_pad = A2.shape[1]
    bn, bpp, bk = pick_tiles(n_pad, A2.shape[0], x2.shape[0], A.dtype,
                             interpret=interpret)

    u = bp.apc_gather(A2, x2, xb2, bn=bn, bp=bpp, bk=bk,
                      interpret=interpret)                      # (k8, p8)
    g = jnp.asarray(gamma, x2.dtype).reshape(1, 1)
    y = bp.apc_scatter(B2, x2, xb2, u, g, bn=bn, bp=bpp, bk=bk,
                       interpret=interpret)
    y = y[:k, :n]
    return y[0] if squeeze else y


def block_projection_batched(A, B, x, xbar, gamma, *,
                             interpret: Optional[bool] = None):
    """vmap over the leading worker axis: A (m,p,n), B (m,n,p), x (m,n)."""
    fn = functools.partial(block_projection, interpret=interpret)
    return jax.vmap(fn, in_axes=(0, 0, 0, None, None))(A, B, x, xbar, gamma)


# ---------------------------------------------------------------------------
# Block Cimmino: the row-projection passes
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def cimmino_gather(A, xbar, *, interpret: Optional[bool] = None):
    """u = A x̄ for one worker.   A (p, n); x̄ (n,) or (k, n) -> (p,)/(k, p).

    The mesh backend psums this over column shards before forming
    v = b − u for ``cimmino_scatter``.
    """
    if interpret is None:
        interpret = bp.default_interpret()
    p, n = A.shape
    A2, _ = _pad_axis(A, 0, 8)
    A2, _ = _pad_axis(A2, 1, 128)
    xb2, squeeze = _rows(xbar)
    k = xb2.shape[0]
    xb2 = _pad_rows(_pad_axis(xb2, 1, 128)[0])
    n_pad = A2.shape[1]
    bn, bpp, bk = pick_tiles(n_pad, A2.shape[0], xb2.shape[0], A.dtype,
                             interpret=interpret)
    u = bp.cimmino_gather(A2, xb2, bn=bn, bp=bpp, bk=bk,
                          interpret=interpret)
    u = u[:k, :p]
    return u[0] if squeeze else u


@functools.partial(jax.jit, static_argnames=("interpret",))
def cimmino_scatter(B, v, *, interpret: Optional[bool] = None):
    """r = B v for one worker.   B (n, p); v (p,) or (k, p) -> (n,)/(k, n)."""
    if interpret is None:
        interpret = bp.default_interpret()
    n, p = B.shape
    B2, _ = _pad_axis(B, 1, 8)
    B2, _ = _pad_axis(B2, 0, 128)
    v2, squeeze = _rows(v)
    k = v2.shape[0]
    v2 = _pad_rows(_pad_axis(v2, 1, 8)[0])
    n_pad = B2.shape[0]
    bn, bpp, bk = pick_tiles(n_pad, B2.shape[1], v2.shape[0], B.dtype,
                             interpret=interpret)
    r = bp.cimmino_scatter(B2, v2, bn=bn, bp=bpp, bk=bk,
                           interpret=interpret)
    r = r[:k, :n]
    return r[0] if squeeze else r


@functools.partial(jax.jit, static_argnames=("interpret",))
def cimmino_update(A, B, b, xbar, *, interpret: Optional[bool] = None):
    """Fused block-Cimmino row projection r = B (b − A x̄) for one worker.

    A (p, n), B = Aᵀ G⁻¹ (n, p); b (p,) or (k, p); x̄ (n,) or (k, n).
    Returns (n,) / (k, n).  The master update x̄ += ν Σᵢ rᵢ stays outside
    (it is the worker-axis reduction, a psum on the mesh backend).
    """
    u = cimmino_gather(A, xbar, interpret=interpret)
    return cimmino_scatter(B, jnp.asarray(b) - u, interpret=interpret)


# ---------------------------------------------------------------------------
# Sparse fused updates (compressed SparseBlocks support)
# ---------------------------------------------------------------------------
#
# One worker's SparseBlocks slice is a dense (p, w) vals tile on w global
# columns ``cols`` plus the matching (w, p) pseudoinverse factor Bvals
# (B_i = A_iᵀ G_i⁻¹ has rows only on the support).  The fused ops gather
# the support columns of the iterate in XLA (TPU has no lane-axis hardware
# gather), run the SAME Pallas contractions as the dense engine over the
# padded support width, and scatter-add the rank-p correction back.
# Padded support slots carry exact-zero vals — and therefore exact-zero
# Bvals rows — so every padded contribution is exactly zero (duplicate
# padded indices add zeros).  Both ops return the gather result ``u``
# alongside the update: it is the per-iteration residual source (APC
# invariant A_i x_i = b_i makes u = A_i x̄ − b_i; Cimmino's is u − b), so
# recording the history costs no second pass over A.


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_proj_update(vals, cols, bvals, x, xbar, gamma, *,
                       interpret: Optional[bool] = None):
    """Fused sparse APC/consensus worker update y = x + γ(d − B u).

    vals (p, w); cols (w,) int32 global indices; bvals (w, p); x/x̄ (n,)
    or (k, n).  Returns ``(y, u)`` with y (n,)/(k, n) and u (p,)/(k, p)
    = A_i(x̄ − x) — the fused-residual source.
    """
    if interpret is None:
        interpret = bp.default_interpret()
    p, w = vals.shape
    x2, squeeze = _rows(x)
    xb2, _ = _rows(xbar)
    k = x2.shape[0]
    V2, _ = _pad_axis(vals, 0, 8)
    V2, _ = _pad_axis(V2, 1, 128)              # (p8, w128)
    Bv2, _ = _pad_axis(bvals, 1, 8)
    Bv2, _ = _pad_axis(Bv2, 0, 128)            # (w128, p8)
    with jax.named_scope("apc.gather"):
        xs2 = _pad_rows(_pad_axis(x2[:, cols], 1, 128)[0])
        xbs2 = _pad_rows(_pad_axis(xb2[:, cols], 1, 128)[0])
        bw, bpp, bk = pick_tiles(V2.shape[1], V2.shape[0], xs2.shape[0],
                                 vals.dtype, interpret=interpret)
        u = bp.sparse_gather(V2, xs2, xbs2, bn=bw, bp=bpp, bk=bk,
                             interpret=interpret)          # (k8, p8)
    with jax.named_scope("apc.project"):
        c = bp.sparse_scatter(Bv2, u, bn=bw, bp=bpp, bk=bk,
                              interpret=interpret)         # (k8, w128)
    g = jnp.asarray(gamma, x2.dtype)
    with jax.named_scope("apc.scatter"):
        y = x2 + g * (xb2 - x2)
        y = y.at[:, cols].add(-g * c[:k, :w].astype(y.dtype))
    u = u[:k, :p].astype(x2.dtype)
    return (y[0], u[0]) if squeeze else (y, u)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_cimmino_update(vals, cols, bvals, b, xbar, *,
                          interpret: Optional[bool] = None):
    """Fused sparse block-Cimmino row projection r = B(b − A x̄).

    vals (p, w); cols (w,); bvals (w, p); b (p,) or (k, p); x̄ (n,) or
    (k, n).  Returns ``(r, u)`` with r (n,)/(k, n) — supported on cols —
    and u = A_i x̄ (p,)/(k, p), whose residual block is u − b.
    """
    if interpret is None:
        interpret = bp.default_interpret()
    p, w = vals.shape
    xb2, squeeze = _rows(xbar)
    b2, _ = _rows(b)
    k = xb2.shape[0]
    n = xb2.shape[1]
    xbs = xb2[:, cols]
    V2, _ = _pad_axis(vals, 0, 8)
    V2, _ = _pad_axis(V2, 1, 128)              # (p8, w128)
    Bv2, _ = _pad_axis(bvals, 1, 8)
    Bv2, _ = _pad_axis(Bv2, 0, 128)            # (w128, p8)
    xbs2 = _pad_rows(_pad_axis(xbs, 1, 128)[0])
    w_pad = V2.shape[1]
    bw, bpp, bk = pick_tiles(w_pad, V2.shape[0], xbs2.shape[0], vals.dtype,
                             interpret=interpret)
    u = bp.sparse_cimmino_gather(V2, xbs2, bn=bw, bp=bpp, bk=bk,
                                 interpret=interpret)      # (k8, p8)
    u = u[:k, :p].astype(xb2.dtype)
    v = b2.astype(xb2.dtype) - u
    v2 = _pad_rows(_pad_axis(v, 1, 8)[0])
    c = bp.sparse_scatter(Bv2, v2, bn=bw, bp=bpp, bk=bk,
                          interpret=interpret)             # (k8, w128)
    r = jnp.zeros((k, n), xb2.dtype).at[:, cols].add(
        c[:k, :w].astype(xb2.dtype))
    return (r[0], u[0]) if squeeze else (r, u)


# Re-exported oracle (tests import both from one place).
block_projection_ref = ref.block_projection_ref
