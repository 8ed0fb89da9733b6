"""Pallas TPU kernels for the projection family's per-iteration hot spot.

The projection solvers' worker updates are two dependent GEMMs over the
worker's (p × n) block — *memory-bound* (arithmetic intensity ≈ 1 FLOP/byte
over A and B).  The kernels therefore optimize HBM traffic, not FLOPs:

  * ``apc_gather``:  U = (X̄ − X)·Aᵀ with the difference formed on the fly
    from (X, X̄) tiles — D is never materialized in HBM (saves 2kn reads +
    kn writes per iter).
  * ``apc_scatter``: Y = X + γ(D − U·Bᵀ) fusing the rank-p correction with
    the AXPY — again no D round-trip and no intermediate (k, n) buffer.
  * ``cimmino_gather`` / ``cimmino_scatter``: the block-Cimmino row
    projection r = B(b − A x̄) split the same way (gather U = X̄·Aᵀ,
    scatter R = V·Bᵀ) so the third projection solver shares the engine
    instead of rewriting its update onto the APC shape.

All four kernels are **multi-RHS**: the row-vector operands carry a leading
batch axis k (k = 1 for a plain solve), and the k right-hand sides stream
through the SAME VMEM residency of the A/B tile — one HBM read of A serves
the whole batch, which is what makes the ``solve_many`` / ``LinsysServer``
hot path fused rather than k replayed single-RHS kernels.

Tiling: three axes are cut independently.  The n axis streams in
lane-aligned BN tiles (multiple of 128); the p axis and the k batch may be
cut into BP / BK sublane tiles (multiples of 8) when they outgrow VMEM —
by default both stay whole (p ≪ n by construction and k is a serving
batch), reproducing the original single-residency schedule, as long as
the double-buffered tiles fit the compiler's scoped VMEM (16 MiB on v5e:
an f32 (2048, 512) tile of A takes 4 MiB per buffer; a (4096, 512) one
does not fit).  All three tiles are chosen by ``ops.pick_tiles`` (VMEM-
budgeted, measured on TPU, cached per (k, p, n, dtype), pins
``REPRO_KERNEL_BN`` / ``REPRO_KERNEL_BP`` / ``REPRO_KERNEL_BK``).

Accumulation dtype follows the *compute* operand (x / x̄ / u), not the
stored A/B tiles: under ``precision="mixed"`` the A and B streams are
bf16 in HBM (half the bytes of the memory-bound pipe) while every MXU
contraction accumulates in f32 and the iterate stays f32.

The U accumulators use the sequential-grid property of TPU Pallas: every
grid step that revisits an output block accumulates into it, with the
block zero-initialized on the first visit.

**Sparse fused pair.**  A ``SparseBlocks`` worker block stores its values
compressed on the support: vals (p, w) on w global columns ``cols``.  The
compressed vals block IS a dense (p, w) tile, so the sparse kernels are
the SAME contractions with the lane axis n replaced by the (padded)
support width w — one VMEM residency of the vals/Bvals tile per grid
step, streamed exactly like the dense A/B tiles:

  * ``sparse_gather``          U = (X̄ₛ − Xₛ)·valsᵀ     (= apc_gather)
  * ``sparse_cimmino_gather``  U = X̄ₛ·valsᵀ            (= cimmino_gather)
  * ``sparse_scatter``         C = U·Bvalsᵀ            (= cimmino_scatter)

The support gather Xₛ = X[:, cols] / scatter-add back to the n axis are
XLA ops around the kernels (TPU has no lane-axis hardware gather; the
compressed contraction is where the bytes are).  ``ops.sparse_proj_update``
and ``ops.sparse_cimmino_update`` assemble the full sparse worker updates.

All kernels are exposed through ``ops.py`` (padding + autotune + jit + vmap
over workers) and validated in interpret mode against ``ref.py``.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BN = 512          # lane-axis tile; multiple of 128
_I0 = np.int32(0)         # a constant block index that stays int32
# f32 contractions at full precision: the MXU default is one bf16 pass,
# which measured 3.4e-3 relative error for the fused update on a v5e chip
_PRECISION = jax.lax.Precision.HIGHEST


def default_interpret() -> bool:
    """Pallas interpret-mode default, derived from the runtime.

    On a real TPU the kernels compile (interpret=False); everywhere else
    (CPU containers, GPU hosts) they run in interpret mode.  The env var
    ``REPRO_PALLAS_INTERPRET=0/1`` overrides both — e.g. force interpret
    on TPU while bisecting a numerics issue.  Resolved when a kernel
    first traces for a given shape; it is not a per-call toggle (pass
    ``interpret=`` explicitly for that).
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "off")
    return jax.default_backend() != "tpu"


def _acc_dtype(dtype):
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _tiles(size: int, tile: Optional[int], axis: str) -> int:
    tile = size if tile is None else tile
    assert size % tile == 0, (axis, size, tile)
    return tile


def _gather_kernel(x_ref, xbar_ref, a_ref, u_ref, *, acc_dtype):
    """Grid (i, l, j): U[i, l] += (X̄ − X)[i, j] @ A[l, j]ᵀ."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    d = (xbar_ref[...] - x_ref[...]).astype(acc_dtype)      # (BK, BN)
    a = a_ref[...].astype(acc_dtype)                        # (BP, BN)
    # (BK, BN) @ (BN, BP) on the MXU; accumulate in acc_dtype.
    u_ref[...] += jax.lax.dot_general(
        d, a, (((1,), (1,)), ((), ())), precision=_PRECISION,
        preferred_element_type=acc_dtype).astype(u_ref.dtype)


def _scatter_kernel(x_ref, xbar_ref, b_ref, u_ref, g_ref, y_ref, *,
                    acc_dtype):
    """Grid (i, j, l): Y[i, j] = X + γD at l == 0, then −= γ·U[i, l]·B[j, l]ᵀ."""
    l = pl.program_id(2)
    gamma = g_ref[0, 0].astype(acc_dtype)

    @pl.when(l == 0)
    def _init():
        x = x_ref[...].astype(acc_dtype)
        d = xbar_ref[...].astype(acc_dtype) - x             # (BK, BN)
        y_ref[...] = (x + gamma * d).astype(y_ref.dtype)

    u = u_ref[...].astype(acc_dtype)                        # (BK, BP)
    b = b_ref[...].astype(acc_dtype)                        # (BN, BP)
    bu = jax.lax.dot_general(
        u, b, (((1,), (1,)), ((), ())), precision=_PRECISION,
        preferred_element_type=acc_dtype)                   # (BK, BN)
    y = y_ref[...].astype(acc_dtype) - gamma * bu
    y_ref[...] = y.astype(y_ref.dtype)


def _cim_gather_kernel(xbar_ref, a_ref, u_ref, *, acc_dtype):
    """Grid (i, l, j): U[i, l] += X̄[i, j] @ A[l, j]ᵀ."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    xb = xbar_ref[...].astype(acc_dtype)                    # (BK, BN)
    a = a_ref[...].astype(acc_dtype)                        # (BP, BN)
    u_ref[...] += jax.lax.dot_general(
        xb, a, (((1,), (1,)), ((), ())), precision=_PRECISION,
        preferred_element_type=acc_dtype).astype(u_ref.dtype)


def _cim_scatter_kernel(v_ref, b_ref, r_ref, *, acc_dtype):
    """Grid (i, j, l): R[i, j] += V[i, l]·B[j, l]ᵀ (rank-BP write-out)."""
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        r_ref[...] = jnp.zeros_like(r_ref)

    v = v_ref[...].astype(acc_dtype)                        # (BK, BP)
    b = b_ref[...].astype(acc_dtype)                        # (BN, BP)
    r = jax.lax.dot_general(
        v, b, (((1,), (1,)), ((), ())), precision=_PRECISION,
        preferred_element_type=acc_dtype)                   # (BK, BN)
    r_ref[...] = (r_ref[...].astype(acc_dtype) + r).astype(r_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bp", "bk", "interpret"))
def apc_gather(A, x, xbar, *, bn: int = DEFAULT_BN,
               bp: Optional[int] = None, bk: Optional[int] = None,
               interpret: Optional[bool] = None):
    """U = (X̄ − X) Aᵀ.   A (p, n); X, X̄ (k, n) lane-layout.  n % bn == 0.

    k is the RHS batch (k = 1 for a plain solve): every batch row reuses
    the A tile already resident in VMEM, so one A read serves all k.
    ``bp``/``bk`` (default: whole axis) cut the p / k axes into sublane
    tiles; the n axis is innermost so each U block accumulates across its
    BN stream.  Output and accumulation dtypes follow x (the compute
    stream), so a bf16-stored A contracts into an f32 U.
    """
    if interpret is None:
        interpret = default_interpret()
    p, n = A.shape
    k = x.shape[0]
    assert n % bn == 0, (n, bn)
    bp = _tiles(p, bp, "p")
    bk = _tiles(k, bk, "k")
    acc = _acc_dtype(x.dtype)
    kernel = functools.partial(_gather_kernel, acc_dtype=acc)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, p // bp, n // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, l, j: (i, j)),   # x
            pl.BlockSpec((bk, bn), lambda i, l, j: (i, j)),   # xbar
            pl.BlockSpec((bp, bn), lambda i, l, j: (l, j)),   # A
        ],
        out_specs=pl.BlockSpec((bk, bp), lambda i, l, j: (i, l)),
        out_shape=jax.ShapeDtypeStruct((k, p), x.dtype),
        interpret=interpret,
    )(x, xbar, A)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bp", "bk", "interpret"))
def apc_scatter(B, x, xbar, u, gamma, *, bn: int = DEFAULT_BN,
                bp: Optional[int] = None, bk: Optional[int] = None,
                interpret: Optional[bool] = None):
    """Y = X + γ(D − U Bᵀ).   B (n, p); X, X̄ (k, n); U (k, p); γ (1, 1).

    The p axis is innermost: each Y block starts as the fused AXPY
    X + γD on its first visit and accumulates the −γ·U·Bᵀ rank
    correction across the BP stream.
    """
    if interpret is None:
        interpret = default_interpret()
    n, p = B.shape
    k = x.shape[0]
    assert n % bn == 0, (n, bn)
    bp = _tiles(p, bp, "p")
    bk = _tiles(k, bk, "k")
    acc = _acc_dtype(x.dtype)
    kernel = functools.partial(_scatter_kernel, acc_dtype=acc)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, n // bn, p // bp),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j, l: (i, j)),   # x
            pl.BlockSpec((bk, bn), lambda i, j, l: (i, j)),   # xbar
            pl.BlockSpec((bn, bp), lambda i, j, l: (j, l)),   # B
            pl.BlockSpec((bk, bp), lambda i, j, l: (i, l)),   # U
            # gamma scalar in SMEM; int32 block indices, as python ints
            # would trace to int64 under jax_enable_x64 and not lower
            pl.BlockSpec((1, 1), lambda i, j, l: (_I0, _I0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), x.dtype),
        interpret=interpret,
    )(x, xbar, B, u, gamma)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bp", "bk", "interpret"))
def cimmino_gather(A, xbar, *, bn: int = DEFAULT_BN,
                   bp: Optional[int] = None, bk: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """U = X̄ Aᵀ.   A (p, n); X̄ (k, n).  The Cimmino gather pass A x̄."""
    if interpret is None:
        interpret = default_interpret()
    p, n = A.shape
    k = xbar.shape[0]
    assert n % bn == 0, (n, bn)
    bp = _tiles(p, bp, "p")
    bk = _tiles(k, bk, "k")
    acc = _acc_dtype(xbar.dtype)
    kernel = functools.partial(_cim_gather_kernel, acc_dtype=acc)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, p // bp, n // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, l, j: (i, j)),   # xbar
            pl.BlockSpec((bp, bn), lambda i, l, j: (l, j)),   # A
        ],
        out_specs=pl.BlockSpec((bk, bp), lambda i, l, j: (i, l)),
        out_shape=jax.ShapeDtypeStruct((k, p), xbar.dtype),
        interpret=interpret,
    )(xbar, A)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bp", "bk", "interpret"))
def cimmino_scatter(B, v, *, bn: int = DEFAULT_BN,
                    bp: Optional[int] = None, bk: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """R = V Bᵀ.   B (n, p); V (k, p).  The Cimmino scatter pass B v."""
    if interpret is None:
        interpret = default_interpret()
    n, p = B.shape
    k = v.shape[0]
    assert n % bn == 0, (n, bn)
    bp = _tiles(p, bp, "p")
    bk = _tiles(k, bk, "k")
    acc = _acc_dtype(v.dtype)
    kernel = functools.partial(_cim_scatter_kernel, acc_dtype=acc)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, n // bn, p // bp),
        in_specs=[
            pl.BlockSpec((bk, bp), lambda i, j, l: (i, l)),   # v
            pl.BlockSpec((bn, bp), lambda i, j, l: (j, l)),   # B
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), v.dtype),
        interpret=interpret,
    )(v, B)


# ---------------------------------------------------------------------------
# Sparse fused pair (compressed SparseBlocks operands)
# ---------------------------------------------------------------------------
#
# A SparseBlocks worker block is already a dense (p, w) tile on its column
# support, so the sparse kernels ARE the dense contractions with the lane
# axis n replaced by the padded support width w — same VMEM residency, same
# accumulation schedule, ~w/n of the HBM bytes.  The support gather
# Xₛ = X[:, cols] and the scatter-add back to the n axis happen in XLA
# around these calls (``ops.sparse_proj_update`` / ``sparse_cimmino_update``)
# because the TPU has no lane-axis hardware gather; padded support slots
# carry exact-zero vals/Bvals, so their contributions are exactly zero.

sparse_gather = apc_gather            # U = (X̄ₛ − Xₛ)·valsᵀ   (p, w) tile
sparse_cimmino_gather = cimmino_gather  # U = X̄ₛ·valsᵀ
sparse_scatter = cimmino_scatter      # C = U·Bvalsᵀ; scatter-add via cols
