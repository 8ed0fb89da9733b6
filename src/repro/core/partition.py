"""Row-block partitioning of a linear system across workers.

The paper assumes ``m | N`` and a disjoint even split: machine ``i`` receives
``[A_i, b_i]`` with ``A_i in R^{p x n}``, ``p = N/m``.  We keep that layout but
store the blocks stacked as a single ``(m, p, n)`` array so that the whole
worker fleet can be expressed with ``vmap`` (single host) or ``shard_map``
(mesh) without Python-level per-worker loops.

A system carries two orthogonal tags beyond its blocks:

* ``mode`` — ``"square"`` (an exact solution exists; residuals measure
  ``‖Ax−b‖/‖b‖``) or ``"least_squares"`` (minimize ``‖Ax−b‖``; residuals
  measure the LS optimality ``‖AᵀW(Ax−b)‖``, see ``solvers/api.py``).
  Auto-resolved when not given: ``N == n`` -> square, else least_squares.
  Generators that build CONSISTENT tall systems (``b = A x_true``) tag
  ``mode="square"`` explicitly — an exact solution exists even though
  ``N > n``.
* ``structure`` — ``"dense"`` or ``"sparse"``.  Sparse systems keep the
  dense ``(m, p, n)`` block stack (zeros off-support) PLUS a per-block
  column support ``cols`` (m, w); ``A_op`` exposes the compressed
  :class:`repro.core.blockops.SparseBlocks` operand the solvers consume.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import blockops

MODES = ("square", "least_squares")
STRUCTURES = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class BlockSystem:
    """A linear system ``Ax = b`` split into ``m`` row blocks.

    Attributes:
      A_blocks: (m, p, n) stacked row blocks.
      b_blocks: (m, p) stacked right-hand sides.
      x_true:   optional (n,) reference solution for error tracking.
      structure: "dense" | "sparse" (sparse adds the ``cols`` support).
      cols:     (m, w) int32 per-block column support (sparse only);
                padded slots point at all-zero columns so the compressed
                operand is exact.
      mode:     "square" | "least_squares"; auto-resolved from the shape
                when None (N == n -> square).
    """

    A_blocks: jnp.ndarray
    b_blocks: jnp.ndarray
    x_true: Optional[jnp.ndarray] = None
    structure: str = "dense"
    cols: Optional[jnp.ndarray] = None
    mode: Optional[str] = None

    def __post_init__(self):
        if self.structure not in STRUCTURES:
            raise ValueError(f"structure={self.structure!r} not in "
                             f"{STRUCTURES}")
        if self.structure == "sparse" and self.cols is None:
            raise ValueError("sparse systems need a (m, w) cols support; "
                             "build one with partition.as_sparse()")
        if self.mode is None:
            object.__setattr__(
                self, "mode",
                "square" if self.N == self.n else "least_squares")
        elif self.mode not in MODES:
            raise ValueError(f"mode={self.mode!r} not in {MODES}")

    @property
    def m(self) -> int:
        return self.A_blocks.shape[0]

    @property
    def p(self) -> int:
        return self.A_blocks.shape[1]

    @property
    def n(self) -> int:
        return self.A_blocks.shape[2]

    @property
    def N(self) -> int:
        return self.m * self.p

    @property
    def is_sparse(self) -> bool:
        return self.structure == "sparse"

    @property
    def A_op(self):
        """The operand the solvers consume: the dense (m, p, n) stack, or
        the compressed ``SparseBlocks`` support for sparse systems."""
        if not self.is_sparse:
            return self.A_blocks
        vals = jnp.take_along_axis(self.A_blocks, self.cols[:, None, :],
                                   axis=2)
        return blockops.SparseBlocks(
            vals=vals, cols=self.cols,
            span=jnp.zeros((self.n,), self.A_blocks.dtype))

    @functools.cached_property
    def support_fill(self) -> float:
        """Nonzeros over the entries the operand stores: m·p·w for the
        compressed support (w the padded support width), m·p·n dense.
        Counted once on the device, then kept on the host."""
        width = self.cols.shape[1] if self.is_sparse else self.n
        nnz = int(jnp.count_nonzero(self.A_blocks))
        return nnz / (self.m * self.p * width)

    @property
    def sparsity(self) -> float:
        """Fraction of exactly-zero entries in the block stack."""
        return float((np.asarray(self.A_blocks) == 0).mean())

    def densified(self) -> "BlockSystem":
        """The same system with the dense execution path (parity twin)."""
        return dataclasses.replace(self, structure="dense", cols=None)

    def dense(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Reassemble the global ``(N, n)`` system (for small-n analysis)."""
        return (self.A_blocks.reshape(self.N, self.n),
                self.b_blocks.reshape(self.N))


def partition(A, b, m: int, *, x_true=None, mode=None) -> BlockSystem:
    """Split ``Ax=b`` into ``m`` even row blocks (paper's Figure 1 layout).

    Raises if ``m`` does not divide ``N`` — mirroring the paper's setup; pad
    upstream if needed (``pad_to_blocks``).  ``mode=`` propagates a known
    system mode (e.g. a consistent-by-construction tall system stays
    ``"square"``); left None it resolves from the shape.
    """
    A = jnp.asarray(A)
    b = jnp.asarray(b)
    N, n = A.shape
    if N % m != 0:
        raise ValueError(f"m={m} must divide N={N}; use pad_to_blocks() first")
    p = N // m
    return BlockSystem(A.reshape(m, p, n), b.reshape(m, p),
                       None if x_true is None else jnp.asarray(x_true),
                       mode=mode)


def as_sparse(sys_: BlockSystem) -> BlockSystem:
    """Tag a system sparse, deriving each block's column support from its
    nonzero pattern (padded to the widest block with zero-column indices,
    so the compressed operand stays exact)."""
    A = np.asarray(sys_.A_blocks)
    m, _, n = A.shape
    support = (A != 0).any(axis=1)                       # (m, n)
    w = max(int(support.sum(axis=1).max()), 1)
    cols = np.zeros((m, w), np.int32)
    for i in range(m):
        idx = np.flatnonzero(support[i])
        if idx.size < w:
            # pad with an all-zero column: its gathered values are exact
            # zeros, so duplicates contribute nothing to any contraction
            zero_cols = np.flatnonzero(~support[i])
            idx = np.concatenate(
                [idx, np.full(w - idx.size, zero_cols[0], idx.dtype)])
        cols[i] = idx
    return dataclasses.replace(sys_, structure="sparse",
                               cols=jnp.asarray(cols))


def pad_to_blocks(A, b, m: int):
    """Pad (A, b) with duplicated rows so that m | N.

    Duplicating an existing row keeps the solution set unchanged (the system
    stays consistent) while making the even split legal.
    """
    A = np.asarray(A)
    b = np.asarray(b)
    N = A.shape[0]
    rem = (-N) % m
    if rem == 0:
        return jnp.asarray(A), jnp.asarray(b)
    # Duplicate the first `rem` rows (scaled by 1.0; projections are invariant
    # to row duplication within a block only up to Gram conditioning, so spread
    # the duplicates across distinct source rows).
    idx = np.arange(rem) % N
    A2 = np.concatenate([A, A[idx]], axis=0)
    b2 = np.concatenate([b, b[idx]], axis=0)
    return jnp.asarray(A2), jnp.asarray(b2)
