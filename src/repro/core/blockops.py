"""Block-matrix operations over dense or block-sparse worker blocks.

Every solver expresses its per-iteration linear algebra through the
small operator set below instead of hard-coding ``jnp.einsum`` on a
dense ``(m, p, n)`` stack.  The dense branches use the *identical*
einsum contractions the solvers always used, so routing a dense system
through these helpers is bit-exact; the sparse branches act on a
:class:`SparseBlocks` operand — a BSR-style per-block column support —
and touch only each block's nonzero columns.

Representation.  Block ``i`` of a sparse system stores its ``w``
supported column indices ``cols[i]`` and the ``(p, w)`` values on that
support.  Blocks with smaller support are padded up to the common ``w``
with indices of all-zero columns, so padded entries carry exact zeros
and every contraction below (including the Gram products) is exact —
no masking needed.  ``cols`` always indexes the GLOBAL ``n`` axis,
which is why the mesh backend shards sparse systems over worker axes
only (see ``solvers/mesh.py``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


class SparseBlocks(NamedTuple):
    """Block-sparse operand: per-block column support + values.

    Attributes:
      vals: (m, p, w) values of each block on its column support.
      cols: (m, w) int32 global column indices; padded slots point at
        all-zero columns so their values are exact zeros.
      span: (n,) zeros — a static-shape carrier for the global column
        count, which no other field records (``cols.max()+1`` would
        under-estimate it and is traced anyway).  Replicated on meshes.
    """

    vals: jnp.ndarray
    cols: jnp.ndarray
    span: jnp.ndarray


def is_sparse(A) -> bool:
    return isinstance(A, SparseBlocks)


def ncols(A) -> int:
    """Global column count ``n`` of either operand kind (trace-static)."""
    if is_sparse(A):
        return A.span.shape[0]
    return A.shape[2]


def bmatvec(A, x):
    """Per-block matvec ``A_i x`` -> (m, p) for a shared ``(n,)`` x."""
    if is_sparse(A):
        return jnp.einsum("mpw,mw->mp", A.vals, x[A.cols])
    return jnp.einsum("mpn,n->mp", A, x)


def bmatvec_each(A, D):
    """Per-block matvec ``A_i d_i`` -> (m, p) for per-block ``(m, n)`` D."""
    if is_sparse(A):
        return jnp.einsum("mpw,mw->mp", A.vals, gather_support(A, D))
    return jnp.einsum("mpn,mn->mp", A, D)


def bmatvec_many(A, X):
    """Batched ``A_i x_k`` -> (k, m, p) for a ``(k, n)`` RHS batch."""
    if is_sparse(A):
        return jnp.einsum("mpw,kmw->kmp", A.vals, X[:, A.cols])
    return jnp.einsum("mpn,kn->kmp", A, X)


def brmatvec(A, u):
    """Per-block transpose matvec ``A_i^T u_i`` -> (m, n)."""
    if is_sparse(A):
        return scatter_support(A, jnp.einsum("mpw,mp->mw", A.vals, u))
    return jnp.einsum("mpn,mp->mn", A, u)


def gather_support(A: SparseBlocks, D):
    """Each block's support entries of per-block vectors: (..., m, n) D ->
    (..., m, w) with ``[..., i, j] = D[..., i, cols[i, j]]``."""
    rows = jnp.arange(A.cols.shape[0])[:, None]
    return D[..., rows, A.cols]


def scatter_support(A: SparseBlocks, C):
    """The inverse placement: (..., m, w) values on each block's support,
    scatter-added into zero (..., m, n) vectors (a padded slot's duplicate
    index adds its value, which the operand keeps at zero)."""
    rows = jnp.arange(A.cols.shape[0])[:, None]
    return jnp.zeros(C.shape[:-1] + (ncols(A),), C.dtype).at[
        ..., rows, A.cols].add(C)


def brmatvec_sum(A, u):
    """Summed transpose matvec ``sum_i A_i^T u_i`` -> (n,)."""
    if is_sparse(A):
        contr = jnp.einsum("mpw,mp->mw", A.vals, u)
        return jnp.zeros((ncols(A),), contr.dtype).at[
            A.cols.reshape(-1)].add(contr.reshape(-1))
    return jnp.einsum("mpn,mp->n", A, u)


def brmatvec_sum_many(A, U):
    """Batched summed transpose matvec -> (k, n) for ``(k, m, p)`` U."""
    if is_sparse(A):
        contr = jnp.einsum("mpw,kmp->kmw", A.vals, U)
        k = U.shape[0]
        return jnp.zeros((k, ncols(A)), contr.dtype).at[
            :, A.cols.reshape(-1)].add(contr.reshape(k, -1))
    return jnp.einsum("mpn,kmp->kn", A, U)


def bgram(A):
    """Per-block Gram ``A_i A_i^T`` -> (m, p, p).

    Exact for sparse operands: padded columns hold zero values, so the
    support contraction equals the full-row contraction.
    """
    if is_sparse(A):
        return jnp.einsum("mpw,mqw->mpq", A.vals, A.vals)
    return jnp.einsum("mpn,mqn->mpq", A, A)


def densify(A):
    """Materialize a ``SparseBlocks`` operand as a dense (m, p, n) stack."""
    if not is_sparse(A):
        return A
    m, p, _ = A.vals.shape
    rows = jnp.arange(m)[:, None]
    # advanced indices (m, w) around the p slice -> update shape (m, w, p)
    return jnp.zeros((m, p, ncols(A)), A.vals.dtype).at[rows, :, A.cols].add(
        A.vals.transpose(0, 2, 1))


def block_shape(A) -> tuple[int, int]:
    """(m, p) of either operand kind."""
    if is_sparse(A):
        return A.vals.shape[0], A.vals.shape[1]
    return A.shape[0], A.shape[1]


def block_dtype(A):
    """Element dtype of either operand kind."""
    return A.vals.dtype if is_sparse(A) else A.dtype
