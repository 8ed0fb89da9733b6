"""Pure-jnp oracles for the Pallas kernels (the correctness reference).

The projection-family worker updates, given the precomputed pseudoinverse
factor B_i = A_i^T (A_i A_i^T)^{-1}  (n x p):

APC / consensus (gather + scatter):

    d = xbar - x
    u = A d                  (p,)    gather pass
    y = x + gamma * (d - B u)        scatter pass

Block Cimmino (row projection):

    u = A xbar               (p,)    gather pass
    r = B (b - u)            (n,)    scatter pass

Every oracle is batch-polymorphic exactly like the kernels: row-vector
operands may carry a leading (k,) RHS axis (einsum '...' broadcasting), so
one reference covers the single-RHS and the multi-RHS kernel paths.

The dense oracles evaluate in numpy when every operand is a numpy array,
so a float64 host reference stays float64 whatever JAX's x64 setting.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _einsum(spec, *ops):
    xp = np if all(isinstance(o, np.ndarray) for o in ops) else jnp
    return xp.einsum(spec, *ops)


def apc_gather_ref(A, x, xbar):
    """u = A (xbar - x).   A (p, n); x, xbar (n,) or (k, n)."""
    return _einsum("pn,...n->...p", A, xbar - x)


def apc_scatter_ref(B, x, xbar, u, gamma):
    """y = x + gamma * ((xbar - x) - B u).   B (n, p); u (p,) or (k, p)."""
    d = xbar - x
    return x + gamma * (d - _einsum("np,...p->...n", B, u))


def block_projection_ref(A, B, x, xbar, gamma):
    """Full fused worker update: y = x + gamma * P (xbar - x) with
    P = I - B A (note B A == A^T G^{-1} A)."""
    u = apc_gather_ref(A, x, xbar)
    return apc_scatter_ref(B, x, xbar, u, gamma)


def cimmino_gather_ref(A, xbar):
    """u = A xbar.   A (p, n); xbar (n,) or (k, n)."""
    return _einsum("pn,...n->...p", A, xbar)


def cimmino_scatter_ref(B, v):
    """r = B v.   B (n, p); v (p,) or (k, p)."""
    return _einsum("np,...p->...n", B, v)


def cimmino_update_ref(A, B, b, xbar):
    """Full fused row projection: r = B (b - A xbar)."""
    return cimmino_scatter_ref(B, b - cimmino_gather_ref(A, xbar))


def sparse_proj_update_ref(vals, cols, bvals, x, xbar, gamma):
    """Sparse fused APC update on the compressed support (the oracle for
    ``ops.sparse_proj_update``): vals (p, w) on global columns cols (w,);
    bvals (w, p) = B_i compressed to the support.  Returns (y, u)."""
    d = xbar - x
    u = jnp.einsum("pw,...w->...p", vals, d[..., cols])
    c = jnp.einsum("wp,...p->...w", bvals, u)
    y = x + gamma * d
    return y.at[..., cols].add(-gamma * c), u


def sparse_cimmino_update_ref(vals, cols, bvals, b, xbar):
    """Sparse fused Cimmino row projection (the oracle for
    ``ops.sparse_cimmino_update``).  Returns (r, u)."""
    u = jnp.einsum("pw,...w->...p", vals, xbar[..., cols])
    c = jnp.einsum("wp,...p->...w", bvals, b - u)
    r = jnp.zeros_like(xbar).at[..., cols].add(c)
    return r, u
