"""HPCG's operator (``GenerateProblem`` of the HPCG reference code): the
27-point stencil on an nx × ny × nz grid, 26 on the diagonal and −1 for
each grid neighbour inside the grid, split into m z-slabs.

``build(cfg, seed)`` makes the system through the program's own generator,
``repro.data.linsys.hpcg_stencil``, in float32; the seed draws the
right-hand sides, b = A v with v ~ N(0, I), made on the device.  The
plain reference beside it is the float64 ``scipy.sparse`` operator built
here from the stencil's definition, A = 27 I − T_z ⊗ T_y ⊗ T_x with T the
tridiagonal all-ones matrix of each axis, apart from the program.  The
control is the same APC with every product in three bfloat16 passes
(``numerics.dot3``), its factors and its γ and η from a float64 spectral
estimate of that operator.  ``iteration_work`` counts one iteration's
work as the algorithm needs it: A by its nonzeros (a float32 value and an
int32 column index each), each slab's support pinv once, the iterates.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sps

from bench import numerics, work
from repro.data.linsys import hpcg_stencil


def stencil_operator(nx: int, ny: int, nz: int) -> sps.csr_matrix:
    """HPCG's matrix in float64, row ``ix + nx·(iy + ny·iz)``: every pair of
    grid points at most one step apart on each axis is a −1, and the
    diagonal is 27 − 1 = 26."""
    def tri(k):
        return sps.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(k, k))
    near = sps.kron(tri(nz), sps.kron(tri(ny), tri(nx)))
    return (27.0 * sps.identity(nx * ny * nz) - near).tocsr()


def slab_widths(nx: int, ny: int, nz: int, m: int) -> list:
    """Column support of each z-slab: its planes and the neighbouring plane
    on each side that lies inside the grid."""
    q = nz // m
    return [(min(nz, (i + 1) * q + 1) - max(0, i * q - 1)) * nx * ny
            for i in range(m)]


@jax.jit
def _rhs(blocks, V):
    A = blocks.reshape(-1, blocks.shape[-1])
    return jnp.matmul(V, A.T, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal(key, blocks, k: int, n: int):
    return _rhs(blocks, jax.random.normal(key, (k, n), jnp.float32))


def _support_factors(A64: sps.csr_matrix, m: int):
    """Per slab, in float64: the support columns, the values V_i on them and
    the support pinv Bᵀ_i = (V_i V_iᵀ)⁻¹ V_i, each padded to the widest slab
    with a zero column (its values and pinv rows are zeros)."""
    p = A64.shape[0] // m
    cols, Vs, Bts = [], [], []
    for i in range(m):
        blk = A64[i * p:(i + 1) * p]
        c = np.unique(blk.indices)
        V = blk[:, c].toarray()
        cols.append(c)
        Vs.append(V)
        Bts.append(np.linalg.solve(V @ V.T, V))
    w = max(len(c) for c in cols)
    pad = lambda a: np.pad(a, ((0, 0), (0, w - a.shape[1])))  # noqa: E731
    C = np.stack([np.pad(c, (0, w - len(c))) for c in cols])
    return C, np.stack([pad(V) for V in Vs]), np.stack([pad(B) for B in Bts])


def _apc_params(V, Bt, C, n: int):
    """γ and η of Theorem 1 from the extreme eigenvalues of
    X = (1/m) Σ_i A_iᵀ G_i⁻¹ A_i, in float64."""
    m = V.shape[0]
    X = np.zeros((n, n))
    for i in range(m):
        np.add.at(X, np.ix_(C[i], C[i]), V[i].T @ Bt[i])
    mu = np.linalg.eigvalsh(X / m)
    kappa = mu[-1] / mu[0]
    rho = (math.sqrt(kappa) - 1.0) / (math.sqrt(kappa) + 1.0)
    s = (1.0 + rho) ** 2 / mu[-1]               # γ η
    q = s + 1.0 - rho ** 2                      # γ + η
    big = (q + math.sqrt(max(q * q - 4.0 * s, 0.0))) / 2.0
    small = s / big
    return (small, big) if small <= 2.0 else (big, small)


@functools.partial(jax.jit, static_argnums=(6,))
def _control_apc(V, Bt, C, b, gamma, eta, iters: int):
    """APC on the z-slabs, every product in three bfloat16 passes: the
    min-norm init x_i = B_i b_i, then x_i += γ(d_i − B_i A_i d_i) with
    d_i = x̄ − x_i and x̄ ← η mean_i x_i + (1 − η) x̄.  b (m, p) -> x̄ (n,)."""
    m, _, w = V.shape
    rows = jnp.arange(m)[:, None]
    n = b.shape[-1] * m
    V3, B3 = numerics.split(V), numerics.split(jnp.swapaxes(Bt, 1, 2))

    def scatter(c):                              # (m, w) -> (m, n)
        return jnp.zeros((m, n), c.dtype).at[rows, C].add(c)

    def prod(M, v):                              # (m, r, s) @ (m, s) -> (m, r)
        return numerics.dot3(M, v[..., None])[..., 0]

    x = scatter(prod(B3, b))
    xbar = jnp.mean(x, axis=0)

    def body(carry, _):
        x, xbar = carry
        d = xbar[None, :] - x
        u = prod(V3, d[rows, C])
        x = x + gamma * d - gamma * scatter(prod(B3, u))
        return (x, eta * jnp.mean(x, axis=0) + (1.0 - eta) * xbar), None

    (_, xbar), _ = jax.lax.scan(body, (x, xbar), None, length=iters)
    return xbar


class HpcgStencil:
    def __init__(self, cfg: dict, seed: int):
        self.nx, self.ny, self.nz = (int(cfg[k]) for k in ("nx", "ny", "nz"))
        self.m, self.iters = int(cfg["m"]), int(cfg["iters"])
        self.system = hpcg_stencil(self.nx, self.ny, self.nz, self.m,
                                   dtype=jnp.float32)
        self.N = self.n = self.nx * self.ny * self.nz
        self.p = self.n // self.m
        self.width = int(self.system.cols.shape[1])
        self.key = numerics.key_from_seed(seed)
        self._control = None

    def to_rhs(self, V) -> np.ndarray:
        """Right-hand sides b = A v for the rows v of V."""
        V = jnp.asarray(V, jnp.float32).reshape(-1, self.n)
        return np.asarray(_rhs(self.system.A_blocks, V))

    def random_rhs(self, k: int, stream: int) -> np.ndarray:
        """k seeded right-hand sides b = A v, v ~ N(0, I), made on the
        device."""
        return np.asarray(_normal(jax.random.fold_in(self.key, stream),
                                  self.system.A_blocks, k, self.n))

    def reference(self):
        """The float64 matvec of the plain reference, built from the
        stencil's definition."""
        A64 = stencil_operator(self.nx, self.ny, self.nz)
        return lambda X: (A64 @ X.T).T

    def control_solve(self, B) -> np.ndarray:
        if self._control is None:
            C, V, Bt = _support_factors(
                stencil_operator(self.nx, self.ny, self.nz), self.m)
            gamma, eta = _apc_params(V, Bt, C, self.n)
            self._control = (jnp.asarray(V, jnp.float32),
                             jnp.asarray(Bt, jnp.float32),
                             jnp.asarray(C, jnp.int32), gamma, eta)
        V, Bt, C, gamma, eta = self._control
        B = np.asarray(B, np.float32).reshape(-1, self.m, self.p)
        return np.stack([np.asarray(_control_apc(V, Bt, C, jnp.asarray(b),
                                                 gamma, eta, self.iters))
                         for b in B])

    def iteration_work(self, k: float) -> work.Work:
        """One APC iteration over k right-hand sides as the algorithm needs
        it: the gather u_i = A_i d_i over A's nonzeros (float32 value and
        int32 column index each), the projection B_i u_i over each slab's
        unpadded support pinv (w_i × p float32), and the iterates x_i and x̄
        read and written; two flops per multiply-add."""
        nnz = (3 * self.nx - 2) * (3 * self.ny - 2) * (3 * self.nz - 2)
        pinv = sum(slab_widths(self.nx, self.ny, self.nz, self.m)) * self.p
        flops = 2.0 * k * (nnz + pinv)
        nbytes = (nnz * (4 + 4) + pinv * 4
                  + 2.0 * k * (self.m + 1) * self.n * 4)
        return work.Work(flops=flops, bytes=nbytes)


def build(cfg: dict, seed: int) -> HpcgStencil:
    return HpcgStencil(cfg, seed)
