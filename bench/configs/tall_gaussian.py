"""The standard tall Gaussian ensemble (arXiv:1708.01413, §6, Table 2):
A with i.i.d. N(0, 1) entries, N > n, and consistent right-hand sides
b = A v, v ~ N(0, I).

``build(cfg, seed)`` makes A on the device in one jitted call from the
seed, in float32, as the (m, p, n) row-block stack the program serves.
The plain reference beside it is the float64 numpy residual against the
same A; the control is the least-squares solution of the normal
equations with every product in three bfloat16 passes (``dot3``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import numerics
from repro.core.partition import BlockSystem


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _blocks(key, m: int, p: int, n: int):
    return jax.random.normal(key, (m, p, n), jnp.float32)


@jax.jit
def _rhs(blocks, V):
    A = blocks.reshape(-1, blocks.shape[-1])
    return jnp.matmul(V, A.T, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal(key, blocks, k: int, n: int):
    return _rhs(blocks, jax.random.normal(key, (k, n), jnp.float32))


@jax.jit
def _control(blocks, B):
    A = blocks.reshape(-1, blocks.shape[-1])
    G = numerics.dot3(A.T, A)
    c = numerics.dot3(B, A)
    L = jnp.linalg.cholesky(G)
    return jax.scipy.linalg.cho_solve((L, True), c.T).T


class TallGaussian:
    def __init__(self, cfg: dict, seed: int):
        N, n, m = int(cfg["N"]), int(cfg["n"]), int(cfg["m"])
        if N % m:
            raise ValueError(f"m={m} must divide N={N}")
        self.N, self.n, self.m, self.p = N, n, m, N // m
        self.width = n
        self.key = numerics.key_from_seed(seed)
        blocks = _blocks(jax.random.fold_in(self.key, 0), m, self.p, n)
        self.system = BlockSystem(blocks, jnp.zeros((m, self.p), jnp.float32),
                                  mode="square")

    def to_rhs(self, V) -> np.ndarray:
        """Consistent right-hand sides b = A v for the rows v of V."""
        V = jnp.asarray(V, jnp.float32).reshape(-1, self.n)
        return np.asarray(_rhs(self.system.A_blocks, V))

    def random_rhs(self, k: int, stream: int) -> np.ndarray:
        """k seeded consistent right-hand sides, made on the device."""
        return np.asarray(_normal(jax.random.fold_in(self.key, stream),
                                  self.system.A_blocks, k, self.n))

    def reference(self):
        """The float64 matvec of the plain reference (A fetched once)."""
        A64 = np.asarray(jax.device_get(self.system.A_blocks),
                         np.float64).reshape(self.N, self.n)
        return lambda X: X @ A64.T

    def control_solve(self, B) -> np.ndarray:
        return np.asarray(_control(self.system.A_blocks,
                                   jnp.asarray(B, jnp.float32)))


def build(cfg: dict, seed: int) -> TallGaussian:
    return TallGaussian(cfg, seed)
