"""Seeds, the float64 residual and the control's precision, shared by the
configurations' plain references.

Nothing here imports the program: the references and the control must not
take anything that the program has made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def key_from_seed(seed: int) -> jax.Array:
    """A JAX key from any non-negative integer seed (wider than 32 bits
    included): the seed's SeedSequence state, as threefry key data."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent host generator per (seed, stream)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def rel_residuals(matvec64, X, B, chunk: int = 256) -> np.ndarray:
    """‖A x − b‖/‖b‖ per row of X against the rows of B, in float64 numpy.
    ``matvec64`` maps a (k, n) float64 block of rows to the (k, N) rows of
    A xᵀ."""
    X = np.atleast_2d(np.asarray(X))
    B = np.atleast_2d(np.asarray(B))
    out = []
    for i in range(0, X.shape[0], chunk):
        Xi = X[i:i + chunk].astype(np.float64)
        Bi = B[i:i + chunk].astype(np.float64)
        out.append(np.linalg.norm(matvec64(Xi) - Bi, axis=1)
                   / np.linalg.norm(Bi, axis=1))
    return np.concatenate(out) if out else np.zeros(0)


def split(a):
    """a = hi + lo with hi exact in bfloat16: hi keeps the top 16 bits of
    each float32.  Masking bits (not a round trip through bfloat16, which
    XLA may fold away) keeps the split on every backend."""
    bits = lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def dot3(a, b):
    """``a @ b`` in three bfloat16 passes with float32 accumulation: the
    ``high`` precision, one step below the float32 ``highest`` that the
    configurations state.  Written out so that it computes the same on the
    CPU as on the chip.  Either operand may come already ``split``."""
    ah, al = a if isinstance(a, tuple) else split(a)
    bh, bl = b if isinstance(b, tuple) else split(b)

    def d(u, v):
        return jnp.matmul(u, v, preferred_element_type=jnp.float32)

    return d(ah, bh) + (d(ah, bl) + d(al, bh))
