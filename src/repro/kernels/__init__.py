"""Pallas TPU kernels for the paper's per-iteration hot spot.

The fused iteration engine for the projection family (apc / consensus /
cimmino), multi-RHS and mesh-composable:

block_projection.py — pl.pallas_call kernels with explicit BlockSpec VMEM
  tiling: the APC gather/scatter passes and the Cimmino row-projection
  pair, all batch-polymorphic over a leading (k,) RHS axis so one read of
  every A/B tile serves the whole serving batch.
ops.py  — jit'd public wrappers (padding, BN autotune cached per
  (p, n, dtype) and env-overridable, worker vmap, the split gather/psum/
  scatter entry points the mesh backend composes with shard_map).
ref.py  — pure-jnp oracles; every kernel is allclose-validated against
  them across shapes, dtypes and batch sizes in tests/test_kernels.py.

Interpret vs compiled is decided at trace time from the runtime backend
(compiled on real TPU, interpret everywhere else); override with the
``REPRO_PALLAS_INTERPRET=0/1`` env var or an explicit ``interpret=`` kwarg
(see ``block_projection.default_interpret``).  The CI kernel smoke runs
every path under ``=1`` each push; ``tests/test_tpu_compile.py`` compiles
the kernels for a described TPU v5e chip, and ``chip_smoke.py`` runs them
on one.
"""
from . import ops, ref  # noqa: F401
