"""Mesh-distributed APC — now a thin shim over ``repro.solvers.mesh``.

The general mesh execution backend lives in ``repro.solvers.mesh``: ANY
registered solver runs sharded via ``solvers.get(name).solve(sys,
backend="mesh", mesh=...)``, with the worker blocks on the ``data`` axis
(the Eq. 2b master update is a psum — the taskmaster has no physical node)
and the n dimension optionally cut along ``model``.  See that module for
the data layout and collective structure.

This module keeps the APC-specialized surface the fault-tolerance runtime
and older callers use — ``ShardedAPC`` (a compiled per-iteration step +
residual monitor over raw (A, chol, x, xbar) arrays, e.g. for the elastic
remesh cycle in ``runtime/fault.py``) and the ``solve_on_mesh`` one-call
driver — all delegating to the backend's APC hooks so the iteration math
exists in exactly one place (``solvers/projection.py``).

Imports of ``repro.solvers`` are deferred into the methods: ``repro.core``
loads this module eagerly while the solver registry is itself importing
``repro.core`` building blocks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .partition import BlockSystem


@dataclasses.dataclass(frozen=True)
class ShardedAPC:
    """Compiled distributed APC solver bound to a mesh."""
    mesh: Mesh
    worker_axes: Tuple[str, ...]   # axes the m workers shard over
    model_axis: Optional[str]      # axis the n dimension shards over
    gamma: float
    eta: float

    # ----- backend plumbing ----------------------------------------------
    def _ctx(self):
        from repro.solvers.mesh import MeshContext
        return MeshContext(mesh=self.mesh, worker_axes=self.worker_axes,
                           model_axis=self.model_axis)

    def _solver(self):
        from repro import solvers
        return solvers.get("apc")

    def _params(self):
        return {"gamma": self.gamma, "eta": self.eta}

    # ----- shardings ------------------------------------------------------
    def specs(self):
        wa = self.worker_axes if len(self.worker_axes) > 1 else self.worker_axes[0]
        ma = self.model_axis
        return {
            "A": P(wa, None, ma),
            "b": P(wa, None),
            "chol": P(wa, None, None),
            "x": P(wa, ma),
            "xbar": P(ma),
        }

    # ----- one APC iteration over raw arrays ------------------------------
    def step_fn(self):
        """jit(shard_map) of (A, chol, x, xbar) -> (x, xbar), one Eq. 2a/2b
        iteration — the raw-array surface the elastic runtime drives."""
        from repro.core.apc import APCState
        from repro.solvers.projection import ProjFactors
        ctx, solver, prm = self._ctx(), self._solver(), self._params()

        def body(A, chol, x, xbar):
            st = solver.mesh_step(
                ProjFactors(A=A, chol=chol), None,
                APCState(x=x, xbar=xbar, t=jnp.zeros((), jnp.int32)),
                prm, ctx)
            return st.x, st.xbar

        sp = self.specs()
        return jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(sp["A"], sp["chol"], sp["x"], sp["xbar"]),
            out_specs=(sp["x"], sp["xbar"]),
        ))

    # ----- residual (for convergence monitoring / fault recovery) ---------
    def residual_fn(self):
        from repro.solvers.mesh import residual_shard
        ctx = self._ctx()

        def body(A, b, xbar):
            b_norm = jnp.sqrt(ctx.psum_workers(jnp.sum(b * b)))
            return residual_shard(A, b, xbar, b_norm, ctx)

        sp = self.specs()
        return jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(sp["A"], sp["b"], sp["xbar"]),
            out_specs=P(),
        ))


def make_sharded_apc(mesh: Mesh, *, worker_axes: Sequence[str] = ("data",),
                     model_axis: Optional[str] = "model",
                     gamma: float, eta: float) -> ShardedAPC:
    if model_axis is not None and model_axis not in mesh.axis_names:
        model_axis = None
    worker_axes = tuple(a for a in worker_axes if a in mesh.axis_names)
    return ShardedAPC(mesh=mesh, worker_axes=worker_axes,
                      model_axis=model_axis, gamma=gamma, eta=eta)


# ---------------------------------------------------------------------------
# Host-side driver: place a BlockSystem on the mesh and run APC.
# ---------------------------------------------------------------------------


def prepare_on_mesh(solver: ShardedAPC, sys: BlockSystem):
    """Factorize Gram matrices and build the initial state, all on-mesh.

    The Gram/Cholesky/x0 computation runs as a shard_mapped setup step so no
    single host ever materializes the full A.
    """
    ctx, apc, prm = solver._ctx(), solver._solver(), solver._params()
    sp = solver.specs()
    mesh = solver.mesh

    def setup(A, b):
        factors = apc.mesh_prepare(A, prm, ctx)
        st = apc.mesh_init(factors, b, prm, ctx)
        return factors.chol, st.x, st.xbar

    setup_fn = jax.jit(jax.shard_map(
        setup, mesh=mesh, in_specs=(sp["A"], sp["b"]),
        out_specs=(sp["chol"], sp["x"], sp["xbar"])))

    A = jax.device_put(sys.A_blocks, NamedSharding(mesh, sp["A"]))
    b = jax.device_put(sys.b_blocks, NamedSharding(mesh, sp["b"]))
    chol, x0, xbar0 = setup_fn(A, b)
    return A, b, chol, x0, xbar0


def solve_on_mesh(mesh: Mesh, sys: BlockSystem, *, iters: int = 500,
                  gamma: Optional[float] = None, eta: Optional[float] = None,
                  worker_axes: Sequence[str] = ("data",),
                  model_axis: Optional[str] = "model"):
    """End-to-end distributed APC (legacy surface; returns (xbar, residual)).

    New code should call the backend directly for the full ``SolveResult``:
    ``solvers.get(name).solve(sys, backend="mesh", mesh=mesh)``.
    """
    from repro import solvers
    from repro.solvers.mesh import solve_mesh
    res = solve_mesh(solvers.get("apc"), sys, mesh=mesh, iters=iters,
                     worker_axes=worker_axes, model_axis=model_axis,
                     gamma=gamma, eta=eta)
    return res.x, float(res.residuals[-1])
