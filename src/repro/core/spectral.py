"""Spectral analysis and optimal hyper-parameters for APC and all baselines.

Everything in this module is *analysis-time* (taskmaster-side, done once):
forming X = (1/m) sum_i A_i^T (A_i A_i^T)^{-1} A_i, extracting mu_min/mu_max,
and solving the optimality conditions of Theorem 1 for (gamma*, eta*).

The iteration-time code never calls into here; production users may also pass
hand-tuned (gamma, eta).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.runtime.spans import span

from .partition import BlockSystem

# ---------------------------------------------------------------------------
# The X matrix and its spectrum (paper Eq. (3)-(4))
# ---------------------------------------------------------------------------


def x_matrix(sys: BlockSystem) -> np.ndarray:
    """X = (1/m) sum_i A_i^T (A_i A_i^T)^{-1} A_i   (n x n, symmetric PSD)."""
    with span("repro.spectral.x_matrix"):
        A = np.asarray(sys.A_blocks, dtype=np.float64)
        m, p, n = A.shape
        X = np.zeros((n, n), dtype=np.float64)
        for i in range(m):
            Ai = A[i]
            G = Ai @ Ai.T                      # (p, p) Gram
            X += Ai.T @ np.linalg.solve(G, Ai)
        return X / m


def mu_extremes(X: np.ndarray) -> tuple[float, float]:
    """(mu_min, mu_max) of X. Eigenvalues lie in [0, 1] (sum of projections)."""
    with span("repro.spectral.eig"):
        w = np.linalg.eigvalsh(X)
    return float(w[0]), float(w[-1])


def kappa(X: np.ndarray) -> float:
    mu_min, mu_max = mu_extremes(X)
    return mu_max / mu_min


def ata_extremes(sys: BlockSystem) -> tuple[float, float]:
    """(lambda_min, lambda_max) of A^T A — drives the gradient-family rates."""
    A, _ = sys.dense()
    A = np.asarray(A, dtype=np.float64)
    w = np.linalg.eigvalsh(A.T @ A)
    return float(w[0]), float(w[-1])


# ---------------------------------------------------------------------------
# Optimal parameters (Theorem 1 and Section 4 closed forms)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class APCParams:
    gamma: float
    eta: float
    rho: float  # optimal spectral radius (convergence rate)


def apc_optimal(mu_min: float, mu_max: float) -> APCParams:
    """Solve Theorem 1's optimality system.

      mu_max * eta * gamma = (1 + rho)^2
      mu_min * eta * gamma = (1 - rho)^2,   rho = sqrt((gamma-1)(eta-1))

    Dividing gives rho = (sqrt(kappa)-1)/(sqrt(kappa)+1).  Then with
    s = eta*gamma = (1+rho)^2/mu_max and (gamma-1)(eta-1) = rho^2 we get
    gamma + eta = s + 1 - rho^2, so gamma, eta are the two roots of
    z^2 - (s + 1 - rho^2) z + s = 0.  The discriminant is >= 0 whenever
    mu_max <= 1, which always holds (X is an average of projections).
    """
    if mu_min <= 0:
        raise ValueError("mu_min must be > 0 (system must be solvable)")
    k = mu_max / mu_min
    rho = (math.sqrt(k) - 1.0) / (math.sqrt(k) + 1.0)
    s = (1.0 + rho) ** 2 / mu_max           # eta * gamma
    q = s + 1.0 - rho ** 2                  # eta + gamma
    disc = q * q - 4.0 * s
    disc = max(disc, 0.0)                   # numeric guard (disc==0 @ mu_max=1)
    r = math.sqrt(disc)
    z2 = (q + r) / 2.0                      # large root: no cancellation
    z1 = s / z2 if z2 > 0 else 0.0          # small root via product z1*z2 = s
    #  ((q - r)/2 cancels catastrophically when s >> 1, i.e. tiny mu_max)
    # gamma must lie in [0, 2] (set S definition); the smaller root does.
    gamma, eta = (z1, z2) if z1 <= 2.0 else (z2, z1)
    return APCParams(gamma=gamma, eta=eta, rho=rho)


def apc_rate(mu_min: float, mu_max: float) -> float:
    return apc_optimal(mu_min, mu_max).rho


def dgd_optimal(lmin: float, lmax: float) -> tuple[float, float]:
    """(alpha*, rho*) for distributed gradient descent on ||Ax-b||^2.

    Gradient iteration matrix I - alpha A^T A; optimal alpha = 2/(lmin+lmax),
    rho = (kappa-1)/(kappa+1).
    """
    alpha = 2.0 / (lmin + lmax)
    rho = (lmax - lmin) / (lmax + lmin)
    return alpha, rho


def dnag_optimal(lmin: float, lmax: float) -> tuple[float, float, float]:
    """(alpha*, beta*, rho*) for Nesterov on a quadratic (Lessard et al. [9]).

    alpha = 4/(3 lmax + lmin), beta = (sqrt(3 kappa + 1) - 2)/(sqrt(3 kappa+1)+2),
    rho = 1 - 2/sqrt(3 kappa + 1).
    """
    k = lmax / lmin
    alpha = 4.0 / (3.0 * lmax + lmin)
    s = math.sqrt(3.0 * k + 1.0)
    beta = (s - 2.0) / (s + 2.0)
    rho = 1.0 - 2.0 / s
    return alpha, beta, rho


def dhbm_optimal(lmin: float, lmax: float) -> tuple[float, float, float]:
    """(alpha*, beta*, rho*) for heavy-ball on a quadratic (Polyak [16]).

    alpha = (2/(sqrt(lmax)+sqrt(lmin)))^2, beta = rho^2,
    rho = (sqrt(kappa)-1)/(sqrt(kappa)+1).
    """
    sl, sm = math.sqrt(lmax), math.sqrt(lmin)
    alpha = (2.0 / (sl + sm)) ** 2
    rho = (sl - sm) / (sl + sm)
    beta = rho ** 2
    return alpha, beta, rho


def cimmino_optimal(mu_min: float, mu_max: float) -> tuple[float, float]:
    """(nu*, rho*) for the block Cimmino method.

    Error iteration: e(t+1) = (I - nu m X) e(t); optimal nu = 2/(m(mu_min+mu_max))
    gives rho = (kappa-1)/(kappa+1).  We return nu*m (caller divides by m).
    """
    nu_m = 2.0 / (mu_min + mu_max)
    rho = (mu_max - mu_min) / (mu_max + mu_min)
    return nu_m, rho


def consensus_rate(mu_min: float) -> float:
    """Plain projection-consensus [11,14]: rho = 1 - mu_min(X)."""
    return 1.0 - mu_min


def convergence_time(rho: float) -> float:
    """T = 1 / (-log rho)   (paper Section 5; ~ 1/(1-rho))."""
    if rho >= 1.0:
        return float("inf")
    if rho <= 0.0:
        return 0.0
    return 1.0 / (-math.log(rho))


# ---------------------------------------------------------------------------
# One-call summary used by benchmarks (Table 1 / Table 2 reproduction)
# ---------------------------------------------------------------------------


def rates_summary(sys: BlockSystem) -> dict[str, float]:
    """Optimal convergence rates of every method in the paper for `sys`."""
    X = x_matrix(sys)
    mu_min, mu_max = mu_extremes(X)
    lmin, lmax = ata_extremes(sys)
    _, rho_dgd = dgd_optimal(lmin, lmax)
    _, _, rho_nag = dnag_optimal(lmin, lmax)
    _, _, rho_hbm = dhbm_optimal(lmin, lmax)
    _, rho_cim = cimmino_optimal(mu_min, mu_max)
    apc = apc_optimal(mu_min, mu_max)
    return {
        "mu_min": mu_min,
        "mu_max": mu_max,
        "kappa_X": mu_max / mu_min,
        "kappa_AtA": lmax / lmin,
        "DGD": rho_dgd,
        "D-NAG": rho_nag,
        "D-HBM": rho_hbm,
        "Consensus": consensus_rate(mu_min),
        "B-Cimmino": rho_cim,
        "APC": apc.rho,
    }
