"""Duration models d(k): FL rounds-to-convergence vs. mean participants.

The paper measures rounds-to-convergence ``d`` for participation probabilities
``p in [0.1, 0.7]`` with N = 50 clients (Table II) and fits a polynomial
regression; ``d(k)`` is then read as a function of the *number of
participating nodes* ``k ~ PoiBin(p)`` via ``k = N p``.

We provide:

* ``PAPER_TABLE_II`` — the paper's measured (p, d_mean, d_std, E_mean, E_std)
  verbatim, used to calibrate the reproduction exactly as the paper does.
* ``fit_polynomial_duration`` — weighted least-squares polynomial fit (numpy,
  on the host).
* ``DurationModel`` — evaluates d(k) on k = 0..N with a guarded k=0 plateau
  (zero participants ⇒ the round contributes nothing: d(0) is set to a finite
  horizon penalty, mirroring the paper's finite simulation horizon).
* ``theoretical_duration`` — an optional analytic surrogate
  d(k) ≈ a + b/k (convergence speedup ~ participant count, diminishing
  returns), used in unit tests and available to the controller when no
  simulation data exists yet.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PAPER_TABLE_II",
    "PAPER_N_CLIENTS",
    "fit_polynomial_duration",
    "DurationModel",
    "paper_duration_model",
    "theoretical_duration",
]

PAPER_N_CLIENTS = 50

# Table II(b): p, mean rounds, std rounds, mean energy (Wh), std energy (Wh).
PAPER_TABLE_II: np.ndarray = np.array([
    [0.100, 74.50, 11.47, 1072.14, 123.43],
    [0.125, 68.00, 13.09, 1005.97, 140.49],
    [0.130, 56.00, 5.29, 862.84, 60.19],
    [0.150, 62.50, 8.81, 950.26, 100.14],
    [0.160, 57.25, 6.13, 887.80, 61.31],
    [0.175, 51.00, 9.42, 797.18, 145.67],
    [0.200, 51.00, 4.55, 816.96, 37.86],
    [0.225, 45.50, 3.70, 747.44, 54.52],
    [0.250, 51.00, 9.56, 803.96, 132.64],
    [0.300, 46.75, 2.75, 768.25, 41.50],
    [0.350, 43.00, 5.23, 724.40, 73.21],
    [0.400, 43.25, 2.22, 734.25, 33.22],
    [0.410, 44.50, 5.32, 758.88, 62.29],
    [0.420, 42.75, 4.11, 725.76, 59.45],
    [0.430, 42.75, 3.30, 734.69, 35.41],
    [0.440, 43.00, 4.08, 732.95, 49.07],
    [0.450, 43.50, 4.43, 751.96, 61.11],
    [0.460, 42.75, 5.56, 750.14, 89.77],
    [0.470, 39.50, 3.11, 698.25, 33.15],
    [0.480, 39.25, 6.70, 696.30, 71.74],
    [0.490, 40.67, 2.89, 709.99, 33.48],
    [0.500, 40.00, 0.82, 704.10, 11.11],
    [0.510, 41.75, 3.30, 719.96, 43.71],
    [0.520, 42.50, 7.33, 729.13, 81.90],
    [0.530, 40.00, 3.16, 703.01, 37.23],
    [0.540, 41.75, 4.27, 726.11, 44.34],
    [0.550, 39.50, 2.65, 706.41, 35.12],
    [0.560, 40.25, 2.99, 719.03, 48.51],
    [0.570, 40.50, 4.43, 712.93, 46.15],
    [0.580, 46.25, 14.15, 771.83, 152.41],
    [0.590, 39.00, 2.58, 694.74, 27.70],
    [0.600, 39.00, 4.24, 691.24, 51.19],
    [0.610, 37.75, 2.87, 682.34, 30.05],
    [0.620, 39.75, 5.56, 708.59, 58.31],
    [0.630, 37.75, 3.50, 697.93, 70.71],
    [0.640, 39.75, 5.91, 726.61, 102.68],
    [0.650, 39.00, 2.16, 702.75, 23.75],
    [0.660, 40.75, 4.99, 719.79, 48.48],
    [0.670, 40.00, 4.69, 725.12, 75.90],
    [0.680, 41.25, 4.03, 728.89, 36.60],
    [0.690, 37.50, 3.87, 676.75, 45.17],
    [0.700, 38.25, 5.50, 696.29, 59.19],
])


def fit_polynomial_duration(
    mean_participants: jax.Array,
    rounds: jax.Array,
    degree: int = 3,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Weighted least-squares polynomial fit ``d(k) ~ sum_j c_j k^j``.

    Mirrors the paper's "polynomial regression model" over Table II(b).
    Returns coefficients ``(degree+1,)`` low-order-first.

    Solved on the host in numpy float64: the inputs are data, not traced
    values, and a TPU solves a float64 least-squares problem to about
    float32 precision (7e-8 relative in the coefficients on a v5e), which
    moved the N=50 surrogate's table by up to 1.5e-3 and the equilibrium
    certificates computed from it by up to 1.4e-7.
    """
    k = np.asarray(mean_participants, np.float64)
    d = np.asarray(rounds, np.float64)
    # Normalize k to [0,1]-ish for conditioning; bake the scale into coeffs
    # evaluation by storing the Vandermonde in normalized space.
    vander = np.stack([k**j for j in range(degree + 1)], axis=1)
    if weights is not None:
        w = np.sqrt(np.asarray(weights, np.float64))
        vander = vander * w[:, None]
        d = d * w
    coeffs, *_ = np.linalg.lstsq(vander, d, rcond=None)
    return jnp.asarray(coeffs)


def _polyval(coeffs: jax.Array, k: jax.Array) -> jax.Array:
    powers = jnp.stack([k**j for j in range(coeffs.shape[0])], axis=-1)
    return powers @ coeffs


@dataclasses.dataclass(frozen=True)
class DurationModel:
    """Evaluates d(k) for k = 0..N participants per round.

    The polynomial is fit on the paper's measured domain p = k/N ∈
    [lo_frac, hi_frac]. Outside it:

    * below ``lo_frac`` the raw polynomial is kept (for the Table II fit it
      rises steeply toward the finite horizon — the p→0 cliff the paper's
      Tragedy of the Commons rests on) but capped at ``d_zero``;
    * above ``hi_frac`` extrapolation is replaced by an increasing quadratic
      continuation ``d(edge) + rise · ((x - hi)/(1 - hi))²`` — full
      participation is penalized ("overfitting or entrapment", §I), matching
      the paper's Fig. 2 utility that falls beyond its peak.

    Attributes:
        coeffs: polynomial coefficients in normalized participants x = k/N.
        n_nodes: N.
        d_zero: k→0 penalty horizon (rounds; the sim never converges at p=0).
        d_floor: minimum achievable rounds (guards downward blips).
        lo_frac / hi_frac: fitted data range in x = k/N.
        rise: rounds added by the time x = 1 relative to the hi edge.
    """

    coeffs: jax.Array
    n_nodes: int
    d_zero: float
    d_floor: float
    lo_frac: float = 0.1
    hi_frac: float = 0.70
    rise: float = 80.0

    def table(self) -> jax.Array:
        """d(k) for k = 0..N, shape (N+1,). Entry 0 is the penalty horizon."""
        k = jnp.arange(self.n_nodes + 1, dtype=jnp.float64)
        return self.eval_continuous(k)

    def eval_continuous(self, k: jax.Array) -> jax.Array:
        """Evaluate d at (possibly fractional) participant count k >= 0."""
        kf = jnp.asarray(k, jnp.float64)
        x = kf / self.n_nodes
        poly = _polyval(self.coeffs, jnp.clip(x, 0.0, self.hi_frac))
        d_edge = _polyval(self.coeffs, jnp.asarray(self.hi_frac))
        above = d_edge + self.rise * ((x - self.hi_frac)
                                      / (1.0 - self.hi_frac)) ** 2
        d = jnp.where(x > self.hi_frac, above, poly)
        d = jnp.clip(d, self.d_floor, self.d_zero)
        # At k = 0 the task never converges: charge the full horizon.
        return jnp.where(kf <= 0.0, self.d_zero, d)


def paper_duration_model(degree: int = 9, horizon: float = 500.0,
                         rise: float = 80.0) -> DurationModel:
    """Duration model calibrated on the paper's Table II(b), N = 50.

    Degree 9 (inverse-variance weighted) reproduces the multi-minimum
    structure the paper's results imply: a local minimum near p ≈ 0.28
    (d ≈ 45.6 — the paper's no-incentive NE basin at p ≈ 0.24) and the
    global minimum near p ≈ 0.62 (d ≈ 38.4 — the paper's centralized
    optimum p ≈ 0.61). ``horizon`` is the k→0 penalty; 500 rounds ≫ any
    measured d preserves the collapse cliff while keeping the utility finite.
    """
    tab = PAPER_TABLE_II
    x = jnp.asarray(tab[:, 0], jnp.float64)  # p = k/N (table is indexed by p)
    d = jnp.asarray(tab[:, 1], jnp.float64)
    w = 1.0 / jnp.clip(jnp.asarray(tab[:, 2], jnp.float64), 0.5, None) ** 2
    coeffs = fit_polynomial_duration(x, d, degree=degree, weights=w)
    d_floor = float(tab[:, 1].min() * 0.9)
    return DurationModel(coeffs=coeffs, n_nodes=PAPER_N_CLIENTS,
                         d_zero=horizon, d_floor=d_floor,
                         lo_frac=float(tab[:, 0].min()),
                         hi_frac=float(tab[:, 0].max()), rise=rise)


def theoretical_duration(
    n_nodes: int,
    d_inf: float = 35.0,
    slope: float = 4.0,
    horizon: float = 500.0,
) -> DurationModel:
    """Analytic surrogate d(k) = d_inf + slope * N / k.

    Encodes diminishing returns of extra participants; exposed as a
    DurationModel by fitting the polynomial to the curve so both paths share
    one code path downstream.
    """
    k = np.arange(1, n_nodes + 1, dtype=np.float64)
    d = d_inf + slope * n_nodes / k
    coeffs = fit_polynomial_duration(
        jnp.asarray(k / n_nodes), jnp.asarray(np.minimum(d, horizon)), degree=6)
    return DurationModel(coeffs=coeffs, n_nodes=n_nodes, d_zero=horizon,
                         d_floor=float(d.min()))
