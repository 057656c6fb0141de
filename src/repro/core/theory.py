"""Theorem 2 / Corollary 3 of the QSDP paper, executable.

The paper's analytical core is the iteration

    x_{t+1} = Q^w_delta( x_t - (eta / beta) * Q^g( g(x_t) ) )

for a beta-smooth, alpha-PL objective f, with Q^w the random-shift lattice
quantizer (Definition 1) and Q^g any unbiased gradient quantizer.  Theorem 2
fixes  delta = eta * delta_star / ceil(16 (beta/alpha)^2)  and proves linear
convergence (rate 1 - (3/4) eta alpha/beta per step, Lemma 10) to within
epsilon of the best point on the *coarser* lattice delta_star Z^n + r 1.

This module provides:
  * quadratic PL test objectives with known (alpha, beta) and known lattice
    optima, plus noisy-gradient oracles;
  * `theorem2_params` computing (eta, delta, T) exactly as in the theorem;
  * `run_qsgd` executing the iteration with selectable weight/gradient
    quantizers — used by tests and the theory benchmark to check both the
    convergence claim and its *failure* under naive round-to-nearest.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .quant import q_coinflip, q_nearest, q_shift


@dataclasses.dataclass(frozen=True)
class Quadratic:
    """f(x) = 0.5 * sum_i h_i (x_i - c_i)^2  — beta = max h, alpha = min h.

    Strongly convex, hence alpha-PL; the minimizer over a shifted lattice is
    the coordinate-wise rounding of c, which makes the benchmark
    E f(x*_{r,delta_star}) computable in closed form.
    """

    h: jax.Array  # (n,) positive curvatures
    c: jax.Array  # (n,) optimum

    @property
    def alpha(self) -> float:
        return float(jnp.min(self.h))

    @property
    def beta(self) -> float:
        return float(jnp.max(self.h))

    def f(self, x: jax.Array) -> jax.Array:
        return 0.5 * jnp.sum(self.h * (x - self.c) ** 2)

    def grad(self, x: jax.Array) -> jax.Array:
        return self.h * (x - self.c)

    def noisy_grad(self, x: jax.Array, key: jax.Array, sigma: float) -> jax.Array:
        """Unbiased gradient oracle with E||g - grad||^2 = sigma^2."""
        n = x.shape[0]
        noise = jax.random.normal(key, (n,)) * (sigma / math.sqrt(n))
        return self.grad(x) + noise

    def lattice_opt_value(self, delta_star: float, key: jax.Array, n_shifts: int = 256) -> float:
        """Monte-Carlo estimate of E_r f(x*_{r,delta_star}): for a separable
        quadratic the best lattice point is round-to-nearest of c on each
        shifted grid."""
        rs = jax.random.uniform(key, (n_shifts,), minval=-0.5, maxval=0.5) * delta_star

        def one(r):
            xs = delta_star * jnp.round((self.c - r) / delta_star) + r
            return self.f(xs)

        return float(jnp.mean(jax.vmap(one)(rs)))


def make_quadratic(key: jax.Array, n: int = 64, kappa: float = 4.0,
                   c_scale: float = 8.0) -> Quadratic:
    """Random separable quadratic with condition number `kappa`.

    `c_scale` sets ||x0 - c|| relative to the coarse-lattice benchmark
    E f(x*_{r,delta_star}) (which depends only on h and delta_star, not c):
    starting from x0 = 0, the initial gap is ~c_scale^2 larger than the
    benchmark floor, so the linear transient of Theorem 2 spans enough
    iterations to *measure* the contraction rate before f(x_t) crosses the
    floor (with c_scale=1 the gap goes negative after ~2 steps and a rate
    fit is ill-posed)."""
    k1, k2 = jax.random.split(key)
    h = jnp.exp(jax.random.uniform(k1, (n,)) * math.log(kappa))  # in [1, kappa]
    c = jax.random.normal(k2, (n,)) * c_scale
    return Quadratic(h=h, c=c)


@dataclasses.dataclass(frozen=True)
class Theorem2Params:
    eta: float
    delta: float
    T: int
    lr: float  # eta / beta — the actual step size


def theorem2_params(
    alpha: float,
    beta: float,
    delta_star: float,
    eps: float,
    sigma: float,
    f0_gap: float,
    sigma_q: float = 0.0,
) -> Theorem2Params:
    """Exactly the parameter choices of Theorem 2 / Corollary 3."""
    var = sigma**2 + sigma_q**2
    eta = 1.0 if var == 0 else min(0.3 * eps * alpha / var, 1.0)
    delta = eta * delta_star / math.ceil(16.0 * (beta / alpha) ** 2)
    T = math.ceil(10.0 / eta * (beta / alpha) * math.log(max(f0_gap / eps, math.e)))
    return Theorem2Params(eta=eta, delta=delta, T=T, lr=eta / beta)


WeightQ = str  # "shift" | "nearest" | "coinflip" | "none"


def run_qsgd(
    obj: Quadratic,
    x0: jax.Array,
    params: Theorem2Params,
    key: jax.Array,
    sigma: float = 0.0,
    weight_q: WeightQ = "shift",
    grad_q_delta: Optional[float] = None,
    record_every: int = 1,
    x64: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Run the Theorem-2 iteration; returns (x_T, f-trajectory).

    By default the iteration runs in float64 (`jax.enable_x64(True)`
    scoped to this call): the f(x_t) - f* gaps the rate tests fit span many
    orders of magnitude and bottom out at the f32 resolution of f after a
    handful of steps, which poisons any contraction-rate estimate."""
    if x64:
        with jax.enable_x64(True):
            return _run_qsgd_impl(obj, x0.astype(jnp.float64), params, key,
                                  sigma, weight_q, grad_q_delta, record_every)
    return _run_qsgd_impl(obj, x0, params, key, sigma, weight_q, grad_q_delta,
                          record_every)


def _run_qsgd_impl(obj, x0, params, key, sigma, weight_q, grad_q_delta,
                   record_every):

    def qw(x, k):
        if weight_q == "shift":
            return q_shift(x, params.delta, k)
        if weight_q == "nearest":
            return q_nearest(x, params.delta)
        if weight_q == "coinflip":
            return q_coinflip(x, params.delta, k)
        if weight_q == "none":
            return x
        raise ValueError(weight_q)

    def step(carry, _):
        x, k = carry
        k, kg, kq, kgq = jax.random.split(k, 4)
        g = obj.noisy_grad(x, kg, sigma) if sigma > 0 else obj.grad(x)
        if grad_q_delta is not None:  # Corollary 3: unbiased gradient quantizer
            g = q_coinflip(g, grad_q_delta, kgq)
        x = qw(x - params.lr * g, kq)
        return (x, k), obj.f(x)

    (xT, _), fs = jax.lax.scan(step, (x0, key), None, length=params.T)
    return xT, fs[::record_every]
