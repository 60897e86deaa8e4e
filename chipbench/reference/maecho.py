"""Plain MA-Echo (Algorithm 1 of arXiv:2204.12493), leaf by leaf.

For every weight leaf, and every layer of a stacked leaf, independently:

    W = mean_i V_i
    repeat tau times:
        R_i = P_i (W - V_i)                          (x @ W layout)
        a   = argmin 1/2 a^T G a,  G_ij = <R_i, R_j>,
              on {sum a = 1, 0 <= a <= C}            (Eq. 6)
        W  += eta * (-2 sum_i a_i R_i)                (Eq. 7)
        V_i += (W - V_i) - mu/(1+mu) P_i (W - V_i)    (Eq. 11)

P_i is a full (in, in) matrix, a diagonal over the input axis, or a
scalar.  The QP is solved as the configuration states it: ``qp_iters``
steps of accelerated projected gradient with step 1/max-row-sum |G|,
each projection onto the capped simplex by 60 bisection steps.

Imports nothing of the program.  Every contraction runs at ``HIGHEST``
precision on float32 operands.  ``lowp`` is the control's knob: with
"bf16" the operands of every contraction are rounded to bfloat16 first
(what one default-precision pass of a TPU's matrix unit does to
float32), with "fp8" to float8 e4m3.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

BISECTION_STEPS = 60
HI = jax.lax.Precision.HIGHEST


def project_capped_simplex(x, C):
    lo = jnp.min(x) - C - 1.0
    hi = jnp.max(x)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        over = jnp.sum(jnp.clip(x - mid, 0.0, C)) > 1.0
        return jnp.where(over, mid, lo), jnp.where(over, hi, mid)

    lo, hi = jax.lax.fori_loop(0, BISECTION_STEPS, body, (lo, hi))
    return jnp.clip(x - 0.5 * (lo + hi), 0.0, C)


def solve_qp(G, C, iters):
    n = G.shape[0]
    step = 1.0 / jnp.maximum(jnp.max(jnp.sum(jnp.abs(G), axis=1)), 1e-12)
    a0 = project_capped_simplex(jnp.full((n,), 1.0 / n, jnp.float32), C)

    def body(_, s):
        a, y, t = s
        a_new = project_capped_simplex(y - step * (G @ y), C)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        return a_new, a_new + ((t - 1.0) / t_new) * (a_new - a), t_new

    a, _, _ = jax.lax.fori_loop(0, iters, body, (a0, a0, jnp.float32(1.0)))
    return a


LOWP = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def _mm(a, b, lowp, dims=None):
    if lowp:
        t = LOWP[lowp]
        a, b = a.astype(t).astype(jnp.float32), b.astype(t).astype(jnp.float32)
    if dims is not None:
        return jnp.tensordot(a, b, axes=dims, precision=HI,
                             preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def apply_p(delta, p, lowp):
    """P (W - V) for one client and one layer, ``delta`` (in, out) or
    (in,)."""
    if p.ndim == 0:
        return delta * p
    if p.ndim == 1:
        return delta * (p[:, None] if delta.ndim == 2 else p)
    return _mm(p, delta, lowp)


def one_layer(V, P, m: dict, lowp):
    """Algorithm 1 on one layer: V (N, ...) client weights, P (N, ...)
    their projectors.  Returns the merged weights."""
    N = V.shape[0]
    frac = m["mu"] / (1.0 + m["mu"])
    W = jnp.sum(V, axis=0) * (1.0 / N)

    def resid(W, V):
        return jax.vmap(lambda v, p: apply_p(W - v, p, lowp))(V, P)

    def outer(_, s):
        W, V = s
        R = resid(W, V)
        Rf = R.reshape(N, -1)
        G = _mm(Rf, Rf.T, lowp)
        a = solve_qp(G, m["C"], m["qp_iters"])
        W = W + m["eta"] * (-2.0 * _mm(a, R, lowp, dims=(0, 0)))
        V = V + (W[None] - V) - frac * resid(W, V)
        return W, V

    W, _ = jax.lax.fori_loop(0, m["tau"], outer, (W, V))
    return W


@partial(jax.jit, static_argnames=("levels", "tau", "qp_iters", "lowp"))
def _leaf(V, P, eta, mu, C, *, levels, tau, qp_iters, lowp):
    m = dict(eta=eta, mu=mu, C=C, tau=tau, qp_iters=qp_iters)
    if levels == 0:
        return one_layer(V, P, m, lowp)
    return jax.vmap(lambda v, p: one_layer(v, p, m, lowp),
                    in_axes=(1, 1))(V, P)


def aggregate_leaf(V, P, levels: int, maecho: dict, lowp: str = ""):
    """Merged weights of one leaf.  ``V`` stacks the clients on axis 0,
    ``P`` likewise (a scalar or diagonal projector broadcasts to it);
    ``levels`` is 1 for a leaf with a leading layer axis."""
    return _leaf(V.astype(jnp.float32), P.astype(jnp.float32),
                 jnp.float32(maecho["eta"]), jnp.float32(maecho["mu"]),
                 jnp.float32(maecho["C"]), levels=levels,
                 tau=int(maecho["tau"]), qp_iters=int(maecho["qp_iters"]),
                 lowp=lowp)
