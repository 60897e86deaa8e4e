"""Pallas TPU kernel: symmetric block-RLS projector downdate.

The OWM/block-RLS recursion (core/projections.py) is

    Q ← Q − U A Uᵀ,   U = Q X_bᵀ (d×b),   A = (αI_b + X_b Q X_bᵀ)⁻¹

The b×b inverse is trivial; the d×d downdate is the hot spot (d up to
16384 for the assigned archs ⇒ 256M-element update per block of
features).  This kernel fuses the rank-b symmetric downdate
``Q − U A Uᵀ`` over 128-aligned VMEM tiles: per output tile (i, j) it
keeps U_i (bo×b) and U_j·Aᵀ? — rather, computes U_i A U_jᵀ with A
staged in VMEM once, avoiding the d×b intermediate round-trip to HBM
that the naive three-GEMM chain costs.

GPU→TPU note (DESIGN.md §6): the original OWM uses n rank-1 updates
(vector ops, latency-bound on GPU warps); the block form converts the
recursion into MXU-shaped GEMM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.env import resolve


def _kernel(q_ref, u_ref, a_ref, uj_ref, out_ref):
    u_i = u_ref[...].astype(jnp.float32)          # (bo, b)
    a = a_ref[...].astype(jnp.float32)            # (b, b)
    u_j = uj_ref[...].astype(jnp.float32)         # (bj, b)
    upd = jax.lax.dot(jax.lax.dot(u_i, a,
                                  preferred_element_type=jnp.float32),
                      u_j.T, preferred_element_type=jnp.float32)
    out_ref[...] = (q_ref[...].astype(jnp.float32) - upd
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bo", "bj", "interpret"))
def rank_downdate(Q, U, A, *, bo: int = 256, bj: int = 256,
                  interpret: bool | None = None):
    """Q − U A Uᵀ.  Q: (d, d); U: (d, b); A: (b, b) small."""
    d, b = U.shape
    bo = min(bo, d)
    bj = min(bj, d)
    assert d % bo == 0 and d % bj == 0
    grid = (d // bo, d // bj)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bo, bj), lambda i, j: (i, j)),   # Q tile
            pl.BlockSpec((bo, b), lambda i, j: (i, 0)),    # U_i rows
            pl.BlockSpec((b, b), lambda i, j: (0, 0)),     # A (staged)
            pl.BlockSpec((bj, b), lambda i, j: (j, 0)),    # U_j rows
        ],
        out_specs=pl.BlockSpec((bo, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, d), Q.dtype),
        interpret=resolve(interpret),
    )(Q, U, A, U)


def block_rls_update(Q, Xb, alpha: float = 1.0, *,
                     interpret: bool | None = None, bo: int = 256):
    """One full block-RLS step using the fused downdate kernel.

    Equivalent to ``repro.core.projections.block_update`` (the oracle).
    """
    QX = Q @ Xb.T                                  # (d, b) — plain GEMM
    S = alpha * jnp.eye(Xb.shape[0], dtype=Q.dtype) + Xb @ QX
    A = jnp.linalg.inv(S)
    A = 0.5 * (A + A.T)
    return rank_downdate(Q, QX, A, bo=bo, bj=bo, interpret=interpret)
