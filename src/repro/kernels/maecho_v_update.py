"""Pallas TPU kernel: fused MA-Echo anchor update (Eq. 11).

Computes, for every client i,

    Vᵢ' = Vᵢ + Norm( Δᵢ − μ/(1+μ) · Δᵢ Pᵢ ),   Δᵢ = W' − Vᵢ

i.e. the residual re-projected through (I − μ/(1+μ)Pᵢ), with the
optional row-normalisation.  The reference path materializes the
(N, out, in) Δᵢ Pᵢ product in HBM; here each output tile keeps the
whole chain in VMEM: Δ tiles are formed in-register from W'/Vᵢ blocks,
the Δᵢ Pᵢ contraction accumulates in a (bo, bi) fp32 scratch across
the k-grid axis, and the finalize step fuses the subtraction, optional
row-norm and the += into a single store of Vᵢ'.

Grid: (N, n_out, n_in, n_k); scratch persists across the innermost
axis only (one tile's reduction).  With ``norm=True`` the row norm
needs the full row resident, so callers must set bi = in_d (the auto
wrapper in ``ops`` does; rows up to ~16k fp32 fit VMEM comfortably).

Fast paths mirror ``maecho_gram``:
  - ``maecho_v_update_factored``: Δᵢ Pᵢ = Bᵢ @ Uᵢᵀ with the compressed
    Bᵢ = ((W' − Vᵢ)Uᵢ)·diag(sᵢ) formed without the full residual —
    reduction runs over the rank k instead of in;
  - ``maecho_v_update_diag``: elementwise Δᵢ·(1 − μ/(1+μ)·pᵢ), one
    pass, no reduction axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.env import resolve


def _apply_norm(u, eps: float):
    """Row-normalise u (bo, full-row) exactly like the jnp oracle."""
    nrm = jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True))
    return u / jnp.maximum(nrm, eps)


def _v_tail(contrib, wj_ref, vj_ref, out_ref, acc_ref,
            *, frac: float, norm: bool, eps: float, n_k: int,
            off: int = 0):
    """Accumulate one k-block of Δᵢ Pᵢ, then fuse Eq. 11 at the end.

    ``off`` is the grid offset of the (client, out, in, k) axes — 1
    when the stacked-layer axis rides in front."""
    k = pl.program_id(off + 3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contrib

    @pl.when(k == n_k - 1)
    def _finalize():
        dj = (wj_ref[...] - vj_ref[...]).astype(jnp.float32)  # (bo, bi)
        u = dj - frac * acc_ref[...]
        if norm:
            u = _apply_norm(u, eps)
        out_ref[...] = (vj_ref[...].astype(jnp.float32) + u
                        ).astype(out_ref.dtype)


def _v_kernel_dense(w_ref, v_ref, p_ref, wj_ref, vj_ref, out_ref,
                    acc_ref, *, frac, norm, eps, n_k, off=0):
    contrib = jax.lax.dot((w_ref[...] - v_ref[...]).astype(jnp.float32),
                          p_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    _v_tail(contrib, wj_ref, vj_ref, out_ref, acc_ref,
            frac=frac, norm=norm, eps=eps, n_k=n_k, off=off)


def _v_kernel_left(b_ref, ut_ref, wj_ref, vj_ref, out_ref,
                   acc_ref, *, frac, norm, eps, n_k, off=0):
    contrib = jax.lax.dot(b_ref[...].astype(jnp.float32),
                          ut_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    _v_tail(contrib, wj_ref, vj_ref, out_ref, acc_ref,
            frac=frac, norm=norm, eps=eps, n_k=n_k, off=off)


@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "bk",
                                             "interpret"))
def maecho_v_update(W, V, P, *, frac: float, norm: bool = False,
                    eps: float = 1e-12, bo: int = 128, bi: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """W: (out, in) updated global; V: (N, out, in); P: (N, in, in).

    Returns V' per Eq. 11.  ``frac`` is μ/(1+μ).  With ``norm=True``
    the caller must pass bi = in_d (full rows resident for the norm).
    """
    out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, in_d)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples (ops.maecho_v_update_auto)")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_v_kernel_dense, frac=frac, norm=norm,
                               eps=eps, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(N, n_out, n_in, n_k),
        in_specs=[
            pl.BlockSpec((bo, bk), lambda i, o, j, k: (o, k)),       # W (red.)
            pl.BlockSpec((None, bo, bk), lambda i, o, j, k: (i, o, k)),  # V
            pl.BlockSpec((None, bk, bi), lambda i, o, j, k: (i, k, j)),  # P
            pl.BlockSpec((bo, bi), lambda i, o, j, k: (o, j)),       # W (out)
            pl.BlockSpec((None, bo, bi), lambda i, o, j, k: (i, o, j)),  # V
        ],
        out_specs=pl.BlockSpec((None, bo, bi), lambda i, o, j, k: (i, o, j)),
        out_shape=jax.ShapeDtypeStruct(V.shape, V.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(W, V, P, W, V)


@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "bk",
                                             "interpret"))
def maecho_v_update_factored(W, V, U, s, *, frac: float,
                             norm: bool = False, eps: float = 1e-12,
                             bo: int = 128, bi: int = 128, bk: int = 128,
                             interpret: bool | None = None):
    """Factored Pᵢ = Uᵢ·diag(sᵢ)·Uᵢᵀ.  U: (N, in, k); s: (N, k)."""
    from repro.kernels.maecho_gram import compressed_residual

    out_d, in_d = W.shape
    N, _, kd = U.shape
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    B = compressed_residual(W, V, U, s)                  # (N, out, k)
    UT = jnp.swapaxes(U, 1, 2).astype(jnp.float32)       # (N, k, in)
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_v_kernel_left, frac=frac, norm=norm,
                               eps=eps, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(N, n_out, n_in, n_k),
        in_specs=[
            pl.BlockSpec((None, bo, bk), lambda i, o, j, k: (i, o, k)),  # B
            pl.BlockSpec((None, bk, bi), lambda i, o, j, k: (i, k, j)),  # Uᵀ
            pl.BlockSpec((bo, bi), lambda i, o, j, k: (o, j)),       # W (out)
            pl.BlockSpec((None, bo, bi), lambda i, o, j, k: (i, o, j)),  # V
        ],
        out_specs=pl.BlockSpec((None, bo, bi), lambda i, o, j, k: (i, o, j)),
        out_shape=jax.ShapeDtypeStruct(V.shape, V.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(B, UT, W, V)


# --------------------------------------------------------------------------
# stacked-layer variants: the scan-layer axis L rides the grid outermost
# (grid (L, N, n_out, n_in, n_k)), one launch per leaf covers all layers
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "bk",
                                             "interpret"))
def maecho_v_update_stacked(W, V, P, *, frac: float, norm: bool = False,
                            eps: float = 1e-12, bo: int = 128,
                            bi: int = 128, bk: int = 128,
                            interpret: bool | None = None):
    """W: (L, out, in) updated global; V: (N, L, out, in);
    P: (N, L, in, in).  Returns the (N, L, out, in) Eq. 11 anchors
    from one launch.  ``norm=True`` needs bi = in_d, as per-layer."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, in_d)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_v_kernel_dense, frac=frac, norm=norm,
                               eps=eps, n_k=n_k, off=1)
    return pl.pallas_call(
        kernel,
        grid=(L, N, n_out, n_in, n_k),
        in_specs=[
            pl.BlockSpec((None, bo, bk),
                         lambda l, i, o, j, k: (l, o, k)),          # W (red.)
            pl.BlockSpec((None, None, bo, bk),
                         lambda l, i, o, j, k: (i, l, o, k)),       # V
            pl.BlockSpec((None, None, bk, bi),
                         lambda l, i, o, j, k: (i, l, k, j)),       # P
            pl.BlockSpec((None, bo, bi),
                         lambda l, i, o, j, k: (l, o, j)),          # W (out)
            pl.BlockSpec((None, None, bo, bi),
                         lambda l, i, o, j, k: (i, l, o, j)),       # V
        ],
        out_specs=pl.BlockSpec((None, None, bo, bi),
                               lambda l, i, o, j, k: (i, l, o, j)),
        out_shape=jax.ShapeDtypeStruct(V.shape, V.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(W, V, P, W, V)


@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "bk",
                                             "interpret"))
def maecho_v_update_factored_stacked(W, V, U, s, *, frac: float,
                                     norm: bool = False,
                                     eps: float = 1e-12, bo: int = 128,
                                     bi: int = 128, bk: int = 128,
                                     interpret: bool | None = None):
    """Stacked factored Pₗᵢ = Uₗᵢ·diag(sₗᵢ)·Uₗᵢᵀ.
    U: (N, L, in, k); s: (N, L, k)."""
    from repro.kernels.maecho_gram import compressed_residual

    L, out_d, in_d = W.shape
    N, _, _, kd = U.shape
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    B = compressed_residual(W, V, U, s)                # (N, L, out, k)
    UT = jnp.swapaxes(U, 2, 3).astype(jnp.float32)     # (N, L, k, in)
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_v_kernel_left, frac=frac, norm=norm,
                               eps=eps, n_k=n_k, off=1)
    return pl.pallas_call(
        kernel,
        grid=(L, N, n_out, n_in, n_k),
        in_specs=[
            pl.BlockSpec((None, None, bo, bk),
                         lambda l, i, o, j, k: (i, l, o, k)),       # B
            pl.BlockSpec((None, None, bk, bi),
                         lambda l, i, o, j, k: (i, l, k, j)),       # Uᵀ
            pl.BlockSpec((None, bo, bi),
                         lambda l, i, o, j, k: (l, o, j)),          # W (out)
            pl.BlockSpec((None, None, bo, bi),
                         lambda l, i, o, j, k: (i, l, o, j)),       # V
        ],
        out_specs=pl.BlockSpec((None, None, bo, bi),
                               lambda l, i, o, j, k: (i, l, o, j)),
        out_shape=jax.ShapeDtypeStruct(V.shape, V.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(B, UT, W, V)


@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "interpret"))
def maecho_v_update_diag_stacked(W, V, p, *, frac: float,
                                 norm: bool = False, eps: float = 1e-12,
                                 bo: int = 128, bi: int = 128,
                                 interpret: bool | None = None):
    """Stacked diagonal projectors.  p: (N, L, in)."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi = min(bo, out_d), min(bi, in_d)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0, (
        "pad layer dims to block multiples")
    p4 = p.reshape(N, L, 1, in_d)
    kernel = functools.partial(_v_diag_kernel, frac=frac, norm=norm,
                               eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(L, N, out_d // bo, in_d // bi),
        in_specs=[
            pl.BlockSpec((None, bo, bi),
                         lambda l, i, o, j: (l, o, j)),             # W
            pl.BlockSpec((None, None, bo, bi),
                         lambda l, i, o, j: (i, l, o, j)),          # V
            pl.BlockSpec((None, None, 1, bi),
                         lambda l, i, o, j: (i, l, 0, j)),          # p
        ],
        out_specs=pl.BlockSpec((None, None, bo, bi),
                               lambda l, i, o, j: (i, l, o, j)),
        out_shape=jax.ShapeDtypeStruct(V.shape, V.dtype),
        interpret=resolve(interpret),
    )(W, V, p4)


def _v_diag_kernel(w_ref, v_ref, p_ref, out_ref, *, frac, norm, eps):
    dj = (w_ref[...] - v_ref[...]).astype(jnp.float32)   # (bo, bi)
    p = p_ref[...].astype(jnp.float32)                   # (1, bi)
    u = dj * (1.0 - frac * p)
    if norm:
        u = _apply_norm(u, eps)
    out_ref[...] = (v_ref[...].astype(jnp.float32) + u
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "interpret"))
def maecho_v_update_diag(W, V, p, *, frac: float, norm: bool = False,
                         eps: float = 1e-12, bo: int = 128,
                         bi: int = 128, interpret: bool | None = None):
    """Diagonal projectors.  p: (N, in)."""
    out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi = min(bo, out_d), min(bi, in_d)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0, (
        "pad layer dims to block multiples")
    p3 = p.reshape(N, 1, in_d)
    kernel = functools.partial(_v_diag_kernel, frac=frac, norm=norm,
                               eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(N, out_d // bo, in_d // bi),
        in_specs=[
            pl.BlockSpec((bo, bi), lambda i, o, j: (o, j)),          # W
            pl.BlockSpec((None, bo, bi), lambda i, o, j: (i, o, j)),  # V
            pl.BlockSpec((None, 1, bi), lambda i, o, j: (i, 0, j)),   # p
        ],
        out_specs=pl.BlockSpec((None, bo, bi), lambda i, o, j: (i, o, j)),
        out_shape=jax.ShapeDtypeStruct(V.shape, V.dtype),
        interpret=resolve(interpret),
    )(W, V, p3)
