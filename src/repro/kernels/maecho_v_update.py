"""Pallas TPU kernel: fused MA-Echo anchor update (Eq. 11).

Computes, for every client i and every layer of a stack,

    Vᵢ' = Vᵢ + Norm( Δᵢ − μ/(1+μ) · Δᵢ Pᵢ ),   Δᵢ = W' − Vᵢ

i.e. the residual re-projected through (I − μ/(1+μ)Pᵢ), with the
optional row-normalisation.  The reference path materializes the
(N, out, in) Δᵢ Pᵢ product in HBM; here each output tile keeps the
whole chain in VMEM: Δ tiles are formed in-register from W'/Vᵢ blocks,
the Δᵢ Pᵢ contraction accumulates in a (bo, bi) fp32 scratch across
the k-grid axis, and the finalize step fuses the subtraction, optional
row-norm and the += into a single store of Vᵢ'.

Every kernel takes a stack of L layers (a 2-D leaf is L = 1).  Grid:
(L, N, n_out, n_in, n_k), the layer axis outermost; scratch persists
across the innermost axis only (one tile's reduction).  With
``norm=True`` the row norm needs the full row resident, so callers must
set bi = in_d (the ``ops`` apply halves do, and ``core.plan`` counts it
in the leaf's VMEM).

Fast paths mirror ``maecho_gram``:
  - ``maecho_v_update_factored_stacked``: Δᵢ Pᵢ = Bᵢ @ Uᵢᵀ with the
    compressed Bᵢ = ((W' − Vᵢ)Uᵢ)·diag(sᵢ) formed without the full
    residual — reduction runs over the rank k instead of in;
  - ``maecho_v_update_diag_stacked``: elementwise
    Δᵢ·(1 − μ/(1+μ)·pᵢ), one pass, no reduction axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.env import compiler_params, resolve, vmem_bytes


def vmem(kind: str, n: int, bo: int, bi: int, bk: int) -> int:
    """VMEM bytes of one Eq. 11 launch of ``kind`` ("full", "left" or
    "diag") at blocks (bo, bi, bk); the grid walks the ``n`` clients,
    so no buffer holds more than one."""
    del n
    if kind == "diag":
        return vmem_bytes([(bo, bi), (bo, bi), (1, bi), (bo, bi)],
                          values=[(bo, bi)] * 2)
    operands = [(bo, bk), (bk, bi)] + ([(bo, bk)] if kind == "full" else [])
    return vmem_bytes(operands + [(bo, bi)] * 3, [(bo, bi)],
                      [(bo, bk), (bo, bi)])


def _apply_norm(u, eps: float):
    """Row-normalise u (bo, full-row) exactly like the jnp oracle."""
    nrm = jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True))
    return u / jnp.maximum(nrm, eps)


def _v_tail(contrib, wj_ref, vj_ref, out_ref, acc_ref,
            *, frac: float, norm: bool, eps: float, n_k: int):
    """Accumulate one k-block of Δᵢ Pᵢ, then fuse Eq. 11 at the end.
    The k axis is the last of the (layer, client, out, in, k) grid."""
    k = pl.program_id(4)

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
                    acc_ref, *, frac, norm, eps, n_k):
    contrib = jax.lax.dot((w_ref[...] - v_ref[...]).astype(jnp.float32),
                          p_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    _v_tail(contrib, wj_ref, vj_ref, out_ref, acc_ref,
            frac=frac, norm=norm, eps=eps, n_k=n_k)


def _v_kernel_left(b_ref, ut_ref, wj_ref, vj_ref, out_ref,
                   acc_ref, *, frac, norm, eps, n_k):
    contrib = jax.lax.dot(b_ref[...].astype(jnp.float32),
                          ut_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    _v_tail(contrib, wj_ref, vj_ref, out_ref, acc_ref,
            frac=frac, norm=norm, eps=eps, n_k=n_k)


@functools.partial(jax.jit, static_argnames=("frac", "norm", "eps",
                                             "bo", "bi", "bk",
                                             "interpret"))
def maecho_v_update_stacked(W, V, P, *, frac: float, norm: bool = False,
                            eps: float = 1e-12, bo: int = 128,
                            bi: int = 128, bk: int = 128,
                            interpret: bool | None = None):
    """W: (L, out, in) updated global; V: (N, L, out, in);
    P: (N, L, in, in).  Returns the (N, L, out, in) Eq. 11 anchors
    from one launch.  ``norm=True`` needs bi = in_d."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, in_d)
    if norm:
        assert bi == in_d, "row-norm needs full rows: set bi = in_d"
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_v_kernel_dense, frac=frac, norm=norm,
                               eps=eps, n_k=n_k)
    return pl.pallas_call(
        kernel,
        name="maecho_v_update_stacked",
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
        compiler_params=compiler_params(vmem("full", N, bo, bi, bk)),
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
                               eps=eps, n_k=n_k)
    return pl.pallas_call(
        kernel,
        name="maecho_v_update_factored_stacked",
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
        compiler_params=compiler_params(vmem("left", N, bo, bi, bk)),
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
        name="maecho_v_update_diag_stacked",
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
        compiler_params=compiler_params(vmem("diag", N, bo, bi, 0)),
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
