"""Pallas TPU kernel: fused MA-Echo layer update (Eq. 7).

Computes, for one layer,   W' = W + η · D,
         D = − Σ_{i<N} 2 αᵢ (W − Vᵢ) Pᵢ

The PyTorch reference runs N separate GEMMs plus adds, streaming W−Vᵢ
and the (d_in×d_in) projector from HBM each time.  On TPU we tile the
(out×in) output into MXU-aligned VMEM blocks and accumulate the client
sum **in VMEM scratch** across the (client, k-block) grid axes, so each
output tile is written once and the residual (W−Vᵢ) tile is formed
in-register — the fusion the paper's hot loop wants (DESIGN.md §6).

Grid: (n_out, n_in, N, n_k); scratch persists across the two inner
axes.  Block shapes (bo, bk) / (bk, bi) / (bo, bi), 128-aligned.

Fast paths matching ``maecho_gram`` / ``maecho_v_update``:
  - ``maecho_update_factored``: Pᵢ = Uᵢ·diag(sᵢ)·Uᵢᵀ kept factored —
    the per-client GEMM contracts the (N, out, k) compressed residual
    Aᵢ = ((W − Vᵢ)Uᵢ)·diag(sᵢ) against Uᵢᵀ, reduction over the rank k
    instead of in (O(out·in·k) per client);
  - ``maecho_update_diag``: 1-D projectors, single elementwise pass,
    no scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.env import resolve


def _kernel(alpha_ref, w_ref, v_ref, p_ref, wout_ref, out_ref, acc_ref,
            *, eta: float, n_clients: int, n_k: int, off: int = 0):
    i = pl.program_id(off + 2)    # client index
    k = pl.program_id(off + 3)    # reduction block index

    @pl.when((i == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # stacked grids carry the layer axis in front: α is (L, N) in SMEM
    a_i = alpha_ref[pl.program_id(0), i] if off else alpha_ref[i]
    resid = (w_ref[...] - v_ref[...]).astype(jnp.float32)    # (bo, bk)
    pblk = p_ref[...].astype(jnp.float32)                    # (bk, bi)
    acc_ref[...] += -2.0 * a_i * jax.lax.dot(
        resid, pblk, preferred_element_type=jnp.float32)

    @pl.when((i == n_clients - 1) & (k == n_k - 1))
    def _finalize():
        out_ref[...] = (wout_ref[...].astype(jnp.float32)
                        + eta * acc_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eta", "bo", "bi", "bk",
                                             "interpret"))
def maecho_update(W, V, P, alpha, *, eta: float = 1.0, bo: int = 128,
                  bi: int = 128, bk: int = 128, interpret: bool | None = None):
    """W: (out, in); V: (N, out, in); P: (N, in, in); alpha: (N,).

    Returns W' = W + η·D with D from Eq. 7.  ``interpret=None`` runs
    the Pallas interpreter on a CPU backend and Mosaic on a TPU.
    """
    out_d, in_d = W.shape
    N = V.shape[0]
    bo = min(bo, out_d)
    bi = min(bi, in_d)
    bk = min(bk, in_d)
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk

    grid = (n_out, n_in, N, n_k)
    kernel = functools.partial(_kernel, eta=eta, n_clients=N, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # alpha
            pl.BlockSpec((bo, bk), lambda o, j, i, k: (o, k)),      # W (resid)
            pl.BlockSpec((None, bo, bk), lambda o, j, i, k: (i, o, k)),  # V
            pl.BlockSpec((None, bk, bi), lambda o, j, i, k: (i, k, j)),  # P
            pl.BlockSpec((bo, bi), lambda o, j, i, k: (o, j)),      # W (out)
        ],
        out_specs=pl.BlockSpec((bo, bi), lambda o, j, i, k: (o, j)),
        out_shape=jax.ShapeDtypeStruct((out_d, in_d), W.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(alpha, W, V, P, W)


def _left_kernel(alpha_ref, a_ref, ut_ref, wout_ref, out_ref, acc_ref,
                 *, eta: float, n_clients: int, n_k: int, off: int = 0):
    """Residual given as a left factor: (W − Vᵢ)Pᵢ = Aᵢ @ Uᵢᵀ."""
    i = pl.program_id(off + 2)
    k = pl.program_id(off + 3)

    @pl.when((i == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_i = alpha_ref[pl.program_id(0), i] if off else alpha_ref[i]
    acc_ref[...] += -2.0 * a_i * jax.lax.dot(
        a_ref[...].astype(jnp.float32), ut_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when((i == n_clients - 1) & (k == n_k - 1))
    def _finalize():
        out_ref[...] = (wout_ref[...].astype(jnp.float32)
                        + eta * acc_ref[...]).astype(out_ref.dtype)


def maecho_update_factored(W, V, U, s, alpha, *, eta: float = 1.0,
                           bo: int = 128, bi: int = 128, bk: int = 128,
                           interpret: bool | None = None):
    """Factored Pᵢ = Uᵢ·diag(sᵢ)·Uᵢᵀ.  U: (N, in, k); s: (N, k)."""
    from repro.kernels.maecho_gram import compressed_residual

    A = compressed_residual(W, V, U, s)                  # (N, out, k)
    UT = jnp.swapaxes(U, 1, 2).astype(jnp.float32)       # (N, k, in)
    return maecho_update_left(W, A, UT, alpha, eta=eta, bo=bo, bi=bi,
                              bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eta", "bo", "bi", "bk",
                                             "interpret"))
def maecho_update_left(W, A, UT, alpha, *, eta: float = 1.0,
                       bo: int = 128, bi: int = 128, bk: int = 128,
                       interpret: bool | None = None):
    """Eq. 7 from pre-factored residuals Rᵢ = Aᵢ @ UTᵢ (shareable with
    ``maecho_gram_left`` — one ``compressed_residual`` per iteration)."""
    out_d, in_d = W.shape
    N, _, kd = A.shape
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_left_kernel, eta=eta, n_clients=N,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # alpha
            pl.BlockSpec((None, bo, bk), lambda o, j, i, k: (i, o, k)),  # A
            pl.BlockSpec((None, bk, bi), lambda o, j, i, k: (i, k, j)),  # Uᵀ
            pl.BlockSpec((bo, bi), lambda o, j, i, k: (o, j)),       # W (out)
        ],
        out_specs=pl.BlockSpec((bo, bi), lambda o, j, i, k: (o, j)),
        out_shape=jax.ShapeDtypeStruct((out_d, in_d), W.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(alpha, A, UT, W)


# --------------------------------------------------------------------------
# stacked-layer variants: the scan-layer axis L rides the grid outermost,
# α is the per-layer (L, N) stack, one launch covers the whole leaf
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("eta", "bo", "bi", "bk",
                                             "interpret"))
def maecho_update_stacked(W, V, P, alpha, *, eta: float = 1.0,
                          bo: int = 128, bi: int = 128, bk: int = 128,
                          interpret: bool | None = None):
    """W: (L, out, in); V: (N, L, out, in); P: (N, L, in, in);
    alpha: (L, N).  Returns the (L, out, in) Eq. 7 update from one
    launch — grid (L, n_out, n_in, N, n_k), layer axis outermost."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, in_d)
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_kernel, eta=eta, n_clients=N, n_k=n_k,
                               off=1)
    return pl.pallas_call(
        kernel,
        grid=(L, n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # alpha
            pl.BlockSpec((None, bo, bk),
                         lambda l, o, j, i, k: (l, o, k)),          # W (res)
            pl.BlockSpec((None, None, bo, bk),
                         lambda l, o, j, i, k: (i, l, o, k)),       # V
            pl.BlockSpec((None, None, bk, bi),
                         lambda l, o, j, i, k: (i, l, k, j)),       # P
            pl.BlockSpec((None, bo, bi),
                         lambda l, o, j, i, k: (l, o, j)),          # W (out)
        ],
        out_specs=pl.BlockSpec((None, bo, bi),
                               lambda l, o, j, i, k: (l, o, j)),
        out_shape=jax.ShapeDtypeStruct((L, out_d, in_d), W.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(alpha, W, V, P, W)


@functools.partial(jax.jit, static_argnames=("eta", "bo", "bi", "bk",
                                             "interpret"))
def maecho_update_left_stacked(W, A, UT, alpha, *, eta: float = 1.0,
                               bo: int = 128, bi: int = 128,
                               bk: int = 128, interpret: bool | None = None):
    """Stacked Eq. 7 from pre-factored residuals Rₗᵢ = Aₗᵢ @ UTₗᵢ
    (A shared with ``maecho_gram_left_stacked`` — one
    ``compressed_residual`` per leaf per iteration).
    W: (L, out, in); A: (N, L, out, k); UT: (N, L, k, in);
    alpha: (L, N)."""
    L, out_d, in_d = W.shape
    N, _, _, kd = A.shape
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_left_kernel, eta=eta, n_clients=N,
                               n_k=n_k, off=1)
    return pl.pallas_call(
        kernel,
        grid=(L, n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # alpha
            pl.BlockSpec((None, None, bo, bk),
                         lambda l, o, j, i, k: (i, l, o, k)),       # A
            pl.BlockSpec((None, None, bk, bi),
                         lambda l, o, j, i, k: (i, l, k, j)),       # Uᵀ
            pl.BlockSpec((None, bo, bi),
                         lambda l, o, j, i, k: (l, o, j)),          # W (out)
        ],
        out_specs=pl.BlockSpec((None, bo, bi),
                               lambda l, o, j, i, k: (l, o, j)),
        out_shape=jax.ShapeDtypeStruct((L, out_d, in_d), W.dtype),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32)],
        interpret=resolve(interpret),
    )(alpha, A, UT, W)


@functools.partial(jax.jit, static_argnames=("eta", "bo", "bi",
                                             "interpret"))
def maecho_update_diag_stacked(W, V, p, alpha, *, eta: float = 1.0,
                               bo: int = 128, bi: int = 128,
                               interpret: bool | None = None):
    """Stacked diagonal projectors.  W: (L, out, in);
    V: (N, L, out, in); p: (N, L, in); alpha: (L, N)."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi = min(bo, out_d), min(bi, in_d)
    assert out_d % bo == 0 and in_d % bi == 0, (
        "pad layer dims to block multiples")
    p4 = p.reshape(N, L, 1, in_d)
    a4 = alpha.T.reshape(N, L, 1, 1).astype(jnp.float32)
    kernel = functools.partial(_diag_kernel, eta=eta)
    return pl.pallas_call(
        kernel,
        grid=(L, out_d // bo, in_d // bi),
        in_specs=[
            pl.BlockSpec((None, bo, bi), lambda l, o, j: (l, o, j)),   # W
            pl.BlockSpec((N, None, bo, bi),
                         lambda l, o, j: (0, l, o, j)),                # V
            pl.BlockSpec((N, None, 1, bi),
                         lambda l, o, j: (0, l, 0, j)),                # p
            pl.BlockSpec((N, None, 1, 1),
                         lambda l, o, j: (0, l, 0, 0)),                # alpha
        ],
        out_specs=pl.BlockSpec((None, bo, bi), lambda l, o, j: (l, o, j)),
        out_shape=jax.ShapeDtypeStruct((L, out_d, in_d), W.dtype),
        interpret=resolve(interpret),
    )(W, V, p4, a4)


def _diag_kernel(w_ref, v_ref, p_ref, alpha_ref, out_ref, *, eta: float):
    w = w_ref[...].astype(jnp.float32)                   # (bo, bi)
    v = v_ref[...].astype(jnp.float32)                   # (N, bo, bi)
    p = p_ref[...].astype(jnp.float32)                   # (N, 1, bi)
    a = alpha_ref[...].astype(jnp.float32)               # (N, 1, 1)
    d = jnp.sum(-2.0 * a * (w[None] - v) * p, axis=0)
    out_ref[...] = (w + eta * d).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eta", "bo", "bi",
                                             "interpret"))
def maecho_update_diag(W, V, p, alpha, *, eta: float = 1.0,
                       bo: int = 128, bi: int = 128,
                       interpret: bool | None = None):
    """Diagonal projectors.  p: (N, in); alpha: (N,)."""
    out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi = min(bo, out_d), min(bi, in_d)
    assert out_d % bo == 0 and in_d % bi == 0, (
        "pad layer dims to block multiples")
    p3 = p.reshape(N, 1, in_d)
    a3 = alpha.reshape(N, 1, 1).astype(jnp.float32)
    kernel = functools.partial(_diag_kernel, eta=eta)
    return pl.pallas_call(
        kernel,
        grid=(out_d // bo, in_d // bi),
        in_specs=[
            pl.BlockSpec((bo, bi), lambda o, j: (o, j)),            # W
            pl.BlockSpec((N, bo, bi), lambda o, j: (0, o, j)),      # V
            pl.BlockSpec((N, 1, bi), lambda o, j: (0, 0, j)),       # p
            pl.BlockSpec((N, 1, 1), lambda o, j: (0, 0, 0)),        # alpha
        ],
        out_specs=pl.BlockSpec((bo, bi), lambda o, j: (o, j)),
        out_shape=jax.ShapeDtypeStruct((out_d, in_d), W.dtype),
        interpret=resolve(interpret),
    )(W, V, p3, a3)
