"""Pallas TPU kernel: Gram matrix of projected MA-Echo residuals.

The Eq. 6 QP needs, per layer, the (N, N) table  G[i, j] = ⟨Rᵢ, Rⱼ⟩
with Rᵢ = (W − Vᵢ)Pᵢ.  The naive path materializes the full
(N, out, in) fp32 residual tensor in HBM just to contract it down to N²
scalars.  This kernel streams instead: per (out, in) output tile it
builds each client's residual tile **in VMEM** — the (W − Vᵢ)
difference is formed in-register and contracted against Pᵢ's (bk, bi)
blocks on the fly — then folds all N×N pairwise tile dot products into
an (N, N) VMEM accumulator.  Nothing of size out×in is ever written to
HBM.

Every kernel takes a stack of L layers (a 2-D leaf is L = 1): grid
(L, n_out, n_in, N, n_k), the layer axis outermost.  The two inner axes
build one client's residual tile (k is the GEMM reduction over the
projector's rows); the finished tile is parked in the (N, bo, bi)
``rstore`` scratch, and once all clients' tiles for this (o, j)
position exist, one 2-D dot over the flattened tiles adds their
pairwise products to the Gram accumulator.  The scratch is one layer's
and is re-initialized at the start of every layer, whose (N, N) output
block is written once, at its final step.

Variants (all share the accumulate/finalize tail):
  - ``maecho_gram_stacked``:      dense (N, L, in, in) projectors;
  - ``maecho_gram_left_stacked``: the residual given as a left factor,
    Rᵢ = Aᵢ @ UTᵢ — factored Pᵢ = Uᵢ·diag(sᵢ)·Uᵢᵀ with Aᵢ =
    ((W − Vᵢ)Uᵢ)·diag(sᵢ) the (N, L, out, k) *compressed* residual
    (:func:`compressed_residual`), dropping the GEMM chain from
    O(out·in²) to O(out·in·k) (paper §7.3: projectors are low-rank);
  - ``maecho_gram_diag_stacked``: 1-D per-client diagonal projectors
    (embedding token support / broadcast scalar rule) — elementwise
    residuals, single fused pass, no reduction axis.

Blocks are per axis: ``core.plan`` derives (bo, bi, bk) per leaf so
that, within ``env.VMEM_BUDGET``, the contraction and the columns span
whole padded rows and ``bo`` is as large as fits (:func:`vmem` counts
what a launch holds).  The rstore is N·bo·bi fp32, so a larger cohort
takes a smaller ``bo``; one client's tile holds at most
:data:`PAIR_TILE` elements.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.env import compiler_params, resolve, vmem_bytes

# Elements of one client's (bo, bi) residual tile at most.  Mosaic lays
# the pair contraction of the N tiles out in full, and its compile time
# grows faster than the tile: compiling the Gram for a described v5e
# took 0.5 s at 128 × 128, 4.3 s at 608 × 896, 7.2 s at 896 × 896,
# 13.4 s at 1216 × 896 and 32 s at 16 × 151936.
PAIR_TILE = 2 ** 20


def vmem(kind: str, n: int, bo: int, bi: int, bk: int) -> int:
    """VMEM bytes of one Gram launch of ``kind`` ("full", "left" or
    "diag") over ``n`` clients at blocks (bo, bi, bk)."""
    if kind == "diag":
        return vmem_bytes([(bo, bi), (n, bo, bi), (n, 1, bi), (n, n)],
                          [(n, n)], [(n, bo, bi)] * 2)
    operands = [(bo, bk), (bk, bi)] + ([(bo, bk)] if kind == "full" else [])
    return vmem_bytes(operands + [(n, n)], [(bo, bi), (n, bo, bi), (n, n)],
                      [(bo, bk), (bo, bi)])


def _pair_gram(r):
    """(N, N) pairwise inner products of the N (bo, bi) tiles in ``r``.

    Flattened to (N, bo·bi) and contracted as ONE 2-D MXU dot: Mosaic
    refuses a dot_general whose lhs contracts two axes at once."""
    n = r.shape[0]
    rf = r.reshape(n, -1)
    return jax.lax.dot_general(rf, rf, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _gram_tail(resid, out_ref, racc_ref, rstore_ref, gacc_ref,
               n_clients: int, n_k: int):
    """Shared accumulate/park/contract/finalize logic.

    ``resid`` is this (layer, client, k-block)'s partial-residual
    contribution (bo, bi) in fp32; callers form it from their own
    operands.  Behind the layer axis come the (out, in, client, k)
    axes: the accumulators re-initialize at the start of every layer
    (the (o, j, i, k) == 0 condition fires once per layer) and the
    finalize writes that layer's (N, N) output block.
    """
    o, j, i, k = (pl.program_id(1 + t) for t in range(4))
    n_out, n_in = pl.num_programs(1), pl.num_programs(2)

    @pl.when((o == 0) & (j == 0) & (i == 0) & (k == 0))
    def _init_gram():
        gacc_ref[...] = jnp.zeros_like(gacc_ref)

    @pl.when(k == 0)
    def _init_tile():
        racc_ref[...] = jnp.zeros_like(racc_ref)

    racc_ref[...] += resid

    @pl.when(k == n_k - 1)
    def _park_tile():
        rstore_ref[i] = racc_ref[...]

    @pl.when((i == n_clients - 1) & (k == n_k - 1))
    def _contract_pairs():
        gacc_ref[...] += _pair_gram(rstore_ref[...])

    @pl.when((o == n_out - 1) & (j == n_in - 1) &
             (i == n_clients - 1) & (k == n_k - 1))
    def _finalize():
        out_ref[...] = gacc_ref[...].astype(out_ref.dtype)


def _gram_kernel_dense(w_ref, v_ref, p_ref, out_ref,
                       racc_ref, rstore_ref, gacc_ref,
                       *, n_clients: int, n_k: int):
    resid = jax.lax.dot((w_ref[...] - v_ref[...]).astype(jnp.float32),
                        p_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    _gram_tail(resid, out_ref, racc_ref, rstore_ref, gacc_ref,
               n_clients, n_k)


def _gram_kernel_left(a_ref, ut_ref, out_ref,
                      racc_ref, rstore_ref, gacc_ref,
                      *, n_clients: int, n_k: int):
    """Residual given as a left factor: Rᵢ = Aᵢ @ (right)ᵢ."""
    resid = jax.lax.dot(a_ref[...].astype(jnp.float32),
                        ut_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    _gram_tail(resid, out_ref, racc_ref, rstore_ref, gacc_ref,
               n_clients, n_k)


def compressed_residual(W, V, U, s):
    """Aᵢ = ((W − Vᵢ)Uᵢ)·diag(sᵢ): the (N, …, out, k) compressed
    residual.

    Formed as W@Uᵢ − Vᵢ@Uᵢ so the (N, …, out, in) full residual is
    never materialized — only its rank-k image, which IS the
    factored-path working set.  Any stacked-layer axes ride the
    ellipsis: W (…, out, in), V (N, …, out, in), U (N, …, in, k),
    s (N, …, k).  Unit layer axes (a 2-D leaf as the stack L = 1) are
    dropped for the contraction: XLA:CPU sums a product with a unit
    batch axis in another order, and the stack of one keeps the bits of
    the 2-D leaf.
    """
    lead = W.shape[:-2]
    if lead and math.prod(lead) == 1:
        A = compressed_residual(W.reshape(W.shape[-2:]),
                                V.reshape(V.shape[:1] + V.shape[-2:]),
                                U.reshape(U.shape[:1] + U.shape[-2:]),
                                s.reshape(s.shape[:1] + s.shape[-1:]))
        return A.reshape(A.shape[:1] + lead + A.shape[-2:])
    A = (jnp.einsum("...oi,n...ik->n...ok", W.astype(jnp.float32),
                    U.astype(jnp.float32))
         - jnp.einsum("n...oi,n...ik->n...ok", V.astype(jnp.float32),
                      U.astype(jnp.float32)))
    return A * s[..., None, :].astype(jnp.float32)


def _gram_cross_kernel(a_ref, b_ref, out_ref, acc_ref):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(0) - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def maecho_gram_cross(Ra, Rb, *, bd: int = 512, interpret: bool | None = None):
    """Cross-Gram block between two client chunks' flat residuals.

    Ra: (ca, D); Rb: (cb, D) — flattened residual rows for chunks a and
    b.  Returns the fp32 (ca, cb) block G[i, j] = ⟨Ra_i, Rb_j⟩ by
    streaming the feature axis through VMEM in ``bd``-wide slabs (the
    ``rank_update.py`` tiled-accumulator idiom): only one (ca, bd) +
    (cb, bd) operand pair is resident per grid step, never the full
    (N, D) residual set — the client-chunked Gram path's building
    block.
    """
    ca, D = Ra.shape
    cb = Rb.shape[0]
    bd = min(bd, D)
    assert D % bd == 0, "caller pads the flat feature axis to bd"
    return pl.pallas_call(
        _gram_cross_kernel,
        name="maecho_gram_cross",
        grid=(D // bd,),
        in_specs=[pl.BlockSpec((ca, bd), lambda k: (0, k)),
                  pl.BlockSpec((cb, bd), lambda k: (0, k))],
        out_specs=pl.BlockSpec((ca, cb), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ca, cb), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ca, cb), jnp.float32)],
        interpret=resolve(interpret),
    )(Ra, Rb)


def _gram_diag_kernel(w_ref, v_ref, p_ref, out_ref, gacc_ref):
    o, j = pl.program_id(1), pl.program_id(2)
    n_out, n_in = pl.num_programs(1), pl.num_programs(2)

    @pl.when((o == 0) & (j == 0))
    def _init():
        gacc_ref[...] = jnp.zeros_like(gacc_ref)

    w = w_ref[...].astype(jnp.float32)                   # (bo, bi)
    v = v_ref[...].astype(jnp.float32)                   # (N, bo, bi)
    p = p_ref[...].astype(jnp.float32)                   # (N, 1, bi)
    gacc_ref[...] += _pair_gram((w[None] - v) * p)

    @pl.when((o == n_out - 1) & (j == n_in - 1))
    def _finalize():
        out_ref[...] = gacc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "bk",
                                             "interpret"))
def maecho_gram_stacked(W, V, P, *, bo: int = 128, bi: int = 128,
                        bk: int = 128, interpret: bool | None = None):
    """W: (L, out, in); V: (N, L, out, in); P: (N, L, in, in) dense.

    Returns the fp32 (L, N, N) per-layer Gram stack from ONE launch:
    grid (L, n_out, n_in, N, n_k) with the layer axis outermost, so
    the VMEM scratch (one layer's tile accumulators) is reused across
    layers instead of replicated."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, in_d)
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_gram_kernel_dense, n_clients=N, n_k=n_k)
    return pl.pallas_call(
        kernel,
        name="maecho_gram_stacked",
        grid=(L, n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec((None, bo, bk),
                         lambda l, o, j, i, k: (l, o, k)),             # W
            pl.BlockSpec((None, None, bo, bk),
                         lambda l, o, j, i, k: (i, l, o, k)),          # V
            pl.BlockSpec((None, None, bk, bi),
                         lambda l, o, j, i, k: (i, l, k, j)),          # P
        ],
        out_specs=pl.BlockSpec((None, N, N),
                               lambda l, o, j, i, k: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, N, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32),
                        pltpu.VMEM((N, bo, bi), jnp.float32),
                        pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=compiler_params(vmem("full", N, bo, bi, bk)),
        interpret=resolve(interpret),
    )(W, V, P)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "bk",
                                             "interpret"))
def maecho_gram_left_stacked(A, UT, *, bo: int = 128, bi: int = 128,
                             bk: int = 128, interpret: bool | None = None):
    """Stacked Gram from pre-factored residuals Rₗᵢ = Aₗᵢ @ UTₗᵢ.

    A: (N, L, out, k) compressed residual; UT: (N, L, k, in).
    Returns (L, N, N); callers that also run the Eq. 7 update share
    one ``compressed_residual`` with ``maecho_update_left_stacked``."""
    N, L, out_d, kd = A.shape
    in_d = UT.shape[3]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_gram_kernel_left, n_clients=N, n_k=n_k)
    return pl.pallas_call(
        kernel,
        name="maecho_gram_left_stacked",
        grid=(L, n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec((None, None, bo, bk),
                         lambda l, o, j, i, k: (i, l, o, k)),          # A
            pl.BlockSpec((None, None, bk, bi),
                         lambda l, o, j, i, k: (i, l, k, j)),          # Uᵀ
        ],
        out_specs=pl.BlockSpec((None, N, N),
                               lambda l, o, j, i, k: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, N, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32),
                        pltpu.VMEM((N, bo, bi), jnp.float32),
                        pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=compiler_params(vmem("left", N, bo, bi, bk)),
        interpret=resolve(interpret),
    )(A, UT)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "interpret"))
def maecho_gram_diag_stacked(W, V, p, *, bo: int = 128, bi: int = 128,
                             interpret: bool | None = None):
    """Stacked diagonal projectors.  W: (L, out, in);
    V: (N, L, out, in); p: (N, L, in).  Returns (L, N, N)."""
    L, out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi = min(bo, out_d), min(bi, in_d)
    assert out_d % bo == 0 and in_d % bi == 0, (
        "pad layer dims to block multiples")
    p4 = p.reshape(N, L, 1, in_d)
    return pl.pallas_call(
        _gram_diag_kernel,
        name="maecho_gram_diag_stacked",
        grid=(L, out_d // bo, in_d // bi),
        in_specs=[
            pl.BlockSpec((None, bo, bi), lambda l, o, j: (l, o, j)),   # W
            pl.BlockSpec((N, None, bo, bi),
                         lambda l, o, j: (0, l, o, j)),                # V
            pl.BlockSpec((N, None, 1, bi),
                         lambda l, o, j: (0, l, 0, j)),                # p
        ],
        out_specs=pl.BlockSpec((None, N, N), lambda l, o, j: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, N, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=compiler_params(vmem("diag", N, bo, bi, 0)),
        interpret=resolve(interpret),
    )(W, V, p4)
