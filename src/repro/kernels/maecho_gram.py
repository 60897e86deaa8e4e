"""Pallas TPU kernel: Gram matrix of projected MA-Echo residuals.

The Eq. 6 QP needs the (N, N) table  G[i, j] = ⟨Rᵢ, Rⱼ⟩  with
Rᵢ = (W − Vᵢ)Pᵢ.  The naive path materializes the full (N, out, in)
fp32 residual tensor in HBM just to contract it down to N² scalars.
This kernel streams instead: per (out, in) output tile it builds each
client's residual tile **in VMEM** — the (W − Vᵢ) difference is formed
in-register and contracted against Pᵢ's (bk, bi) blocks on the fly —
then folds all N×N pairwise tile dot products into an (N, N) VMEM
accumulator.  Nothing of size out×in is ever written to HBM.

Grid: (n_out, n_in, N, n_k).  The two inner axes build one client's
residual tile (k is the GEMM reduction over the projector's rows); the
finished tile is parked in the (N, bo, bi) ``rstore`` scratch, and once
all clients' tiles for this (o, j) position exist, one 2-D dot over
the flattened tiles adds their pairwise products to the Gram
accumulator.  Scratch persists across the whole grid; the Gram table
is written exactly once, at the final step.

Variants (all share the accumulate/finalize tail):
  - ``maecho_gram``:          dense (N, in, in) projectors;
  - ``maecho_gram_factored``: Pᵢ = Uᵢ·diag(sᵢ)·Uᵢᵀ kept factored — the
    residual tile is Aᵢ @ Uᵢᵀ with Aᵢ = ((W − Vᵢ)Uᵢ)·diag(sᵢ) formed
    once as the (N, out, k) *compressed* residual, dropping the GEMM
    chain from O(out·in²) to O(out·in·k) (paper §7.3: projectors are
    low-rank);
  - ``maecho_gram_diag``:     1-D per-client diagonal projectors
    (embedding token support / broadcast scalar rule) — elementwise
    residuals, single fused pass, no reduction axis.

VMEM budget: rstore is N·bo·bi fp32 — with the default 128×128 blocks
that caps N around 40 per core (the paper runs N ≤ 50; shrink ``bo``
for larger cohorts).

Stacked-layer variants (``maecho_gram_stacked`` /
``maecho_gram_left_stacked`` / ``maecho_gram_diag_stacked``): the
scan-over-layers axis L is folded into the grid as the outermost
dimension — grid (L, n_out, n_in, N, n_k), per-layer (N, N) output
block, same VMEM scratch reused across layers — so ONE launch covers
every scanned layer of a stacked leaf (the LLM transformer-stack
layout) instead of L oracle fallbacks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.env import resolve


def _pair_gram(r):
    """(N, N) pairwise inner products of the N (bo, bi) tiles in ``r``.

    Flattened to (N, bo·bi) and contracted as ONE 2-D MXU dot: Mosaic
    refuses a dot_general whose lhs contracts two axes at once."""
    n = r.shape[0]
    rf = r.reshape(n, -1)
    return jax.lax.dot_general(rf, rf, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _gram_tail(resid, out_ref, racc_ref, rstore_ref, gacc_ref,
               n_clients: int, n_k: int, off: int = 0):
    """Shared accumulate/park/contract/finalize logic.

    ``resid`` is this (client, k-block)'s partial-residual contribution
    (bo, bi) in fp32; callers form it from their own operands.
    ``off`` is the grid offset of the (out, in, client, k) axes: 0 for
    the per-layer grid, 1 when a stacked-layer axis rides in front —
    the accumulators then re-initialize at the start of every layer
    (the (o, j, i, k) == 0 condition fires once per outer-grid step)
    and the finalize writes that layer's (N, N) output block.
    """
    o, j, i, k = (pl.program_id(off + t) for t in range(4))
    n_out, n_in = pl.num_programs(off), pl.num_programs(off + 1)

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
                       *, n_clients: int, n_k: int, off: int = 0):
    resid = jax.lax.dot((w_ref[...] - v_ref[...]).astype(jnp.float32),
                        p_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    _gram_tail(resid, out_ref, racc_ref, rstore_ref, gacc_ref,
               n_clients, n_k, off)


def _gram_kernel_left(a_ref, ut_ref, out_ref,
                      racc_ref, rstore_ref, gacc_ref,
                      *, n_clients: int, n_k: int, off: int = 0):
    """Residual given as a left factor: Rᵢ = Aᵢ @ (right)ᵢ."""
    resid = jax.lax.dot(a_ref[...].astype(jnp.float32),
                        ut_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    _gram_tail(resid, out_ref, racc_ref, rstore_ref, gacc_ref,
               n_clients, n_k, off)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "bk",
                                             "interpret"))
def maecho_gram(W, V, P, *, bo: int = 128, bi: int = 128, bk: int = 128,
                interpret: bool | None = None):
    """W: (out, in); V: (N, out, in); P: (N, in, in) dense.

    Returns the fp32 (N, N) Gram matrix of projected residuals.
    """
    out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, in_d)
    assert out_d % bo == 0 and in_d % bi == 0 and in_d % bk == 0, (
        "pad layer dims to block multiples (ops.maecho_gram_auto)")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_gram_kernel_dense, n_clients=N, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec((bo, bk), lambda o, j, i, k: (o, k)),          # W
            pl.BlockSpec((None, bo, bk), lambda o, j, i, k: (i, o, k)),  # V
            pl.BlockSpec((None, bk, bi), lambda o, j, i, k: (i, k, j)),  # P
        ],
        out_specs=pl.BlockSpec((N, N), lambda o, j, i, k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32),
                        pltpu.VMEM((N, bo, bi), jnp.float32),
                        pltpu.VMEM((N, N), jnp.float32)],
        interpret=resolve(interpret),
    )(W, V, P)


def compressed_residual(W, V, U, s):
    """Aᵢ = ((W − Vᵢ)Uᵢ)·diag(sᵢ): the (N, …, out, k) compressed
    residual.

    Formed as W@Uᵢ − Vᵢ@Uᵢ so the (N, …, out, in) full residual is
    never materialized — only its rank-k image, which IS the
    factored-path working set.  Any stacked-layer axes ride the
    ellipsis: W (…, out, in), V (N, …, out, in), U (N, …, in, k),
    s (N, …, k).
    """
    A = (jnp.einsum("...oi,n...ik->n...ok", W.astype(jnp.float32),
                    U.astype(jnp.float32))
         - jnp.einsum("n...oi,n...ik->n...ok", V.astype(jnp.float32),
                      U.astype(jnp.float32)))
    return A * s[..., None, :].astype(jnp.float32)


def maecho_gram_factored(W, V, U, s, *, bo: int = 128, bi: int = 128,
                         bk: int = 128, interpret: bool | None = None):
    """Factored projectors Pᵢ = Uᵢ·diag(sᵢ)·Uᵢᵀ.

    W: (out, in); V: (N, out, in); U: (N, in, k); s: (N, k).
    The kernel streams Rᵢ tiles as Aᵢ @ Uᵢᵀ (reduction over k, not in).
    """
    A = compressed_residual(W, V, U, s)
    UT = jnp.swapaxes(U, 1, 2).astype(jnp.float32)       # (N, k, in)
    return maecho_gram_left(A, UT, bo=bo, bi=bi, bk=bk,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "bk",
                                             "interpret"))
def maecho_gram_left(A, UT, *, bo: int = 128, bi: int = 128,
                     bk: int = 128, interpret: bool | None = None):
    """Gram from pre-factored residuals Rᵢ = Aᵢ @ UTᵢ.

    A: (N, out, k) compressed residual; UT: (N, k, in).  Callers that
    also run the Eq. 7 update can share one ``compressed_residual``.
    """
    N, out_d, kd = A.shape
    in_d = UT.shape[2]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_gram_kernel_left, n_clients=N, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(n_out, n_in, N, n_k),
        in_specs=[
            pl.BlockSpec((None, bo, bk), lambda o, j, i, k: (i, o, k)),  # A
            pl.BlockSpec((None, bk, bi), lambda o, j, i, k: (i, k, j)),  # Uᵀ
        ],
        out_specs=pl.BlockSpec((N, N), lambda o, j, i, k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bo, bi), jnp.float32),
                        pltpu.VMEM((N, bo, bi), jnp.float32),
                        pltpu.VMEM((N, N), jnp.float32)],
        interpret=resolve(interpret),
    )(A, UT)


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
        grid=(D // bd,),
        in_specs=[pl.BlockSpec((ca, bd), lambda k: (0, k)),
                  pl.BlockSpec((cb, bd), lambda k: (0, k))],
        out_specs=pl.BlockSpec((ca, cb), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ca, cb), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ca, cb), jnp.float32)],
        interpret=resolve(interpret),
    )(Ra, Rb)


def _gram_diag_kernel(w_ref, v_ref, p_ref, out_ref, gacc_ref,
                      *, n_clients: int, off: int = 0):
    o, j = pl.program_id(off), pl.program_id(off + 1)
    n_out, n_in = pl.num_programs(off), pl.num_programs(off + 1)

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


# --------------------------------------------------------------------------
# stacked-layer variants: the scan-layer axis L rides the grid in front,
# one launch per leaf covers all L layers (per-layer (N, N) output block,
# per-layer accumulator re-init — see _gram_tail's ``off``)
# --------------------------------------------------------------------------
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
        "pad layer dims to block multiples (ops stacked wrappers)")
    n_out, n_in, n_k = out_d // bo, in_d // bi, in_d // bk
    kernel = functools.partial(_gram_kernel_dense, n_clients=N, n_k=n_k,
                               off=1)
    return pl.pallas_call(
        kernel,
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
        interpret=resolve(interpret),
    )(W, V, P)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "bk",
                                             "interpret"))
def maecho_gram_left_stacked(A, UT, *, bo: int = 128, bi: int = 128,
                             bk: int = 128, interpret: bool | None = None):
    """Stacked Gram from pre-factored residuals Rₗᵢ = Aₗᵢ @ UTₗᵢ.

    A: (N, L, out, k) compressed residual; UT: (N, L, k, in).
    Returns (L, N, N); the compressed residual is shared with the
    stacked Eq. 7 kernel exactly like the per-layer path."""
    N, L, out_d, kd = A.shape
    in_d = UT.shape[3]
    bo, bi, bk = min(bo, out_d), min(bi, in_d), min(bk, kd)
    assert out_d % bo == 0 and in_d % bi == 0 and kd % bk == 0, (
        "pad layer dims / rank to block multiples")
    n_out, n_in, n_k = out_d // bo, in_d // bi, kd // bk
    kernel = functools.partial(_gram_kernel_left, n_clients=N, n_k=n_k,
                               off=1)
    return pl.pallas_call(
        kernel,
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
    kernel = functools.partial(_gram_diag_kernel, n_clients=N, off=1)
    return pl.pallas_call(
        kernel,
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
        interpret=resolve(interpret),
    )(W, V, p4)


@functools.partial(jax.jit, static_argnames=("bo", "bi", "interpret"))
def maecho_gram_diag(W, V, p, *, bo: int = 128, bi: int = 128,
                     interpret: bool | None = None):
    """Diagonal projectors.  W: (out, in); V: (N, out, in); p: (N, in)."""
    out_d, in_d = W.shape
    N = V.shape[0]
    bo, bi = min(bo, out_d), min(bi, in_d)
    assert out_d % bo == 0 and in_d % bi == 0, (
        "pad layer dims to block multiples")
    p3 = p.reshape(N, 1, in_d)
    kernel = functools.partial(_gram_diag_kernel, n_clients=N)
    return pl.pallas_call(
        kernel,
        grid=(out_d // bo, in_d // bi),
        in_specs=[
            pl.BlockSpec((bo, bi), lambda o, j: (o, j)),           # W
            pl.BlockSpec((N, bo, bi), lambda o, j: (0, o, j)),     # V
            pl.BlockSpec((N, 1, bi), lambda o, j: (0, 0, j)),      # p
        ],
        out_specs=pl.BlockSpec((N, N), lambda o, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=resolve(interpret),
    )(W, V, p3)
