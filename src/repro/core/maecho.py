"""MA-Echo — Algorithm 1 of the paper, as a composable JAX op.

Operates on *pytrees of layers*: each client contributes a pytree of
weight leaves plus a structurally matching pytree of projection leaves.
Faithful to the paper:

  W⁽⁰⁾ = init (vanilla average by default);  Vᵢ = Wᵢ
  repeat τ times, per layer l:
      Rᵢ  = (W − Vᵢ) Pᵢ                    (residual in client i's row space)
      α*  = argmin ½‖Σᵢ 2αᵢ Rᵢ‖²  on the capped simplex   (Eq. 6)
      W  += η · ( −Σᵢ 2αᵢ* Rᵢ )                            (Eq. 7)
      Vᵢ += Norm( (W − Vᵢ)(I − μ/(1+μ) Pᵢ) )              (Eq. 11)

Projection leaves may be:
  - 2-D (d_in, d_in): full projector (paper's form);
  - 1-D matching the in-axis: diagonal projector (used for embedding
    tables where the input space is the one-hot vocabulary — P is the
    client's token-support indicator);
  - scalar 1.0: full-rank "input is always live" projector, the bias /
    norm-parameter rule;
  - any of the above with a leading stacked-layer axis L, matching a
    weight leaf (L, …) — the scan-over-layers LLM layout.  The QP is
    then solved per scanned layer (vmap), exactly like the paper's
    per-layer loop.

Weight-leaf convention: ``convention="oi"`` (paper: W is (out, in), the
MLP/CNN models) or ``"io"`` (the LLM zoo: x @ W, W is (in, out)).

Backends — the ``backend`` argument of :func:`maecho_aggregate`:

  - ``"oracle"`` (default): the reference jnp path below.  Each outer
    iteration materializes the full (N, out, in) fp32 residual tensor
    Rᵢ = (W − Vᵢ)Pᵢ twice (once for the Eq. 6/7 Gram+update, once
    re-projected for Eq. 11) — 2·N·out·in fp32 of HBM traffic per
    layer per iteration that exists only to be contracted away.
  - ``"kernel"``: the fused streaming pipeline.  Eligible leaves (a
    2-D weight, with or without leading stacked-layer axes) run three
    Pallas passes per iteration — ``maecho_gram`` (Eq. 6 Gram,
    residual tiles formed in VMEM and contracted on the fly),
    ``maecho_update`` (Eq. 7) and ``maecho_v_update`` (Eq. 11) — so
    no residual tensor is ever resident in HBM.  Every leaf is a stack
    of L layers to the kernels: a stacked leaf's layer axes are
    flattened into the kernel grid's outermost dimension (one launch
    per pass covers all L scanned layers — the ``*_stacked`` kernels),
    and a 2-D leaf is the stack L = 1.  Factored ``{"U", "s"}``
    projectors stay factored through the compute: the
    (N, L, out, k) compressed residual replaces the full one and every
    GEMM chain drops from O(out·in²) to O(out·in·k).  Ineligible
    leaves (1-D biases, shapes below one tile) fall back to the oracle
    — dispatch happens at trace time, the whole τ-loop still jits as
    one program, and the fallback is surfaced once via
    ``ops.fallback_warn``.
  - ``"auto"``: ``"kernel"`` for leaves big enough to tile
    (min trailing dim ≥ 128), ``"oracle"`` otherwise.
  - ``"sharded"``: the same pipeline under ``shard_map`` over
    ``MAEchoConfig.mesh_axis``.  Eligible leaves (out-dim tile count
    divisible by the mesh-axis size — ``ops.sharded_ok``) split their
    out-rows: each device forms only its residual tiles, and ONE
    ``psum`` per leaf per outer iteration reconstructs the (L, N, N)
    Gram stack; the QP solve stays global and the Eq. 7/11 applies
    run purely on the owned rows (compressed-residual reuse intact).
    Ineligible leaves degrade to the single-device ``"auto"``
    dispatch.  Pass the mesh via ``maecho_aggregate(..., mesh=...)``
    (default: a 1-D mesh over every visible device).
  - ``"sharded2d"``: the 2-D (out × in) shard of the same pipeline.
    Eligible leaves (``rules.sharded_ok2d`` — BOTH trailing dims'
    tile counts divide their axis group) split out-rows over
    ``MAEchoConfig.mesh_axis`` AND in-columns over
    ``MAEchoConfig.mesh_in_axis`` ("model"): each device forms only
    its (out/osz, in/isz) residual tile, partial Grams are psum'd
    over BOTH axis groups in ONE collective per leaf per outer
    iteration, and the applies stay row/col-local.  This covers
    leaves whose out-dim alone is too small to span the fleet — the
    device count factors as osz × isz instead of dividing the
    out-tiles 1-D.  Leaves that fail the 2-D gate degrade to the 1-D
    ``"sharded"`` shard over ``mesh_axis``, then to the ``"auto"``
    rule (each fallback warned once).  Without a mesh it runs on every
    visible device, factored as squarely as their count allows.

Placement: the plan is compiled from shapes before anything moves, and
:func:`place` puts each client leaf from the host straight into the
shards its plan's layout gives (``LeafPlan.specs``), stacks the clients
on the device and makes W⁽⁰⁾ there, so no device holds a whole stack it
would only give away again.

Routing is compiled ONCE per (treedef, shapes, conventions,
stack_levels, backend, mesh, config) by ``core.plan.compile_plan``
into a frozen ``AggPlan`` — one ``LeafPlan`` per leaf carrying the
route, kernel layout, effective tile size and psum axes.  The outer
loop below is a pure executor over that plan, and
:func:`dispatch_summary` is a view of the same compiled object, so
the coverage it reports is definitionally the coverage that runs.

Ragged participation (``maecho_aggregate(..., client_mask=...)``): an
optional per-leaf boolean client mask rides the batched QP's validity
masking — masked-out clients get exactly α = 0 (their residuals never
touch the Eq. 7 update), their anchors Vᵢ are frozen, and the result
matches aggregating the participating subset alone (same init point).

The QP and the padding logic (``repro.kernels.ops._pad_to``, zero
padding is exact for all three passes) are shared between backends;
the kernels run in the Pallas interpreter on a CPU backend and lower
to Mosaic on a TPU (``kernels.env``).

Batched QP (``MAEchoConfig.qp_batched``, default on): each outer
iteration runs in three phases — every leaf (and every scanned layer
of a stacked leaf) first emits its (N, N) Gram into one stacked
(L, N, N) tensor, a **single** vmapped PGD solve
(``qp.solve_qp_batched``) produces all τ vectors at once, and the
α rows are scattered back through the per-leaf Eq. 7 / Eq. 11 updates
(reusing the residual / compressed-residual context computed in the
gram phase).  ``qp_batched=False`` restores the sequential
one-PGD-per-leaf loop — same math, L solves instead of one.

Memory trade-off: the batched path keeps every leaf's reuse context
(on the oracle backend, the (N, out, in) fp32 residual) live across
the stacked solve, so peak residency grows from one leaf's residual
to ~N× the whole model in fp32.  Fine for the paper-scale models
this τ-loop targets; for LLM-scale trees where that doesn't fit, set
``qp_batched=False`` (sequential frees each leaf's residual before
the next gram) or use the factored/kernel paths whose contexts are
the (N, out, k) compressed residuals.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import plan as plan_mod
from repro.core import qp as qp_mod
from repro.core.plan import AggPlan, LeafPlan, compile_plan
from repro.utils import spans, trees

Pytree = Any


@dataclasses.dataclass(frozen=True)
class MAEchoConfig:
    tau: int = 30                 # outer iterations
    eta: float = 1.0              # step size on W
    C: float = 1.0                # simplex cap (paper: C ∈ [1/N, 1])
    mu: float = 1.0               # Eq. 8 penalty; factor μ/(1+μ)
    norm: bool = False            # Norm(·) row-normalisation of V updates
    qp_iters: int = 200
    init: str = "average"         # average | first | random
    eps: float = 1e-12
    qp_batched: bool = True       # one stacked PGD solve per outer iter
    mesh_axis: str = "data"       # out-row shard axis ("sharded"/"2d")
    mesh_in_axis: str = "model"   # in-column shard axis ("sharded2d")
    # kernel blocks of the (non-sharded) streaming pipeline: 0 = derived
    # per leaf by the plan from its extents, N and the VMEM budget
    # (core.plan.kernel_blocks); an edge > 0 overrides them with that
    # cube and pads to it — the interpret-mode benches use 512 to
    # amortize per-step interpreter overhead.  The sharded pipelines
    # always derive theirs (their out-padding granularity is
    # DEFAULT_BLOCK × axis_size).
    kernel_block: int = 0
    # client-axis chunk for the Gram/apply sweeps; 0 = unchunked.  When
    # set, eligible leaves accumulate their (N, N) Gram over blocks of
    # ``client_chunk`` clients (only that many residuals resident per
    # step — the cross-device large-N mode) and the QP tiles its
    # Gram-vector products over the same block edge.  Clamped to N per
    # leaf at plan time; composes with "sharded" (rows × client
    # blocks) but not "sharded2d" (degrades to the 1-D shard, warned).
    client_chunk: int = 0


# --------------------------------------------------------------------------
# per-leaf algebra
# --------------------------------------------------------------------------
def _apply_P(delta, P, convention: str):
    """delta·P respecting the in-axis convention and P's kind.

    P kinds: scalar (bias rule), 1-D diag (embedding token support),
    2-D full matrix, or FACTORED {"U": (in, k), "s": (k,)} with
    P = U·diag(s)·Uᵀ — the beyond-paper optimisation: the Eq. 7 GEMM
    chain drops from O(out·in²) to O(out·in·k), and communication from
    in² to in·(k+1) (paper Table 6 shows the projectors are low-rank;
    we keep them factored through the *compute*, not just the wire).
    """
    if isinstance(P, dict):                 # factored projector
        U = P["U"]
        s = P["s"]
        if delta.ndim == 1:
            return ((delta @ U) * s) @ U.T
        if convention == "oi":
            return ((delta @ U) * s) @ U.T  # (out,k)·(k)·(k,in)
        return U @ (s[:, None] * (U.T @ delta))
    if P.ndim == 0:                         # full projector (bias rule)
        return delta * P
    if P.ndim == 1:                         # diagonal projector on in-axis
        if delta.ndim == 1:
            return delta * P
        return delta * (P[None, :] if convention == "oi" else P[:, None])
    # full matrix projector
    if delta.ndim == 1:
        return delta @ P
    if convention == "oi":
        return delta @ P                    # (out,in)@(in,in)
    return P @ delta                        # (in,in)@(in,out)


def _qp_alpha(G, cfg: MAEchoConfig, mask=None):
    """Eq. 6 dual QP for the sequential (per-leaf) path.  Delegates to
    ``qp.solve_qp`` — the same ``_pgd_masked`` body the batched solver
    vmaps, so batched/sequential parity is structural, not maintained
    by hand.  (The jitted wrapper traces inline under the enclosing
    jit; the whole aggregation still compiles as one program.)
    ``mask`` is the leaf's participation mask (ragged cohorts)."""
    return qp_mod.solve_qp(G, cfg.C, iters=cfg.qp_iters, mask=mask,
                           row_block=cfg.client_chunk)


def _flatten_stack(W, V, P, levels: int):
    """Collapse ``levels`` leading stacked-layer axes into one flat L
    axis for the kernel grid (``levels`` 0 gives L = 1, ``lead`` ()).
    Returns ``(Wf, Vf, Pf, lead)`` with Wf (L, out, in),
    Vf (N, L, out, in), Pf stacked per kind, and ``lead`` the original
    leading shape for un-flattening."""
    lead = W.shape[:levels]
    Wf = W.reshape((-1,) + W.shape[levels:])
    Vf = V.reshape(V.shape[:1] + (-1,) + V.shape[1 + levels:])

    def flat_p(x):
        return x.reshape(x.shape[:1] + (-1,) + x.shape[1 + levels:])

    Pf = ({k: flat_p(v) for k, v in P.items()} if isinstance(P, dict)
          else flat_p(P))
    return Wf, Vf, Pf, lead


def _to_kernel_layout(W, V, P, convention: str, levels: int = 0):
    """The kernel pipelines are "oi"-native; "io" leaves are transposed
    around the call (XLA fuses the transposes into the kernels' operand
    loads).  Shared by every kernel gram half — one copy of the layout
    contract; stacked leaves transpose the trailing two axes only."""
    if convention != "io":
        return W, V, P
    # oracle applies delta·P from the left for "io": (PᵢΔ)ᵀ = ΔᵀPᵢᵀ
    Pk = jnp.swapaxes(P, -1, -2) if (not isinstance(P, dict)
                                     and P.ndim == 3 + levels) else P
    return jnp.swapaxes(W, -1, -2), jnp.swapaxes(V, -1, -2), Pk


def _leaf_gram_stacked(W, V, P, cfg: MAEchoConfig, convention: str,
                       route: str, mesh, levels: int,
                       block: int = 0, blocks=None):
    """Gram half of the kernel pipelines: the ``levels`` leading layer
    axes are flattened into the kernel grid's outer dimension — ONE
    launch (and, sharded, ONE psum carrying the (L, N, N) stack)
    covers every scanned layer; a 2-D leaf (``levels`` 0) is the stack
    L = 1.  ``route`` is the leaf plan's: "kernel" | "sharded" |
    "sharded2d"; ``block`` its padding edge on "kernel" and ``blocks``
    the kernels' blocks (the plan is the one source of the tiling).
    Returns ``(G, ctx)`` with G carrying the original leading axes
    before its trailing (N, N), matching the oracle-vmap layout."""
    from repro.kernels import ops

    Wk, Vk, Pk = _to_kernel_layout(W, V, P, convention, levels)
    Wk, Vk, Pk, lead = _flatten_stack(Wk, Vk, Pk, levels)
    if route == "sharded2d":
        G, ctx = ops.maecho_sharded2d_gram_stacked(
            Wk, Vk, Pk, mesh=mesh, axis_out=cfg.mesh_axis,
            axis_in=cfg.mesh_in_axis, blocks=blocks)
    elif route == "sharded":
        G, ctx = ops.maecho_sharded_gram_stacked(Wk, Vk, Pk, mesh=mesh,
                                                 axis=cfg.mesh_axis,
                                                 blocks=blocks)
    else:
        G, ctx = ops.maecho_streaming_gram_stacked(
            Wk, Vk, Pk, block=block or ops.DEFAULT_BLOCK, blocks=blocks)
    return G.reshape(lead + G.shape[-2:]), ("stk", route, lead, ctx)


def _leaf_apply_stacked(alpha, ctx, cfg: MAEchoConfig,
                        convention: str, mesh):
    """Update half of the kernel pipelines: per-layer Eq. 7 + Eq. 11
    from the flattened-grid context of :func:`_leaf_gram_stacked`
    (which carries the gram's blocks).  ``alpha`` carries the leaf's
    leading stack axes before its trailing N (the QP batch layout)."""
    from repro.kernels import ops

    _, route, lead, inner = ctx
    af = alpha.reshape((-1,) + alpha.shape[-1:])
    kw = dict(eta=cfg.eta, frac=cfg.mu / (1.0 + cfg.mu), norm=cfg.norm,
              eps=cfg.eps)
    if route == "sharded2d":
        Wn, Vn = ops.maecho_sharded2d_apply_stacked(
            af, inner, mesh=mesh, axis_out=cfg.mesh_axis,
            axis_in=cfg.mesh_in_axis, **kw)
    elif route == "sharded":
        Wn, Vn = ops.maecho_sharded_apply_stacked(
            af, inner, mesh=mesh, axis=cfg.mesh_axis, **kw)
    else:
        Wn, Vn = ops.maecho_streaming_apply_stacked(af, inner, **kw)
    Wn = Wn.reshape(lead + Wn.shape[-2:])
    Vn = Vn.reshape(Vn.shape[:1] + lead + Vn.shape[-2:])
    if convention == "io":
        return jnp.swapaxes(Wn, -1, -2), jnp.swapaxes(Vn, -1, -2)
    return Wn, Vn


def _leaf_gram_chunked(W, V, P, lp: LeafPlan, cfg: MAEchoConfig,
                       convention: str, mesh):
    """Gram half for a leaf with a compiled ``client_chunk``: the
    (N, N) Gram accumulates over blocks of clients, so peak residual
    residency is O(chunk), not O(N) — the cross-device large-N mode.
    The chunk sweep composes with the leaf's route: "kernel" streams
    each (chunk, chunk) pair block through the Pallas cross-Gram,
    "sharded" additionally splits out-rows over ``cfg.mesh_axis``
    (still ONE psum per leaf per iteration), everything else — the
    oracle and the sub-tile shapes — runs the jnp chunk sweep."""
    from repro.kernels import ops

    chunk = lp.client_chunk
    if lp.levels > 0:
        Wf, Vf, Pf, lead = _flatten_stack(W, V, P, lp.levels)
        Wk, Vk, Pk = _to_kernel_layout(Wf, Vf, Pf, convention, levels=1)
        if lp.route == "sharded":
            G, ctx = ops.maecho_sharded_gram_chunked(
                Wk, Vk, Pk, mesh=mesh, axis=cfg.mesh_axis, chunk=chunk,
                stacked=True)
        else:
            G, ctx = ops.maecho_streaming_gram_chunked_stacked(
                Wk, Vk, Pk, chunk=chunk)
        return (G.reshape(lead + G.shape[-2:]),
                ("stkchunk", lp.route, lead, ctx))
    Wk, Vk, Pk = _to_kernel_layout(W, V, P, convention)
    if lp.route == "sharded":
        G, ctx = ops.maecho_sharded_gram_chunked(
            Wk, Vk, Pk, mesh=mesh, axis=cfg.mesh_axis, chunk=chunk)
    else:
        G, ctx = ops.maecho_streaming_gram_chunked(
            Wk, Vk, Pk, chunk=chunk,
            use_kernel=(lp.route == "kernel"))
    return G, ("chunkroute", lp.route, ctx)


def _leaf_apply_chunked(alpha, ctx, cfg: MAEchoConfig, convention: str,
                        mesh):
    """Update half for a chunked leaf: Eq. 7 accumulates over chunk
    residuals, Eq. 11 rebuilds each chunk's anchors — the full-N
    residual never materializes."""
    from repro.kernels import ops

    kw = dict(eta=cfg.eta, frac=cfg.mu / (1.0 + cfg.mu), norm=cfg.norm,
              eps=cfg.eps)
    if ctx[0] == "stkchunk":
        _, route, lead, inner = ctx
        af = alpha.reshape((-1,) + alpha.shape[-1:])
        if route == "sharded":
            Wn, Vn = ops.maecho_sharded_apply_chunked(
                af, inner, mesh=mesh, axis=cfg.mesh_axis, stacked=True,
                **kw)
        else:
            Wn, Vn = ops.maecho_streaming_apply_chunked_stacked(
                af, inner, **kw)
        if convention == "io":
            Wn, Vn = jnp.swapaxes(Wn, -1, -2), jnp.swapaxes(Vn, -1, -2)
        return (Wn.reshape(lead + Wn.shape[-2:]),
                Vn.reshape(Vn.shape[:1] + lead + Vn.shape[-2:]))
    _, route, inner = ctx
    if route == "sharded":
        Wn, Vn = ops.maecho_sharded_apply_chunked(
            alpha, inner, mesh=mesh, axis=cfg.mesh_axis, **kw)
    else:
        Wn, Vn = ops.maecho_streaming_apply_chunked(alpha, inner, **kw)
    if convention == "io":
        return Wn.T, jnp.swapaxes(Vn, 1, 2)
    return Wn, Vn


def _leaf_gram_oracle(W, V, P, convention: str):
    """Reference gram half: materializes the residual once and returns
    it as the reuse context for :func:`_leaf_apply_oracle` (the same
    tensor the fused step shared between its Gram and Eq. 7)."""
    N = V.shape[0]
    R = jax.vmap(lambda v, p: _apply_P(W - v, p, convention))(V, P)  # (N, ...)
    Rf = R.reshape(N, -1).astype(jnp.float32)
    return Rf @ Rf.T, R                                            # (N, N)


def _leaf_apply_oracle(W, V, P, R, alpha, cfg: MAEchoConfig,
                       convention: str):
    """Reference update half: Eq. 7 from the cached residual, then the
    Eq. 11 anchor update."""
    D = -2.0 * jnp.tensordot(alpha, R.astype(jnp.float32), axes=(0, 0))
    W_new = (W.astype(jnp.float32) + cfg.eta * D).astype(W.dtype)

    # Eq. 11: V_i += Norm((W' − V_i)(I − μ/(1+μ) P_i))
    frac = cfg.mu / (1.0 + cfg.mu)

    def v_update(v, p):
        delta = W_new - v
        U = delta - frac * _apply_P(delta, p, convention)
        if cfg.norm:
            ax = -1 if convention == "oi" else 0
            nrm = jnp.linalg.norm(
                U.astype(jnp.float32), axis=ax, keepdims=True)
            U = U / jnp.maximum(nrm, cfg.eps).astype(U.dtype)
        return v + U

    V_new = jax.vmap(v_update)(V, P)
    return W_new, V_new


# --------------------------------------------------------------------------
# the executor: per-leaf gram/apply keyed purely off the compiled plan
# --------------------------------------------------------------------------
def _leaf_gram(W, V, P, lp: LeafPlan, cfg: MAEchoConfig,
               convention: str, mesh=None):
    """Gram phase for one leaf, dispatched on its compiled
    ``LeafPlan.route`` — no shape inspection happens here, the plan is
    the single source of truth.

    Returns ``(G, ctx)``: G carries any stacked-layer axes in front of
    its trailing (N, N) — the batched caller flattens those into the
    QP batch axis — and ``ctx`` is the per-leaf reuse payload for
    :func:`_leaf_apply` (the oracle residual, or the kernel/sharded
    pipelines' padded-operand context)."""
    if lp.client_chunk:
        return _leaf_gram_chunked(W, V, P, lp, cfg, convention, mesh)
    route = lp.route
    if route == "oracle":
        if lp.levels > 0:
            # any number of leading stacked-layer axes collapses to
            # ONE flat scan axis before a single vmap (nested vmaps
            # over the oracle trip XLA:CPU's simplifier on dense
            # projector contractions); maecho_aggregate pre-flattens
            # multi-level stacks, but direct executor callers (the
            # dryrun driver) hand levels >= 2 leaves straight in
            Wf, Vf, Pf, lead = _flatten_stack(W, V, P, lp.levels)
            G, R = jax.vmap(
                lambda w, v, p: _leaf_gram_oracle(w, v, p, convention),
                in_axes=(0, 1, 1), out_axes=0)(Wf, Vf, Pf)
            return G.reshape(lead + G.shape[1:]), R
        return _leaf_gram_oracle(W, V, P, convention)
    spans.count("maecho.kernel_blocks", route=route, kind=lp.kind,
                **lp.blocks._asdict())
    return _leaf_gram_stacked(W, V, P, cfg, convention, route, mesh,
                              lp.levels, lp.block, lp.blocks)


def _leaf_apply(W, V, P, ctx, alpha, lp: LeafPlan, cfg: MAEchoConfig,
                convention: str, mesh=None):
    """Apply phase for one leaf: scatter its rows of the stacked solve
    back through Eq. 7 / Eq. 11 on the route the plan compiled.
    ``alpha`` carries the leaf's stacked-layer axes in front of its
    trailing N, mirroring the gram layout."""
    if lp.client_chunk:
        return _leaf_apply_chunked(alpha, ctx, cfg, convention, mesh)
    route = lp.route
    if route == "oracle":
        if lp.levels > 0:
            # ctx is the flat (L, N, ...) residual stack from
            # _leaf_gram; alpha carries the original lead axes
            Wf, Vf, Pf, lead = _flatten_stack(W, V, P, lp.levels)
            af = alpha.reshape((-1,) + alpha.shape[-1:])
            Wn, Vn = jax.vmap(
                lambda w, v, p, r, a: _leaf_apply_oracle(
                    w, v, p, r, a, cfg, convention),
                in_axes=(0, 1, 1, 0, 0), out_axes=(0, 1))(Wf, Vf, Pf,
                                                          ctx, af)
            return (Wn.reshape(lead + Wn.shape[1:]),
                    Vn.reshape(Vn.shape[:1] + lead + Vn.shape[2:]))
        return _leaf_apply_oracle(W, V, P, ctx, alpha, cfg, convention)
    return _leaf_apply_stacked(alpha, ctx, cfg, convention, mesh)


def _scope(phase: str, lp: Optional[LeafPlan] = None):
    """``maecho.<phase>``, then the leaf's path, on JAX's name stack
    (``maecho.gram/layers.wq``): the op names by which a profile or a
    compile dump groups the executor's work."""
    name = f"maecho.{phase}"
    return jax.named_scope(name if lp is None else f"{name}/{lp.path}")


def _leaf_sequential(W, V, P, lp: LeafPlan, cfg: MAEchoConfig,
                     convention: str, mesh=None, mask=None):
    """One Algorithm-1 iteration for a single leaf on the sequential-QP
    path (``qp_batched=False``): gram → own PGD solve (per scanned
    layer for stacked leaves, matching the paper's per-layer loop) →
    apply.  The participation mask is shared by every scanned layer.
    Returns (W', V')."""
    with _scope("gram", lp):
        G, ctx = _leaf_gram(W, V, P, lp, cfg, convention, mesh)
    with _scope("qp", lp):
        if lp.levels > 0:
            Gf = G.reshape((-1,) + G.shape[-2:])
            alpha = jax.vmap(lambda g: _qp_alpha(g, cfg, mask))(Gf)
            alpha = alpha.reshape(G.shape[:-2] + alpha.shape[-1:])
        else:
            alpha = _qp_alpha(G, cfg, mask)
    with _scope("apply", lp):
        return _leaf_apply(W, V, P, ctx, alpha, lp, cfg, convention, mesh)


# --------------------------------------------------------------------------
# full aggregation
# --------------------------------------------------------------------------
def default_projections(client_weights: list[Pytree]) -> list[Pytree]:
    """Scalar full projectors everywhere (degenerates MA-Echo toward a
    consensus pull; used when a leaf has no feature statistics)."""
    return [trees.tree_map(lambda x: jnp.ones((), x.dtype), w)
            for w in client_weights]


def init_global(client_weights: list[Pytree], how: str,
                rng: Optional[jax.Array] = None) -> Pytree:
    n = len(client_weights)
    if how == "average":
        out = client_weights[0]
        for w in client_weights[1:]:
            out = trees.tree_add(out, w)
        return trees.tree_scale(out, 1.0 / n)
    if how == "first":
        return trees.tree_map(lambda x: x, client_weights[0])
    if how == "random":
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        leaves, treedef = jax.tree_util.tree_flatten(client_weights[0])
        keys = jax.random.split(rng, len(leaves))
        new = [jax.random.normal(k, x.shape, x.dtype) *
               (jnp.std(x) + 1e-8) for k, x in zip(keys, leaves)]
        return jax.tree_util.tree_unflatten(treedef, new)
    raise ValueError(f"unknown init {how!r}")


def _hold_layout(flatW, flatV, flatP, plan: AggPlan, mesh):
    """The loop's carried W and V, held in the layout ``place`` put
    them in (:meth:`LeafPlan.specs`)."""
    from jax.sharding import NamedSharding

    W, V = [], []
    for w, v, p, lp in zip(flatW, flatV, flatP, plan.leaves):
        ws, vs, _ = lp.specs(mesh, plan.convention, w.ndim, p)
        W.append(jax.lax.with_sharding_constraint(w, NamedSharding(mesh,
                                                                   ws)))
        V.append(jax.lax.with_sharding_constraint(v, NamedSharding(mesh,
                                                                   vs)))
    return W, V


@partial(jax.jit, static_argnames=("cfg", "convention", "plan",
                                   "mesh"), donate_argnames=("V0",))
def _maecho_jit(W0, V0, P, cfg: MAEchoConfig, convention: str,
                plan: AggPlan, mesh=None, masks=None):
    """The pure executor: runs the τ-loop over the COMPILED plan —
    every per-leaf decision was already frozen into ``plan.leaves``
    (one :class:`LeafPlan` per flattened leaf, same order), so the
    loop body below contains zero routing logic.

    ``V0`` is donated: the anchors are the largest state (N × model
    fp32), and the V' output reuses their buffers.  Callers pass a
    stack they own (``maecho_aggregate`` stacks it fresh)."""
    def outer(_, state):
        W, V = state
        flatW, treedef = jax.tree_util.tree_flatten(W)
        flatV = treedef.flatten_up_to(V)
        flatP = treedef.flatten_up_to(P)
        flatM = (list(masks) if masks is not None
                 else [None] * len(flatW))
        if mesh is not None:
            flatW, flatV = _hold_layout(flatW, flatV, flatP, plan, mesh)
        if cfg.qp_batched:
            # Phase 1: every leaf's (and every scanned layer's) Eq. 6
            # Gram, assembled into one (L, N, N) stack.  N — the
            # client count — is shared by construction inside one
            # aggregate call, so stack_grams degenerates to a pure
            # concat here (its padding serves the ragged case).
            grams, ctxs = [], []
            for w, v, p, lp in zip(flatW, flatV, flatP, plan.leaves):
                with _scope("gram", lp):
                    g, ctx = _leaf_gram(w, v, p, lp, cfg, convention,
                                        mesh)
                grams.append(g)
                ctxs.append(ctx)
            # Phase 2: ONE vmapped PGD solve for the whole batch —
            # with ragged participation, each leaf's client mask
            # (broadcast over its scanned layers) rides the solver's
            # validity masking instead of the prefix n_valid.
            with _scope("qp"):
                Gstack, n_valid = qp_mod.stack_grams(grams)
                if masks is None:
                    alphas = qp_mod.solve_qp_batched(
                        Gstack, cfg.C, cfg.qp_iters, n_valid,
                        row_block=cfg.client_chunk)
                else:
                    rows = [jnp.broadcast_to(
                                m, (math.prod(g.shape[:-2]),) + m.shape)
                            for g, m in zip(grams, flatM)]
                    alphas = qp_mod.solve_qp_batched(
                        Gstack, cfg.C, cfg.qp_iters,
                        mask=jnp.concatenate(rows, 0),
                        row_block=cfg.client_chunk)
            # Phase 3: … scattered back through each leaf's Eq. 7/11.
            out, ofs = [], 0
            for w, v, p, lp, ctx, g in zip(flatW, flatV, flatP,
                                           plan.leaves, ctxs, grams):
                cnt = math.prod(g.shape[:-2])
                with _scope("apply", lp):
                    a = alphas[ofs:ofs + cnt].reshape(
                        g.shape[:-2] + alphas.shape[-1:])
                    out.append(_leaf_apply(w, v, p, ctx, a, lp, cfg,
                                           convention, mesh))
                ofs += cnt
        else:
            out = [_leaf_sequential(w, v, p, lp, cfg, convention,
                                    mesh, m)
                   for w, v, p, lp, m in zip(flatW, flatV, flatP,
                                             plan.leaves, flatM)]
        if masks is not None:
            # non-participants contribute nothing (α = 0 via the QP
            # mask) and their anchors stay put — the run matches
            # aggregating the participating subset alone
            with _scope("apply"):
                out = [(w2, jnp.where(
                            m.reshape((-1,) + (1,) * (v1.ndim - 1)),
                            v2, v1))
                       for (w2, v2), v1, m in zip(out, flatV, flatM)]
        W = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        V = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        return W, V

    if cfg.tau <= 4:
        # unrolled (also gives the roofline probe loop-free HLO)
        state = (W0, V0)
        for t in range(cfg.tau):
            state = outer(t, state)
        return state
    W, V = jax.lax.fori_loop(0, cfg.tau, outer, (W0, V0))
    return W, V


def dispatch_summary(W0: Pytree, P: Pytree, levels_tree: Pytree,
                     cfg: MAEchoConfig = MAEchoConfig(),
                     convention: str = "oi", backend: str = "oracle",
                     mesh=None):
    """Per-leaf compute-path report: a VIEW over the compiled
    :class:`AggPlan` — the same object the executor dispatches on, so
    the route reported here is definitionally the route that runs
    (the pre-plan implementation maintained a second copy of the
    routing rules, which could drift).

    ``W0`` / ``P`` are the global-weight and *stacked* (leading client
    axis) projector trees — arrays or ``jax.ShapeDtypeStruct``s both
    work, routing is static-shape-only.  Returns ``(per_leaf,
    counts)``: ``per_leaf`` is a list of ``(path, levels, route)``
    with route in ``plan.ROUTES`` ({"oracle", "kernel", "sharded",
    "sharded2d"}); ``counts`` maps route -> leaf count,
    plus a ``"chunked"`` entry (the number of leaves sweeping their
    client axis in ``cfg.client_chunk`` blocks) whenever chunking is
    active.
    """
    plan = compile_plan(W0, P, levels_tree, cfg, convention, backend,
                        mesh)
    counts = plan.route_counts()
    chunked = sum(1 for lp in plan.leaves if lp.client_chunk)
    if chunked:
        counts["chunked"] = chunked
    return plan.per_leaf(), counts


def coverage_report(W0, Pp, levels_tree, macfg, backend: str,
                    mesh=None, convention: str = "io") -> dict:
    """Print the per-backend leaf-coverage summary: the compiled
    ``AggPlan``'s per-leaf routes (:func:`dispatch_summary` is a view
    over the same plan the executor dispatches on), so a leaf
    silently degraded to the oracle is visible instead of buried in a
    trace-time warning.

    Beyond route counts, every leaf gets a detail line with the
    ``LeafPlan`` knobs that decide its memory/collective shape: the
    mesh axes its Gram psums over, the kernels' (bo, bi, bk) blocks,
    and the client-chunk size (``-`` where the knob is off), visible
    before a long production compile (``launch.dryrun_agg``,
    ``chip_smoke.py``)."""
    per_leaf, counts = dispatch_summary(W0, Pp, levels_tree, macfg,
                                        convention, backend, mesh)
    # same memoized plan the executor dispatches on — per-leaf knobs
    plan = compile_plan(W0, Pp, levels_tree, macfg, convention,
                        backend, mesh)
    total = len(per_leaf)
    summary = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    print(f"[coverage] backend={backend}: {total} leaves ({summary})")
    for lp in plan.leaves:
        axes = ",".join(lp.psum_axes) if lp.psum_axes else "-"
        tile = ("x".join(map(str, lp.blocks[:3])) if lp.blocks
                else "-")
        print(f"[coverage]   {lp.path}: route={lp.route} "
              f"psum_axes={axes} tile={tile} "
              f"chunk={lp.client_chunk or '-'}")
    if backend != "oracle":
        for path, lv, route in per_leaf:
            if route == "oracle":
                print(f"[coverage]   oracle fallback: {path}"
                      f" (stack_levels={lv})")
    return counts


def _default_mesh(axis_name: str, in_axis_name: Optional[str] = None):
    """Mesh over every visible device, so the sharded backends run
    without mesh plumbing.  Without ``in_axis_name`` (``"sharded"``) it
    is 1-D.  With it (``"sharded2d"``) the device count factors as
    squarely as it allows, the larger factor on the out-row axis: 4
    devices → (2, 2), 8 → (4, 2), 1 → (1, 1)."""
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices())
    if in_axis_name is None:
        return Mesh(devs, (axis_name,))
    return Mesh(devs.reshape(mesh_grid(len(devs))),
                (axis_name, in_axis_name))


def mesh_grid(n: int) -> tuple:
    """(out-row, in-column) axis sizes of ``n`` devices, factored as
    squarely as ``n`` allows, the larger factor first."""
    n_in = max(d for d in range(1, math.isqrt(n) + 1) if n % d == 0)
    return n // n_in, n_in


@partial(jax.jit, static_argnums=0)
def _stack_into(sharding, *parts):
    """The clients' copies of one leaf, stacked on a new client axis in
    ``sharding``: each device stacks its own shards, gathering from its
    peers the parts ``place`` sent them."""
    out = jnp.stack(parts, 0)
    if sharding is None:
        return out
    return jax.lax.with_sharding_constraint(out, sharding)


@partial(jax.jit, static_argnums=0)
def _average_clients(sharding, V):
    """``init_global``'s average over the stacked client axis, in its
    order of operations (the same bits), in ``sharding``."""
    out = V[0]
    for i in range(1, V.shape[0]):
        out = out + V[i]
    out = out * (1.0 / V.shape[0])
    if sharding is None:
        return out
    return jax.lax.with_sharding_constraint(out, sharding)


def _moved_bytes(x, sharding, counts: dict) -> None:
    """Add to ``counts`` (device id -> bytes) what putting ``x`` into
    ``sharding`` (``None``: the default device) moves onto each device;
    nothing where ``x`` already lies there."""
    if isinstance(x, jax.Array) and (
            sharding is None or x.sharding.is_equivalent_to(sharding,
                                                            x.ndim)):
        return
    if sharding is None:
        shard, devices = x.shape, [jax.devices()[0]]
    else:
        shard, devices = sharding.shard_shape(x.shape), sharding.device_set
    nbytes = math.prod(shard) * jnp.dtype(x.dtype).itemsize
    for d in devices:
        counts[d.id] = counts.get(d.id, 0) + nbytes


def _spread(spec, shape, mesh):
    """``spec`` with each mesh axis it leaves out put on the largest dim
    it leaves whole that the axis divides: the spec a host array is sent
    in, so that every device receives distinct bytes from the host, and
    the devices that hold the same shard fill it in from each other."""
    from jax.sharding import PartitionSpec

    spec = list(spec)
    used = {a for s in spec if s is not None
            for a in ((s,) if isinstance(s, str) else s)}
    for name, size in mesh.shape.items():
        free = [i for i, s in enumerate(spec)
                if s is None and shape[i] % size == 0]
        if name not in used and size > 1 and free:
            spec[max(free, key=lambda i: shape[i])] = name
    return PartitionSpec(*spec)


def place(client_weights: list, projections: list, plan: AggPlan, mesh,
          init: str = "average", rng=None, init_point=None):
    """The executor's operands, ``(W0, V0, P)``, on the device in the
    layout the plan's leaves take (:meth:`LeafPlan.specs`): each
    client leaf goes from where it lies (the host, as a rule) straight
    into its shards, the clients are stacked on the device, and W0 is
    made there from the placed anchors, so no device holds more than
    its shards.  Where several devices hold the same shard, each
    receives a distinct part of it (:func:`_spread`) and the stacking
    fills in the rest between devices.  With ``mesh=None`` everything
    lands on the default device.  Leaves keep their stack axes; the
    caller flattens them.  Records the counter ``maecho.place_bytes``
    (``device``, ``n``): the bytes each device receives from where the
    leaves lie."""
    from jax.sharding import NamedSharding

    treedef = jax.tree_util.tree_structure(client_weights[0])
    flat_c = [treedef.flatten_up_to(c) for c in client_weights]
    flat_p = [treedef.flatten_up_to(p) for p in projections]
    moved: dict = {}

    def put(xs, spec):
        sh = one = None
        if mesh is not None:
            sh = NamedSharding(mesh, spec)
            one = NamedSharding(mesh, _spread(spec[1:], xs[0].shape, mesh))
        parts = []
        for x in xs:
            _moved_bytes(x, one, moved)
            parts.append(jax.device_put(x, one))
        return _stack_into(sh, *parts)

    V0, P, specs = [], [], []
    for i, lp in enumerate(plan.leaves):
        w = flat_c[0][i]
        p_tree = flat_p[0][i]
        ws, vs, ps = lp.specs(mesh, plan.convention, len(w.shape),
                              _stacked_shape(p_tree, len(flat_p)))
        specs.append(ws)
        V0.append(put([c[i] for c in flat_c], vs))
        if isinstance(p_tree, dict):
            P.append({k: put([p[i][k] for p in flat_p], ps[k])
                      for k in p_tree})
        else:
            P.append(put([p[i] for p in flat_p], ps))
    shardings = [None if mesh is None else NamedSharding(mesh, s)
                 for s in specs]
    if init_point is not None:
        W0 = treedef.flatten_up_to(init_point)
        for w, sh in zip(W0, shardings):
            _moved_bytes(w, sh, moved)
    elif init == "average":
        W0 = [_average_clients(sh, v) for sh, v in zip(shardings, V0)]
    else:
        views = [jax.tree_util.tree_unflatten(treedef, [v[i] for v in V0])
                 for i in range(len(client_weights))]
        W0 = treedef.flatten_up_to(init_global(views, init, rng))
    W0 = [jax.device_put(w, sh) for w, sh in zip(W0, shardings)]
    for dev, nbytes in sorted(moved.items()):
        spans.count("maecho.place_bytes", nbytes, device=dev)
    return tuple(jax.tree_util.tree_unflatten(treedef, x)
                 for x in (W0, V0, P))


def _flat_shape(x, levels: int, lead: tuple = ()):
    """Shape-only stand-in of ``x`` with ``lead`` prepended and its
    ``levels`` leading stack axes collapsed into one (where
    ``levels`` > 1, as the executor takes it)."""
    shape = tuple(x.shape)
    if levels > 1:
        shape = (math.prod(shape[:levels]),) + shape[levels:]
    return jax.ShapeDtypeStruct(lead + shape, jnp.dtype(x.dtype))


def _stacked_shape(p, n: int, levels: int = 0):
    """Shape-only stand-in of one client's projector leaf (or factored
    dict) stacked over ``n`` clients."""
    if isinstance(p, dict):
        return {k: _flat_shape(v, levels, (n,)) for k, v in p.items()}
    return _flat_shape(p, levels, (n,))


def _normalize_client_mask(client_mask, W0, n_clients: int):
    """Per-leaf (N,) boolean masks, aligned with ``tree_flatten(W0)``.

    Accepts one (N,) mask (applies to every leaf) or a pytree matching
    the weight structure whose leaves are (N,) masks."""
    if (hasattr(client_mask, "ndim")
            or (isinstance(client_mask, (list, tuple))
                and not any(isinstance(x, (list, tuple, dict))
                            for x in client_mask))):
        m = jnp.asarray(client_mask, bool)
        mask_tree = trees.tree_map(lambda _: m, W0)
    else:
        mask_tree = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, bool), client_mask)
    treedef = jax.tree_util.tree_structure(W0)
    masks = tuple(treedef.flatten_up_to(mask_tree))
    for m in masks:
        if m.shape != (n_clients,):
            raise ValueError(
                f"client_mask leaves must be ({n_clients},) booleans, "
                f"got shape {m.shape}")
        # concrete here (outside jit): an all-False leaf would make
        # the Σα = 1 constraint unsatisfiable and silently return the
        # init point — surface the upstream participation bug instead
        if not bool(m.any()):
            raise ValueError(
                "client_mask excludes every client for some leaf — "
                "at least one participant is required")
    return masks


def maecho_aggregate(
    client_weights: list[Pytree],
    projections: Optional[list[Pytree]] = None,
    cfg: MAEchoConfig = MAEchoConfig(),
    convention: str = "oi",
    init_point: Optional[Pytree] = None,
    rng: Optional[jax.Array] = None,
    stack_levels=None,
    return_anchors: bool = False,
    backend: str = "oracle",
    mesh=None,
    client_mask=None,
):
    """Run Algorithm 1.  Returns the global model pytree.

    client_weights: list over clients of structurally identical pytrees.
    projections:    matching list of projector pytrees (see module doc);
                    ``None`` falls back to scalar full projectors.
    stack_levels:   per-leaf count of leading stacked-layer axes —
                    ``None`` (all 0, the paper's MLP/CNN layout), a
                    pytree of ints matching the weights, or a callable
                    ``path -> int`` (the LLM scan-over-layers layout).
                    Stacked leaves are first-class on every backend:
                    eligible ones fold their (flattened) layer axis
                    into the kernel grid; projector leaves must carry
                    the same leading axes.
    backend:        ``"oracle"`` | ``"kernel"`` | ``"auto"`` |
                    ``"sharded"`` | ``"sharded2d"`` — the jnp
                    reference path, the fused streaming Pallas
                    pipeline, its out-dim mesh-sharded form, or the
                    2-D (out × in) multi-axis shard (module
                    docstring).  Unknown strings raise with the full
                    choice list.
    mesh:           ``jax.sharding.Mesh`` carrying ``cfg.mesh_axis``
                    for ``backend="sharded"`` (default: a 1-D mesh
                    over every visible device) — plus
                    ``cfg.mesh_in_axis`` for ``backend="sharded2d"``
                    (default: every visible device, factored as
                    squarely as the count allows — 4 → (2, 2)).
                    Ignored otherwise.
    client_mask:    optional ragged-participation mask — one (N,)
                    boolean vector, or a pytree of them matching the
                    weight structure (per-leaf client subsets).
                    Masked-out clients get exactly α = 0, their
                    anchors are frozen, and the result matches
                    aggregating the subset alone.  At least one client
                    must be masked in per leaf.
    """
    with spans.span("maecho.aggregate"):
        plan_mod.validate_backend(backend)
        if backend == "sharded" and mesh is None:
            mesh = _default_mesh(cfg.mesh_axis)
        if backend == "sharded2d" and mesh is None:
            mesh = _default_mesh(cfg.mesh_axis, cfg.mesh_in_axis)
        if backend not in ("sharded", "sharded2d"):
            mesh = None                 # keep the jit cache key canonical
        if projections is None:
            projections = default_projections(client_weights)
        treedef = jax.tree_util.tree_structure(client_weights[0])
        if stack_levels is None:
            levels_tree = jax.tree_util.tree_unflatten(
                treedef, [0] * treedef.num_leaves)
        elif callable(stack_levels):
            levels_tree = trees.map_with_path(
                lambda path, _: stack_levels(path), client_weights[0])
        else:
            levels_tree = stack_levels
        levels = tuple(jax.tree_util.tree_leaves(levels_tree))
        # Multi-level stacks collapse to ONE flat scan axis before
        # dispatch (pure reshape — the QP treats every scanned layer
        # independently, so per-layer semantics are unchanged): the
        # stacked kernel grid wants a single layer axis, and nested vmaps
        # over the oracle both cost an extra batch dim and trip XLA:CPU's
        # simplifier on dense projector contractions.  Outputs are
        # reshaped back below.
        multi = any(lv > 1 for lv in levels)
        run_levels = tuple(min(lv, 1) for lv in levels) if multi else levels
        leads = tuple(w.shape[:lv] for w, lv in
                      zip(jax.tree_util.tree_leaves(client_weights[0]),
                          levels))
        # the compile-once step, from shapes alone, before anything is
        # placed: routing for every leaf is frozen here, and with it the
        # layout each operand is placed in (memoized — repeated
        # aggregations over the same model reuse the identical plan
        # object AND therefore the executor's jit cache)
        n = len(client_weights)
        W_shapes = [_flat_shape(w, lv) for w, lv in
                    zip(jax.tree_util.tree_leaves(client_weights[0]),
                        levels)]
        P_shapes = [_stacked_shape(p, n, lv) for p, lv in
                    zip(treedef.flatten_up_to(projections[0]), levels)]
        plan = compile_plan(
            jax.tree_util.tree_unflatten(treedef, W_shapes),
            jax.tree_util.tree_unflatten(treedef, P_shapes),
            jax.tree_util.tree_unflatten(treedef, list(run_levels)),
            cfg, convention, backend, mesh)
        with spans.span("maecho.place"):
            W0, V0, P = place(client_weights, projections, plan, mesh,
                              cfg.init, rng, init_point)
            masks = (None if client_mask is None else
                     _normalize_client_mask(client_mask, W0, n))
            if multi:
                fW, fV, fP = [], [], []
                for w, v, p, lv in zip(jax.tree_util.tree_leaves(W0),
                                       treedef.flatten_up_to(V0),
                                       treedef.flatten_up_to(P), levels):
                    if lv > 1:
                        w, v, p, _ = _flatten_stack(w, v, p, lv)
                    fW.append(w)
                    fV.append(v)
                    fP.append(p)
                W0 = jax.tree_util.tree_unflatten(treedef, fW)
                V0 = jax.tree_util.tree_unflatten(treedef, fV)
                P = jax.tree_util.tree_unflatten(treedef, fP)
            # the copies onto the device run after their enqueue returns;
            # the executor cannot start before they land, so waiting here
            # costs only its dispatch, and the span times the placement
            jax.block_until_ready((W0, V0, P))
        with spans.span("maecho.execute"):
            for route, count in sorted(plan.route_counts().items()):
                spans.count("maecho.route", count, route=route)
            W, V = _maecho_jit(W0, V0, P, cfg, convention, plan, mesh, masks)
        if multi:
            W = jax.tree_util.tree_unflatten(treedef, [
                w.reshape(lead + w.shape[1:]) if lv > 1 else w
                for w, lead, lv in zip(jax.tree_util.tree_leaves(W),
                                       leads, levels)])
            V = jax.tree_util.tree_unflatten(treedef, [
                v.reshape(v.shape[:1] + lead + v.shape[2:]) if lv > 1 else v
                for v, lead, lv in zip(treedef.flatten_up_to(V),
                                       leads, levels)])
        return (W, V) if return_anchors else W
