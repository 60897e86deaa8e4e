"""Jit'd public wrappers + dispatch layer for the Pallas kernels.

Interpret mode: every kernel takes ``interpret=None``, which resolves
through ``env.resolve`` at trace time — the Pallas interpreter when
JAX's default backend is the CPU, Mosaic on a TPU.  There is no switch
to set: a chip run always lowers the real kernels.

The ``maecho_*_auto`` wrappers are the backend used by
``core.maecho``'s fused streaming pipeline: they normalise the
projector kind (stacked scalar / diagonal / dense / factored
``{"U", "s"}``), zero-pad non-block-multiple shapes via ``_pad_to``
(zero padding is exact: padded residual tiles are identically zero),
and fall back to the jnp oracles in ``ref.py`` for shapes too small to
tile.  All of them assume the "oi" layout — ``core.maecho`` transposes
"io" leaves before dispatch.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.kernels import env as _env
from repro.kernels import ref
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import maecho_gram as _mg
from repro.kernels import maecho_update as _mu
from repro.kernels import maecho_v_update as _mv
from repro.kernels import rank_update as _ru

__all__ = [
    "flash_attention", "maecho_update", "maecho_update_factored",
    "maecho_update_diag", "maecho_gram", "maecho_gram_factored",
    "maecho_gram_diag", "maecho_v_update", "maecho_v_update_factored",
    "maecho_v_update_diag", "rank_downdate", "block_rls_update",
    "maecho_update_auto", "maecho_gram_auto", "maecho_v_update_auto",
    "maecho_streaming_step", "maecho_streaming_gram",
    "maecho_streaming_apply", "maecho_streaming_gram_stacked",
    "maecho_streaming_apply_stacked", "maecho_sharded_gram",
    "maecho_sharded_apply", "maecho_sharded_gram_stacked",
    "maecho_sharded_apply_stacked", "maecho_sharded2d_gram",
    "maecho_sharded2d_apply", "maecho_sharded2d_gram_stacked",
    "maecho_sharded2d_apply_stacked", "maecho_gram_cross",
    "maecho_streaming_gram_chunked", "maecho_streaming_apply_chunked",
    "maecho_streaming_gram_chunked_stacked",
    "maecho_streaming_apply_chunked_stacked",
    "maecho_sharded_gram_chunked", "maecho_sharded_apply_chunked",
    "sharded_ok", "axis_size_of",
    "fallback_warn", "flash_attention_auto", "interpret_default",
    "decode_attention", "decode_attention_auto", "decode_window_block",
    "live_window", "cache_write", "DEFAULT_BLOCK",
]

# one tile edge: the auto wrappers fall back to the jnp oracles below
# this, and core.maecho's backend="auto" keys off the same constant
DEFAULT_BLOCK = 128

# re-exported from env.py (the raw kernel modules resolve their
# interpret=None defaults there; ops keeps the public name)
interpret_default = _env.interpret_default


_warned_fallbacks: set[str] = set()


def fallback_warn(msg: str) -> None:
    """``warnings.warn`` once per distinct message.

    Silent degradation is the failure mode this guards: a leaf the
    caller believes is on the kernel / sharded fast path quietly
    running the jnp oracle.  Dispatch is trace-time, so the warning
    fires when the program is built, not per step; the dedup set keeps
    re-traces (new shapes, new cfg) from spamming."""
    if msg not in _warned_fallbacks:
        _warned_fallbacks.add(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _proj_kind(P) -> str:
    """Kind of a *stacked* (leading client axis) projector leaf."""
    if isinstance(P, dict):
        return "factored"
    if P.ndim == 1:
        return "scalar"          # (N,) stacked scalar full projectors
    if P.ndim == 2:
        return "diag"            # (N, in)
    return "full"                # (N, in, in)


def _as_diag(P, in_d: int):
    """Broadcast stacked scalars (N,) to a diagonal (N, in)."""
    return jnp.broadcast_to(P[:, None], (P.shape[0], in_d))


def _pad_wv(W, V, block):
    Wp, po = _pad_to(W, block, 0)
    Wp, pi = _pad_to(Wp, block, 1)
    if po or pi:
        Vp, _ = _pad_to(_pad_to(V, block, 1)[0], block, 2)
    else:
        Vp = V
    return Wp, Vp, po, pi


def _pad_factored(U, s, block):
    """Pad the in-axis to ``block``; pad the rank only when it exceeds
    one lane tile (bk = rank otherwise).  Zero-padded (U, s) columns
    produce zero compressed-residual columns — exact."""
    Up, _ = _pad_to(U, block, 1)
    kd = U.shape[2]
    if kd > block:
        Up, _ = _pad_to(Up, block, 2)
        sp, _ = _pad_to(s, block, 1)
    else:
        sp = s
    return Up, sp


def _pad_factored_stacked(U, s, block):
    """:func:`_pad_factored` for the stacked (N, L, in, k) layout —
    the same rule shifted by the flattened layer axis, shared by every
    stacked gram wrapper so the rank-padding exactness argument lives
    in one place."""
    Up, _ = _pad_to(U, block, 2)
    kd = U.shape[3]
    if kd > block:
        Up, _ = _pad_to(Up, block, 3)
        sp, _ = _pad_to(s, block, 2)
    else:
        sp = s
    return Up, sp


# --------------------------------------------------------------------------
# the raw kernels under their public names (each resolves interpret=None
# through env.resolve itself)
# --------------------------------------------------------------------------
maecho_update = _mu.maecho_update
maecho_update_factored = _mu.maecho_update_factored
maecho_update_diag = _mu.maecho_update_diag
maecho_gram = _mg.maecho_gram
maecho_gram_factored = _mg.maecho_gram_factored
maecho_gram_diag = _mg.maecho_gram_diag
maecho_gram_cross = _mg.maecho_gram_cross
maecho_v_update = _mv.maecho_v_update
maecho_v_update_factored = _mv.maecho_v_update_factored
maecho_v_update_diag = _mv.maecho_v_update_diag
flash_attention = _fa.flash_attention
decode_attention = _da.decode_attention
cache_write = _da.cache_write
rank_downdate = _ru.rank_downdate
block_rls_update = _ru.block_rls_update


# --------------------------------------------------------------------------
# auto dispatch: kind normalisation + padding + small-shape fallback
# --------------------------------------------------------------------------
def _normalize_padded(W, V, P, block: int):
    """Shared front half of the auto wrappers: classify the projector
    and zero-pad every operand to block multiples.

    Returns ``(kind, Wp, Vp, Pk)`` where ``Pk`` is the padded kernel
    operand for the kind — an ``(U, s)`` tuple for "factored", a
    (N, in_p) diagonal for "scalar"/"diag" (scalars broadcast), or the
    (N, in_p, in_p) dense matrix for "full".
    """
    in_d = W.shape[1]
    kind = _proj_kind(P)
    Wp, Vp, po, pi = _pad_wv(W, V, block)
    if kind == "factored":
        Pk = _pad_factored(P["U"], P["s"], block)
    elif kind in ("scalar", "diag"):
        p = _as_diag(P, in_d) if kind == "scalar" else P
        Pk = _pad_to(p, block, 1)[0]
    else:
        Pk = (_pad_to(_pad_to(P, block, 1)[0], block, 2)[0]
              if (po or pi) else P)
    return kind, Wp, Vp, Pk


def maecho_update_auto(W, V, P, alpha, *, eta: float = 1.0,
                       block: int = 128, interpret=None):
    """Eq. 7 for any projector kind: kernel when tileable, oracle else."""
    out_d, in_d = W.shape
    if out_d < block or in_d < block:
        return ref.maecho_update_ref_any(W, V, P, alpha, eta)
    kind, Wp, Vp, Pk = _normalize_padded(W, V, P, block)
    if kind == "factored":
        out = maecho_update_factored(Wp, Vp, *Pk, alpha, eta=eta,
                                     interpret=interpret)
    elif kind == "full":
        out = maecho_update(Wp, Vp, Pk, alpha, eta=eta,
                            interpret=interpret)
    else:
        out = maecho_update_diag(Wp, Vp, Pk, alpha, eta=eta,
                                 interpret=interpret)
    return out[:out_d, :in_d]


def maecho_gram_auto(W, V, P, *, block: int = 128, interpret=None):
    """(N, N) projected-residual Gram for any projector kind."""
    out_d, in_d = W.shape
    if out_d < block or in_d < block:
        return ref.maecho_gram_ref(W, V, P)
    kind, Wp, Vp, Pk = _normalize_padded(W, V, P, block)
    if kind == "factored":
        return maecho_gram_factored(Wp, Vp, *Pk, interpret=interpret)
    if kind == "full":
        return maecho_gram(Wp, Vp, Pk, interpret=interpret)
    return maecho_gram_diag(Wp, Vp, Pk, interpret=interpret)


def maecho_v_update_auto(W, V, P, *, frac: float, norm: bool = False,
                         eps: float = 1e-12, block: int = 128,
                         interpret=None):
    """Eq. 11 for any projector kind.

    With ``norm=True`` the kernels need full rows resident (bi = padded
    in_d) — fine up to rows of ~16k fp32.
    """
    out_d, in_d = W.shape
    if out_d < block or in_d < block:
        return ref.maecho_v_update_ref(W, V, P, frac, norm, eps)
    kind, Wp, Vp, Pk = _normalize_padded(W, V, P, block)
    bi = Wp.shape[1] if norm else block
    if kind == "factored":
        out = maecho_v_update_factored(Wp, Vp, *Pk, frac=frac,
                                       norm=norm, eps=eps, bi=bi,
                                       interpret=interpret)
    elif kind == "full":
        out = maecho_v_update(Wp, Vp, Pk, frac=frac, norm=norm, eps=eps,
                              bi=bi, interpret=interpret)
    else:
        out = maecho_v_update_diag(Wp, Vp, Pk, frac=frac, norm=norm,
                                   eps=eps, bi=bi, interpret=interpret)
    return out[:, :out_d, :in_d]


def _eff_block(block: int, out_d: int, in_d: int,
               base: int = DEFAULT_BLOCK) -> int:
    """Clamp a requested streaming-pipeline tile edge to the leaf.

    A caller-tuned ``block`` above ``base`` (``MAEchoConfig.
    kernel_block``) must never push a leaf that tiles fine at ``base``
    onto the oracle, nor pad a dim far past its own next
    base-multiple — the effective edge is capped at the smaller dim's
    base-rounded size.  Eligibility ("too small to tile") is always
    judged at ``base``."""
    cap = max(base, min(-(-out_d // base) * base,
                        -(-in_d // base) * base))
    return min(block, cap)


def maecho_streaming_gram(W, V, P, *, block: int = DEFAULT_BLOCK,
                          interpret=None):
    """Gram half of the fused leaf iteration: returns ``(G, ctx)``.

    G is the (N, N) Eq. 6 Gram matrix; ``ctx`` is an opaque reuse
    context for :func:`maecho_streaming_apply` carrying the classified
    kind, the padded operands, and — on the factored path — the
    compressed residual A shared with the Eq. 7 kernel (the dominant
    O(N·out·in·k) einsum is not recomputed).  Splitting gram from
    apply is what lets ``core.maecho`` stack every leaf's Gram into
    one (L, N, N) batch and run a single vmapped QP solve per outer
    iteration instead of L sequential ones.
    """
    out_d, in_d = W.shape
    if out_d < DEFAULT_BLOCK or in_d < DEFAULT_BLOCK:
        fallback_warn(
            f"leaf (out={out_d}, in={in_d}) below one "
            f"{DEFAULT_BLOCK}-tile: running the jnp oracle instead of "
            f"the streaming kernels")
        return ref.maecho_gram_ref(W, V, P), ("ref", W, V, P,
                                              out_d, in_d)
    block = _eff_block(block, out_d, in_d)
    kind, Wp, Vp, Pk = _normalize_padded(W, V, P, block)
    if kind == "factored":
        from repro.kernels.maecho_gram import compressed_residual

        Up, sp = Pk
        A = compressed_residual(Wp, Vp, Up, sp)
        UT = jnp.swapaxes(Up, 1, 2).astype(jnp.float32)
        G = _mg.maecho_gram_left(A, UT, bo=block, bi=block, bk=block,
                                 interpret=interpret)
        return G, (kind, Wp, Vp, (Up, sp, A, UT), out_d, in_d)
    if kind == "full":
        G = maecho_gram(Wp, Vp, Pk, bo=block, bi=block, bk=block,
                        interpret=interpret)
    else:
        G = maecho_gram_diag(Wp, Vp, Pk, bo=block, bi=block,
                             interpret=interpret)
    return G, (kind, Wp, Vp, Pk, out_d, in_d)


def maecho_streaming_apply(alpha, ctx, *, eta: float = 1.0,
                           frac: float = 0.5, norm: bool = False,
                           eps: float = 1e-12, block: int = DEFAULT_BLOCK,
                           interpret=None):
    """Update half of the fused leaf iteration: Eq. 7 then Eq. 11.

    ``ctx`` is the context returned by :func:`maecho_streaming_gram`
    for the same leaf (same padded operands — the pipeline stays in
    padded space; zero padding is invariant under all three passes).
    Returns ``(W', V')`` cropped back to the original shape.
    """
    kind, Wp, Vp, Pk, out_d, in_d = ctx
    if kind == "ref":
        W_new = ref.maecho_update_ref_any(Wp, Vp, Pk, alpha, eta)
        return W_new, ref.maecho_v_update_ref(W_new, Vp, Pk, frac,
                                              norm, eps)
    block = _eff_block(block, out_d, in_d)   # same clamp as the gram
    bi = Wp.shape[1] if norm else block
    if kind == "factored":
        Up, sp, A, UT = Pk
        Wn = _mu.maecho_update_left(Wp, A, UT, alpha, eta=eta,
                                    bo=block, bi=block, bk=block,
                                    interpret=interpret)
        Vn = maecho_v_update_factored(Wn, Vp, Up, sp, frac=frac,
                                      norm=norm, eps=eps, bo=block,
                                      bi=bi, bk=block,
                                      interpret=interpret)
    elif kind == "full":
        Wn = maecho_update(Wp, Vp, Pk, alpha, eta=eta, bo=block,
                           bi=block, bk=block, interpret=interpret)
        Vn = maecho_v_update(Wn, Vp, Pk, frac=frac, norm=norm, eps=eps,
                             bo=block, bi=bi, bk=block,
                             interpret=interpret)
    else:
        Wn = maecho_update_diag(Wp, Vp, Pk, alpha, eta=eta, bo=block,
                                bi=block, interpret=interpret)
        Vn = maecho_v_update_diag(Wn, Vp, Pk, frac=frac, norm=norm,
                                  eps=eps, bo=block, bi=bi,
                                  interpret=interpret)
    return Wn[:out_d, :in_d], Vn[:, :out_d, :in_d]


def maecho_streaming_step(W, V, P, qp, *, eta: float = 1.0,
                          frac: float = 0.5, norm: bool = False,
                          eps: float = 1e-12, block: int = DEFAULT_BLOCK,
                          interpret=None):
    """One fused Algorithm-1 leaf iteration: gram → QP → Eq. 7 → Eq. 11.

    ``qp`` maps the (N, N) Gram matrix to the simplex weights α.  The
    projector is normalised and padded **once** (in the gram half) and
    the whole pipeline runs in padded space.  This is the single-leaf
    composition of :func:`maecho_streaming_gram` and
    :func:`maecho_streaming_apply`; the batched path in
    ``core.maecho`` calls the two halves directly around one stacked
    QP solve.  Layout is "oi"; shapes below one tile run the jnp
    oracles with the same QP.
    """
    G, ctx = maecho_streaming_gram(W, V, P, block=block,
                                   interpret=interpret)
    alpha = qp(G)
    return maecho_streaming_apply(alpha, ctx, eta=eta, frac=frac,
                                  norm=norm, eps=eps, block=block,
                                  interpret=interpret)


# --------------------------------------------------------------------------
# stacked-leaf streaming pipeline: the scan-layer axis rides the grid
# --------------------------------------------------------------------------
def _proj_kind_stacked(P) -> str:
    """Kind of a stacked projector leaf with (N, L) leading axes —
    every unstacked kind shifted by the flattened layer axis."""
    if isinstance(P, dict):
        return "factored"
    if P.ndim == 2:
        return "scalar"          # (N, L) stacked scalar full projectors
    if P.ndim == 3:
        return "diag"            # (N, L, in)
    return "full"                # (N, L, in, in)


def _normalize_padded_stacked(W, V, P, block: int):
    """Stacked analogue of :func:`_normalize_padded`: classify the
    projector of a flattened (L, out, in) leaf and zero-pad the
    out/in (and factored-rank) axes to block multiples.  The layer
    axis L is a grid axis, never padded."""
    in_d = W.shape[2]
    kind = _proj_kind_stacked(P)
    Wp, po = _pad_to(W, block, 1)
    Wp, pi = _pad_to(Wp, block, 2)
    Vp = (_pad_to(_pad_to(V, block, 2)[0], block, 3)[0]
          if (po or pi) else V)
    if kind == "factored":
        Pk = _pad_factored_stacked(P["U"], P["s"], block)
    elif kind in ("scalar", "diag"):
        p = (jnp.broadcast_to(P[:, :, None], P.shape + (in_d,))
             if kind == "scalar" else P)
        Pk = _pad_to(p, block, 2)[0]
    else:
        Pk = (_pad_to(_pad_to(P, block, 2)[0], block, 3)[0]
              if (po or pi) else P)
    return kind, Wp, Vp, Pk


def maecho_streaming_gram_stacked(W, V, P, *, block: int = DEFAULT_BLOCK,
                                  interpret=None):
    """Stacked gram half of the fused leaf iteration: ``(G, ctx)``.

    W: (L, out, in); V: (N, L, out, in); P stacked per kind.  G is the
    per-layer (L, N, N) Eq. 6 Gram stack from ONE kernel launch (the
    layer axis is the outermost grid dimension — see
    ``maecho_gram.maecho_gram_stacked``); ``ctx`` is the reuse payload
    for :func:`maecho_streaming_apply_stacked`, carrying the factored
    path's (N, L, out, k) compressed residual exactly like the
    per-layer pipeline.  Shapes below one tile fall back to the vmapped
    jnp oracle (same contract as :func:`maecho_streaming_gram`)."""
    L, out_d, in_d = W.shape
    if out_d < DEFAULT_BLOCK or in_d < DEFAULT_BLOCK:
        fallback_warn(
            f"stacked leaf (L={L}, out={out_d}, in={in_d}) below one "
            f"{DEFAULT_BLOCK}-tile: running the vmapped jnp oracle "
            f"instead of the stacked kernel grid")
        G = jax.vmap(ref.maecho_gram_ref, in_axes=(0, 1, 1))(W, V, P)
        return G, ("ref", W, V, P, out_d, in_d)
    block = _eff_block(block, out_d, in_d)
    kind, Wp, Vp, Pk = _normalize_padded_stacked(W, V, P, block)
    if kind == "factored":
        Up, sp = Pk
        A = _mg.compressed_residual(Wp, Vp, Up, sp)     # (N, L, out, k)
        UT = jnp.swapaxes(Up, 2, 3).astype(jnp.float32)
        G = _mg.maecho_gram_left_stacked(A, UT, bo=block, bi=block,
                                         bk=block,
                                         interpret=interpret)
        return G, (kind, Wp, Vp, (Up, sp, A, UT), out_d, in_d)
    if kind == "full":
        G = _mg.maecho_gram_stacked(Wp, Vp, Pk, bo=block, bi=block,
                                    bk=block,
                                    interpret=interpret)
    else:
        G = _mg.maecho_gram_diag_stacked(Wp, Vp, Pk, bo=block,
                                         bi=block,
                                         interpret=interpret)
    return G, (kind, Wp, Vp, Pk, out_d, in_d)


def maecho_streaming_apply_stacked(alpha, ctx, *, eta: float = 1.0,
                                   frac: float = 0.5, norm: bool = False,
                                   eps: float = 1e-12,
                                   block: int = DEFAULT_BLOCK,
                                   interpret=None):
    """Stacked update half: per-layer Eq. 7 then Eq. 11 from one
    launch each.  ``alpha`` is the (L, N) per-layer solve stack;
    ``ctx`` comes from :func:`maecho_streaming_gram_stacked` for the
    same leaf.  Returns ``(W', V')`` cropped to the original shape."""
    kind, Wp, Vp, Pk, out_d, in_d = ctx
    if kind == "ref":
        W_new = jax.vmap(
            lambda w, v, p, a: ref.maecho_update_ref_any(w, v, p, a,
                                                         eta),
            in_axes=(0, 1, 1, 0))(Wp, Vp, Pk, alpha)
        V_new = jax.vmap(
            lambda w, v, p: ref.maecho_v_update_ref(w, v, p, frac,
                                                    norm, eps),
            in_axes=(0, 1, 1), out_axes=1)(W_new, Vp, Pk)
        return W_new, V_new
    block = _eff_block(block, out_d, in_d)   # same clamp as the gram
    bi = Wp.shape[2] if norm else block
    if kind == "factored":
        Up, sp, A, UT = Pk
        Wn = _mu.maecho_update_left_stacked(Wp, A, UT, alpha, eta=eta,
                                            bo=block, bi=block,
                                            bk=block, interpret=interpret)
        Vn = _mv.maecho_v_update_factored_stacked(
            Wn, Vp, Up, sp, frac=frac, norm=norm, eps=eps, bo=block,
            bi=bi, bk=block, interpret=interpret)
    elif kind == "full":
        Wn = _mu.maecho_update_stacked(Wp, Vp, Pk, alpha, eta=eta,
                                       bo=block, bi=block, bk=block,
                                       interpret=interpret)
        Vn = _mv.maecho_v_update_stacked(Wn, Vp, Pk, frac=frac,
                                         norm=norm, eps=eps, bo=block,
                                         bi=bi, bk=block,
                                         interpret=interpret)
    else:
        Wn = _mu.maecho_update_diag_stacked(Wp, Vp, Pk, alpha, eta=eta,
                                            bo=block, bi=block,
                                            interpret=interpret)
        Vn = _mv.maecho_v_update_diag_stacked(Wn, Vp, Pk, frac=frac,
                                              norm=norm, eps=eps,
                                              bo=block, bi=bi,
                                              interpret=interpret)
    return Wn[:, :out_d, :in_d], Vn[:, :, :out_d, :in_d]


# --------------------------------------------------------------------------
# mesh-sharded streaming pipeline: out-dim-parallel gram / apply
# --------------------------------------------------------------------------
def _axis_names(axis) -> tuple:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def axis_size_of(mesh, axis) -> int:
    """Product of the named mesh axes' sizes (absent axes count 1).

    Delegates to the sharding rules' ``mesh_axis_size`` — one copy of
    the axis-size contract (imported lazily: the kernels layer stays
    import-light)."""
    from repro.sharding.rules import mesh_axis_size

    return mesh_axis_size(mesh, _axis_names(axis))


def sharded_ok(out_d: int, in_d: int, axis_size: int,
               block: int = DEFAULT_BLOCK, warn: bool = False) -> bool:
    """Eligibility of a leaf for the out-dim-sharded pipeline.

    Both dims must reach one tile and the out-dim's *tile count* must
    divide evenly over the axis — the sharding rules' ``_ok``
    divisibility contract at block granularity (every device gets the
    same number of whole tiles; GSPMD-style uneven shards would skew
    the per-device kernels).  Ineligible leaves stay on the
    single-device kernel/oracle path; with ``warn=True`` (the dispatch
    path in ``core.maecho`` sets it) that fallback is surfaced once
    via :func:`fallback_warn` instead of happening silently.
    """
    if out_d < block or in_d < block:
        ok = False
    else:
        ok = (-(-out_d // block)) % axis_size == 0
    if not ok and warn:
        fallback_warn(
            f"sharded-ineligible leaf (out={out_d}, in={in_d}, "
            f"axis_size={axis_size}, block={block}): falling back to "
            f"the single-device dispatch")
    return ok


def maecho_sharded_gram(W, V, P, *, mesh, axis="data",
                        block: int = DEFAULT_BLOCK, interpret=None):
    """Out-dim-sharded gram half of the streaming pipeline.

    Same ``(G, ctx)`` contract as :func:`maecho_streaming_gram`, but
    the leaf's out-rows are split over the ``axis`` mesh axes with
    ``shard_map``: each device forms only its own
    (out / axis_size, in) residual tiles in VMEM, contracts a partial
    (N, N) Gram locally, and ONE ``psum`` over the axis reconstructs
    the full replicated Gram that feeds the (global, unchanged) QP
    solve.  The apply half (:func:`maecho_sharded_apply`) then runs
    purely locally on the owned rows — no further collectives.

    Operands are zero-padded so the out-dim is a multiple of
    ``block × axis_size`` (even, block-tileable shards; zero padding
    is exact for all three passes) and the in-dim to ``block``.  On
    the factored path the (N, out, k) compressed residual is computed
    *sharded* and carried in ``ctx`` for the Eq. 7 kernel — the
    compressed-residual reuse survives the sharding.  Callers gate
    eligibility with :func:`sharded_ok`; "oi" layout, like the rest of
    the kernel pipeline.
    """
    names = _axis_names(axis)
    asz = axis_size_of(mesh, axis)
    out_d, in_d = W.shape
    kind = _proj_kind(P)
    Wp, _ = _pad_to(_pad_to(W, block * asz, 0)[0], block, 1)
    Vp, _ = _pad_to(_pad_to(V, block * asz, 1)[0], block, 2)
    row = PartitionSpec(names, None)           # W rows
    crow = PartitionSpec(None, names, None)    # V / A rows (axis 1)
    rep2 = PartitionSpec(None, None)
    rep3 = PartitionSpec(None, None, None)
    if kind == "factored":
        Up, sp = _pad_factored(P["U"], P["s"], block)

        def body_f(Wl, Vl, U, s):
            A = _mg.compressed_residual(Wl, Vl, U, s)
            UT = jnp.swapaxes(U, 1, 2).astype(jnp.float32)
            Gl = _mg.maecho_gram_left(A, UT, interpret=interpret)
            return jax.lax.psum(Gl, names), A

        G, A = jax.shard_map(body_f, mesh=mesh,
                         in_specs=(row, crow, rep3, rep2),
                         out_specs=(rep2, crow),
                         check_vma=False)(Wp, Vp, Up, sp)
        return G, (kind, Wp, Vp, (Up, sp, A), out_d, in_d)
    if kind == "full":
        Pk = _pad_to(_pad_to(P, block, 1)[0], block, 2)[0]

        def body_d(Wl, Vl, Pl):
            return jax.lax.psum(
                _mg.maecho_gram(Wl, Vl, Pl, interpret=interpret), names)

        G = jax.shard_map(body_d, mesh=mesh, in_specs=(row, crow, rep3),
                      out_specs=rep2, check_vma=False)(Wp, Vp, Pk)
    else:                                   # scalar / diag
        p = _as_diag(P, in_d) if kind == "scalar" else P
        Pk = _pad_to(p, block, 1)[0]

        def body_g(Wl, Vl, pl):
            return jax.lax.psum(
                _mg.maecho_gram_diag(Wl, Vl, pl, interpret=interpret), names)

        G = jax.shard_map(body_g, mesh=mesh, in_specs=(row, crow, rep2),
                      out_specs=rep2, check_vma=False)(Wp, Vp, Pk)
    return G, (kind, Wp, Vp, Pk, out_d, in_d)


def maecho_sharded_apply(alpha, ctx, *, mesh, axis="data",
                         eta: float = 1.0, frac: float = 0.5,
                         norm: bool = False, eps: float = 1e-12,
                         block: int = DEFAULT_BLOCK, interpret=None):
    """Update half of the sharded pipeline: Eq. 7 then Eq. 11.

    ``ctx`` is the context from :func:`maecho_sharded_gram` for the
    same leaf.  Both phases are row-local under the same out-dim
    sharding: Eq. 7 scales the owned rows' residuals by the replicated
    α, and Eq. 11's row normalisation runs along the unsharded in-axis
    — zero collectives (the gram phase's single psum is the outer
    iteration's only one).  Returns ``(W', V')`` cropped to the
    original shape.
    """
    kind, Wp, Vp, Pk, out_d, in_d = ctx
    names = _axis_names(axis)
    bi = Wp.shape[1] if norm else block
    row = PartitionSpec(names, None)
    crow = PartitionSpec(None, names, None)
    rep1 = PartitionSpec(None)
    rep2 = PartitionSpec(None, None)
    rep3 = PartitionSpec(None, None, None)
    if kind == "factored":
        Up, sp, A = Pk

        def body_f(a, Wl, Vl, U, s, Al):
            UT = jnp.swapaxes(U, 1, 2).astype(jnp.float32)
            Wn = _mu.maecho_update_left(Wl, Al, UT, a, eta=eta,
                                        interpret=interpret)
            Vn = _mv.maecho_v_update_factored(
                Wn, Vl, U, s, frac=frac, norm=norm, eps=eps, bi=bi,
                interpret=interpret)
            return Wn, Vn

        Wn, Vn = jax.shard_map(
            body_f, mesh=mesh,
            in_specs=(rep1, row, crow, rep3, rep2, crow),
            out_specs=(row, crow), check_vma=False)(
            alpha, Wp, Vp, Up, sp, A)
    elif kind == "full":
        def body_d(a, Wl, Vl, Pl):
            Wn = _mu.maecho_update(Wl, Vl, Pl, a, eta=eta,
                                   interpret=interpret)
            Vn = _mv.maecho_v_update(Wn, Vl, Pl, frac=frac, norm=norm,
                                     eps=eps, bi=bi, interpret=interpret)
            return Wn, Vn

        Wn, Vn = jax.shard_map(
            body_d, mesh=mesh, in_specs=(rep1, row, crow, rep3),
            out_specs=(row, crow), check_vma=False)(alpha, Wp, Vp, Pk)
    else:                                   # scalar / diag
        def body_g(a, Wl, Vl, pl):
            Wn = _mu.maecho_update_diag(Wl, Vl, pl, a, eta=eta,
                                        interpret=interpret)
            Vn = _mv.maecho_v_update_diag(Wn, Vl, pl, frac=frac,
                                          norm=norm, eps=eps, bi=bi,
                                          interpret=interpret)
            return Wn, Vn

        Wn, Vn = jax.shard_map(
            body_g, mesh=mesh, in_specs=(rep1, row, crow, rep2),
            out_specs=(row, crow), check_vma=False)(alpha, Wp, Vp, Pk)
    return Wn[:out_d, :in_d], Vn[:, :out_d, :in_d]


def maecho_sharded_gram_stacked(W, V, P, *, mesh, axis="data",
                                block: int = DEFAULT_BLOCK,
                                interpret=None):
    """Out-dim-sharded stacked gram half.

    Same contract as :func:`maecho_sharded_gram` with the flattened
    scan-layer axis riding the kernel grid inside every shard:
    W (L, out, in) splits its out-rows over ``axis``, each device runs
    ONE stacked kernel launch over its (L, out/axis_size, in) slab,
    and a single ``psum`` per leaf per outer iteration reconstructs
    the replicated (L, N, N) Gram stack that feeds the (unchanged)
    stacked QP solve.  The factored path's (N, L, out, k) compressed
    residual is computed sharded and carried in ``ctx``.
    """
    names = _axis_names(axis)
    asz = axis_size_of(mesh, axis)
    L, out_d, in_d = W.shape
    kind = _proj_kind_stacked(P)
    Wp = _pad_to(_pad_to(W, block * asz, 1)[0], block, 2)[0]
    Vp = _pad_to(_pad_to(V, block * asz, 2)[0], block, 3)[0]
    row = PartitionSpec(None, names, None)          # W rows (axis 1)
    crow = PartitionSpec(None, None, names, None)   # V / A rows (axis 2)
    rep3 = PartitionSpec(None, None, None)
    rep4 = PartitionSpec(None, None, None, None)
    if kind == "factored":
        Up, sp = _pad_factored_stacked(P["U"], P["s"], block)

        def body_f(Wl, Vl, U, s):
            A = _mg.compressed_residual(Wl, Vl, U, s)
            UT = jnp.swapaxes(U, 2, 3).astype(jnp.float32)
            Gl = _mg.maecho_gram_left_stacked(A, UT, interpret=interpret)
            return jax.lax.psum(Gl, names), A

        G, A = jax.shard_map(body_f, mesh=mesh,
                         in_specs=(row, crow, rep4, rep3),
                         out_specs=(rep3, crow),
                         check_vma=False)(Wp, Vp, Up, sp)
        return G, (kind, Wp, Vp, (Up, sp, A), out_d, in_d)
    if kind == "full":
        Pk = _pad_to(_pad_to(P, block, 2)[0], block, 3)[0]

        def body_d(Wl, Vl, Pl):
            return jax.lax.psum(
                _mg.maecho_gram_stacked(Wl, Vl, Pl, interpret=interpret),
                names)

        G = jax.shard_map(body_d, mesh=mesh, in_specs=(row, crow, rep4),
                      out_specs=rep3, check_vma=False)(Wp, Vp, Pk)
    else:                                   # scalar / diag
        p = (jnp.broadcast_to(P[:, :, None], P.shape + (in_d,))
             if kind == "scalar" else P)
        Pk = _pad_to(p, block, 2)[0]

        def body_g(Wl, Vl, pl_):
            return jax.lax.psum(
                _mg.maecho_gram_diag_stacked(Wl, Vl, pl_,
                                             interpret=interpret), names)

        G = jax.shard_map(body_g, mesh=mesh, in_specs=(row, crow, rep3),
                      out_specs=rep3, check_vma=False)(Wp, Vp, Pk)
    return G, (kind, Wp, Vp, Pk, out_d, in_d)


def maecho_sharded_apply_stacked(alpha, ctx, *, mesh, axis="data",
                                 eta: float = 1.0, frac: float = 0.5,
                                 norm: bool = False, eps: float = 1e-12,
                                 block: int = DEFAULT_BLOCK,
                                 interpret=None):
    """Stacked update half of the sharded pipeline: per-layer Eq. 7
    then Eq. 11, row-local on each device's owned out-rows under the
    same sharding as :func:`maecho_sharded_gram_stacked` — zero
    collectives (the gram psum is the iteration's only one).
    ``alpha`` is the replicated (L, N) per-layer solve stack.
    Returns ``(W', V')`` cropped to the original shape."""
    kind, Wp, Vp, Pk, out_d, in_d = ctx
    names = _axis_names(axis)
    bi = Wp.shape[2] if norm else block
    row = PartitionSpec(None, names, None)
    crow = PartitionSpec(None, None, names, None)
    rep2 = PartitionSpec(None, None)
    rep3 = PartitionSpec(None, None, None)
    rep4 = PartitionSpec(None, None, None, None)
    if kind == "factored":
        Up, sp, A = Pk

        def body_f(a, Wl, Vl, U, s, Al):
            UT = jnp.swapaxes(U, 2, 3).astype(jnp.float32)
            Wn = _mu.maecho_update_left_stacked(Wl, Al, UT, a, eta=eta,
                                                interpret=interpret)
            Vn = _mv.maecho_v_update_factored_stacked(
                Wn, Vl, U, s, frac=frac, norm=norm, eps=eps, bi=bi,
                interpret=interpret)
            return Wn, Vn

        Wn, Vn = jax.shard_map(
            body_f, mesh=mesh,
            in_specs=(rep2, row, crow, rep4, rep3, crow),
            out_specs=(row, crow), check_vma=False)(
            alpha, Wp, Vp, Up, sp, A)
    elif kind == "full":
        def body_d(a, Wl, Vl, Pl):
            Wn = _mu.maecho_update_stacked(Wl, Vl, Pl, a, eta=eta,
                                           interpret=interpret)
            Vn = _mv.maecho_v_update_stacked(Wn, Vl, Pl, frac=frac,
                                             norm=norm, eps=eps, bi=bi,
                                             interpret=interpret)
            return Wn, Vn

        Wn, Vn = jax.shard_map(
            body_d, mesh=mesh, in_specs=(rep2, row, crow, rep4),
            out_specs=(row, crow), check_vma=False)(alpha, Wp, Vp, Pk)
    else:                                   # scalar / diag
        def body_g(a, Wl, Vl, pl_):
            Wn = _mu.maecho_update_diag_stacked(Wl, Vl, pl_, a, eta=eta,
                                                interpret=interpret)
            Vn = _mv.maecho_v_update_diag_stacked(
                Wn, Vl, pl_, frac=frac, norm=norm, eps=eps, bi=bi,
                interpret=interpret)
            return Wn, Vn

        Wn, Vn = jax.shard_map(
            body_g, mesh=mesh, in_specs=(rep2, row, crow, rep3),
            out_specs=(row, crow), check_vma=False)(alpha, Wp, Vp, Pk)
    return Wn[:, :out_d, :in_d], Vn[:, :, :out_d, :in_d]


# --------------------------------------------------------------------------
# 2-D (out × in) mesh-sharded pipeline: backend="sharded2d"
# --------------------------------------------------------------------------
def _pin(mesh, *pairs):
    """Each array of ``pairs`` ((array, spec), …) held in ``spec`` on
    ``mesh``.  The 2-D gram slices its in-columns out of operands that
    the row-local apply then takes whole: pinned to the apply's layout,
    each is sliced on its device and never gathered back."""
    from jax.sharding import NamedSharding

    return [jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
            for x, spec in pairs]


def maecho_sharded2d_gram(W, V, P, *, mesh, axis_out="data",
                          axis_in="model", block: int = DEFAULT_BLOCK,
                          interpret=None):
    """2-D-sharded gram half: out-rows over ``axis_out`` AND
    in-columns over ``axis_in``.

    Each device forms only its own (out/osz, in/isz) tile of the
    projected residual — the dominant O(N·out·in²) projection FLOPs
    split over the *whole* osz × isz fleet, which is the point: a leaf
    whose out-dim tile count cannot divide the full device count 1-D
    can still span it as the product of two smaller per-axis factors
    (``rules.sharded_ok2d`` gates both dims).  The partial (N, N)
    Grams are reconstructed by ONE ``psum`` over BOTH axis groups —
    the leaf's only collective per outer iteration.

    The residual tile is formed as a left-factor product (``Δ`` rows
    against the projector's owned output columns), so dense and
    factored kinds ride the existing ``maecho_gram_left`` kernel and
    diagonal/scalar kinds the elementwise ``maecho_gram_diag`` on
    pre-sliced operands.  Operands are zero-padded to
    ``block × axis_size`` multiples on each sharded dim (zero padding
    is exact for all three passes).

    Returns ``(G, ctx)`` with ``ctx`` in the SAME format as
    :func:`maecho_sharded_gram` — the apply half reuses the 1-D
    row-local kernels verbatim (see :func:`maecho_sharded2d_apply`).
    """
    no, ni = _axis_names(axis_out), _axis_names(axis_in)
    allnames = no + ni
    osz = axis_size_of(mesh, axis_out)
    isz = axis_size_of(mesh, axis_in)
    out_d, in_d = W.shape
    kind = _proj_kind(P)
    row = PartitionSpec(no, None)
    crow = PartitionSpec(None, no, None)
    col3 = PartitionSpec(None, None, ni)
    rep2 = PartitionSpec(None, None)
    rep3 = PartitionSpec(None, None, None)
    Wp, Vp = _pin(mesh,
                  (_pad_to(_pad_to(W, block * osz, 0)[0], block * isz,
                           1)[0], row),
                  (_pad_to(_pad_to(V, block * osz, 1)[0], block * isz,
                           2)[0], crow))
    if kind == "factored":
        Up, sp = _pin(mesh, *zip(_pad_factored(P["U"], P["s"], block),
                                 (rep3, rep2)))
        UTs = jnp.swapaxes(Up, 1, 2).astype(jnp.float32)

        def body_f(Wl, Vl, U, s, UTl):
            # A (full in-contraction, replicated over axis_in);
            # the gram contracts A against only the owned UT columns
            A = _mg.compressed_residual(Wl, Vl, U, s)
            Gl = _mg.maecho_gram_left(A, UTl, interpret=interpret)
            return jax.lax.psum(Gl, allnames), A

        G, A = jax.shard_map(body_f, mesh=mesh,
                         in_specs=(row, crow, rep3, rep2, col3),
                         out_specs=(rep2, crow),
                         check_vma=False)(Wp, Vp, Up, sp, UTs)
        return G, (kind, Wp, Vp, (Up, sp, A), out_d, in_d)
    if kind == "full":
        in_p = Wp.shape[1]
        Pk, = _pin(mesh, (_pad_to(_pad_to(P, in_p, 1)[0], in_p, 2)[0],
                          rep3))

        def body_d(Wl, Vl, Pl):
            # residual tile = Δ @ P[:, owned columns]: the delta rows
            # are the left factor, the projector's owned output
            # columns the right — maecho_gram_left streams the tiles
            A = (Wl[None] - Vl).astype(jnp.float32)
            Gl = _mg.maecho_gram_left(A, Pl.astype(jnp.float32),
                                      interpret=interpret)
            return jax.lax.psum(Gl, allnames)

        G = jax.shard_map(body_d, mesh=mesh, in_specs=(row, crow, col3),
                      out_specs=rep2, check_vma=False)(Wp, Vp, Pk)
    else:                                   # scalar / diag
        p = _as_diag(P, in_d) if kind == "scalar" else P
        Pk, = _pin(mesh, (_pad_to(p, block * isz, 1)[0], rep2))

        def body_g(Wl, Vl, pl):
            # elementwise kind: 2-D-slicing the operands is exact
            return jax.lax.psum(
                _mg.maecho_gram_diag(Wl, Vl, pl, interpret=interpret),
                allnames)

        G = jax.shard_map(body_g, mesh=mesh,
                      in_specs=(PartitionSpec(no, ni),
                                PartitionSpec(None, no, ni),
                                PartitionSpec(None, ni)),
                      out_specs=rep2, check_vma=False)(Wp, Vp, Pk)
    return G, (kind, Wp, Vp, Pk, out_d, in_d)


def maecho_sharded2d_apply(alpha, ctx, *, mesh, axis_out="data",
                           axis_in="model", eta: float = 1.0,
                           frac: float = 0.5, norm: bool = False,
                           eps: float = 1e-12,
                           block: int = DEFAULT_BLOCK, interpret=None):
    """Update half of the 2-D pipeline: Eq. 7 then Eq. 11, row/col-local.

    Delegates to the 1-D row-local apply over ``axis_out``: the
    devices along ``axis_in`` hold replicated rows (the in-dim
    contraction of Eq. 11 needs full Δ' rows, which stay resident from
    the gram phase's in-replicated operands) and recompute identical
    row shards — ZERO collectives either way, so the gram phase's
    single two-axis psum remains the leaf's only one per outer
    iteration.  ``ctx`` comes from :func:`maecho_sharded2d_gram`
    (same layout as the 1-D context; the extra in-padding to
    ``block × axis_in_size`` is still a block multiple, which is all
    the kernels require)."""
    del axis_in  # rows-only: the in-group replicates the apply
    return maecho_sharded_apply(alpha, ctx, mesh=mesh, axis=axis_out,
                                eta=eta, frac=frac, norm=norm, eps=eps,
                                block=block, interpret=interpret)


def maecho_sharded2d_gram_stacked(W, V, P, *, mesh, axis_out="data",
                                  axis_in="model",
                                  block: int = DEFAULT_BLOCK,
                                  interpret=None):
    """Stacked 2-D gram half: same contract as
    :func:`maecho_sharded2d_gram` with the flattened scan-layer axis
    riding the kernel grid inside every (out × in) shard — ONE stacked
    launch per device and ONE two-axis ``psum`` per leaf per outer
    iteration carrying the whole (L, N, N) Gram stack."""
    no, ni = _axis_names(axis_out), _axis_names(axis_in)
    allnames = no + ni
    osz = axis_size_of(mesh, axis_out)
    isz = axis_size_of(mesh, axis_in)
    L, out_d, in_d = W.shape
    kind = _proj_kind_stacked(P)
    row = PartitionSpec(None, no, None)
    crow = PartitionSpec(None, None, no, None)
    col4 = PartitionSpec(None, None, None, ni)
    rep3 = PartitionSpec(None, None, None)
    rep4 = PartitionSpec(None, None, None, None)
    Wp, Vp = _pin(mesh,
                  (_pad_to(_pad_to(W, block * osz, 1)[0], block * isz,
                           2)[0], row),
                  (_pad_to(_pad_to(V, block * osz, 2)[0], block * isz,
                           3)[0], crow))
    if kind == "factored":
        Up, sp = _pin(mesh, *zip(_pad_factored_stacked(P["U"], P["s"],
                                                       block),
                                 (rep4, rep3)))
        UTs = jnp.swapaxes(Up, 2, 3).astype(jnp.float32)

        def body_f(Wl, Vl, U, s, UTl):
            A = _mg.compressed_residual(Wl, Vl, U, s)
            Gl = _mg.maecho_gram_left_stacked(A, UTl, interpret=interpret)
            return jax.lax.psum(Gl, allnames), A

        G, A = jax.shard_map(body_f, mesh=mesh,
                         in_specs=(row, crow, rep4, rep3, col4),
                         out_specs=(rep3, crow),
                         check_vma=False)(Wp, Vp, Up, sp, UTs)
        return G, (kind, Wp, Vp, (Up, sp, A), out_d, in_d)
    if kind == "full":
        in_p = Wp.shape[2]
        Pk, = _pin(mesh, (_pad_to(_pad_to(P, in_p, 2)[0], in_p, 3)[0],
                          rep4))

        def body_d(Wl, Vl, Pl):
            # Δ (N, L, o_sh, in_p) is already the left-factor layout;
            # Pl (N, L, in_p, in_sh) carries the owned output columns
            A = (Wl[None] - Vl).astype(jnp.float32)
            Gl = _mg.maecho_gram_left_stacked(
                A, Pl.astype(jnp.float32), interpret=interpret)
            return jax.lax.psum(Gl, allnames)

        G = jax.shard_map(body_d, mesh=mesh, in_specs=(row, crow, col4),
                      out_specs=rep3, check_vma=False)(Wp, Vp, Pk)
    else:                                   # scalar / diag
        p = (jnp.broadcast_to(P[:, :, None], P.shape + (in_d,))
             if kind == "scalar" else P)
        Pk, = _pin(mesh, (_pad_to(p, block * isz, 2)[0], rep3))

        def body_g(Wl, Vl, pl_):
            return jax.lax.psum(
                _mg.maecho_gram_diag_stacked(Wl, Vl, pl_,
                                             interpret=interpret), allnames)

        G = jax.shard_map(body_g, mesh=mesh,
                      in_specs=(PartitionSpec(None, no, ni),
                                PartitionSpec(None, None, no, ni),
                                PartitionSpec(None, None, ni)),
                      out_specs=rep3, check_vma=False)(Wp, Vp, Pk)
    return G, (kind, Wp, Vp, Pk, out_d, in_d)


def maecho_sharded2d_apply_stacked(alpha, ctx, *, mesh,
                                   axis_out="data", axis_in="model",
                                   eta: float = 1.0, frac: float = 0.5,
                                   norm: bool = False,
                                   eps: float = 1e-12,
                                   block: int = DEFAULT_BLOCK,
                                   interpret=None):
    """Stacked 2-D apply: row/col-local per-layer Eq. 7 + Eq. 11 via
    the 1-D stacked apply over ``axis_out`` (the in-group replicates
    the rows — zero collectives, cf. :func:`maecho_sharded2d_apply`)."""
    del axis_in
    return maecho_sharded_apply_stacked(
        alpha, ctx, mesh=mesh, axis=axis_out, eta=eta, frac=frac,
        norm=norm, eps=eps, block=block, interpret=interpret)


# --------------------------------------------------------------------------
# client-chunked streaming pipeline: peak memory O(chunk), not O(N)
# --------------------------------------------------------------------------
def _slice_chunk(P, a: int, chunk: int):
    """Client-chunk ``a`` of a stacked projector operand (dicts slice
    leaf-wise: the factored kind stays factored through the chunking)."""
    if isinstance(P, dict):
        return {k: v[a * chunk:(a + 1) * chunk] for k, v in P.items()}
    return P[a * chunk:(a + 1) * chunk]


def _dyn_chunk(P, a, chunk: int):
    """Client-chunk ``a`` (a TRACED loop index) via ``dynamic_slice``
    — the loop-body form of :func:`_slice_chunk`.  Dynamic slicing is
    what actually bounds memory: a statically-unrolled sweep lets XLA
    CSE every chunk's residual into one live buffer each, rebuilding
    the O(N) footprint the chunking exists to remove."""
    def sl(x):
        return jax.lax.dynamic_slice_in_dim(x, a * chunk, chunk, axis=0)
    if isinstance(P, dict):
        return {k: sl(v) for k, v in P.items()}
    return sl(P)


def _pad_clients(W, V, P, chunk: int, kind: str):
    """Zero-pad the client axis to a ``chunk`` multiple.

    Padded anchors are W itself — their residual (W − W)P is
    identically zero whatever the projector — and padded projectors
    are zeros (belt and braces; the Gram/apply crops never read them).
    Exact for every pass, mirroring the ``_pad_to`` tile-padding
    argument on the feature axes."""
    N = V.shape[0]
    pad = (-N) % chunk
    if pad == 0:
        return V, P
    Vp = jnp.concatenate(
        [V, jnp.broadcast_to(W[None], (pad,) + W.shape).astype(V.dtype)],
        axis=0)
    if kind == "factored":
        Pp = {k: _pad_to(v, chunk, 0)[0] for k, v in P.items()}
    else:
        Pp = _pad_to(P, chunk, 0)[0]
    return Vp, Pp


def _chunked_resid(W, Va, Pa, kind: str):
    """Rᵢ = (W − Vᵢ)Pᵢ for ONE client chunk, any projector kind, with
    optional stacked-layer axes riding the einsum ellipsis.  This is
    the only place the chunked pipeline materializes residual rows —
    (chunk, […,] out, in) fp32, never the full client axis."""
    delta = (W[None] - Va).astype(jnp.float32)
    if kind == "full":
        return jnp.einsum("n...oi,n...ij->n...oj", delta,
                          Pa.astype(jnp.float32))
    if kind == "diag":
        return delta * Pa[..., None, :].astype(jnp.float32)
    if kind == "scalar":
        return delta * Pa[..., None, None].astype(jnp.float32)
    U = Pa["U"].astype(jnp.float32)
    A = (jnp.einsum("n...oi,n...ik->n...ok", delta, U)
         * Pa["s"][..., None, :].astype(jnp.float32))
    return jnp.einsum("n...ok,n...ik->n...oi", A, U)


def _pair_jnp(stacked: bool):
    """Chunk-pair contraction ⟨Rₐ, R_b⟩ on flat residual rows:
    (ca, D) × (cb, D) -> (ca, cb), or (ca, L, D) × (cb, L, D) ->
    (L, ca, cb) with the layer axis as a dot_general batch dim."""
    if stacked:
        return lambda Ra, Rb: jax.lax.dot_general(
            Ra, Rb, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32)
    return lambda Ra, Rb: jax.lax.dot_general(
        Ra, Rb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _chunked_gram_core(W, Vp, Pp, kind: str, chunk: int, stacked: bool,
                       pair):
    """Triangular chunk-pair sweep: the (ncpad, ncpad) Gram assembled
    from (chunk, chunk) blocks with at most TWO chunks' residuals
    resident at any point.  Row chunk a's residual is computed once
    and held across its inner sweep; the strict lower triangle is the
    mirror of the upper (⟨Rₐ, R_b⟩ is symmetric under transpose) — the
    recompute factor is (nc+1)/2 residual passes, all O(chunk) in
    memory.  ``pair`` is the block contraction (jnp dot or the Pallas
    ``maecho_gram_cross`` streamer).

    The sweep is a ``fori_loop`` over DYNAMIC chunk indices rather
    than a python unroll: unrolled, XLA common-subexpressions each
    chunk's residual across its (nc) pair uses and keeps every one
    live through the whole sweep — measured peak equal to the
    unchunked path.  The loop + ``dynamic_slice`` form is opaque to
    that hoist, so exactly Rₐ and R_b exist at any program point."""
    nc = Vp.shape[0] // chunk
    lead = 2 if stacked else 1

    def resid(a):
        """Flattened residual rows of (traced) chunk ``a``."""
        Va = jax.lax.dynamic_slice_in_dim(Vp, a * chunk, chunk, axis=0)
        R = _chunked_resid(W, Va, _dyn_chunk(Pp, a, chunk), kind)
        return R.reshape(R.shape[:lead] + (-1,))

    if nc == 1:                        # one chunk: no sweep, no loop
        R0 = _chunked_resid(W, Vp, Pp, kind)
        R0 = R0.reshape(R0.shape[:lead] + (-1,))
        return pair(R0, R0)

    npadc = nc * chunk
    gshape = ((W.shape[0], npadc, npadc) if stacked
              else (npadc, npadc))
    zeros = (0,) if stacked else ()

    def put(G, blk, a, b):
        # diagonal blocks (a == b) write twice; ⟨Rₐ, Rₐ⟩ equals its
        # own transpose bit-for-bit, so the second write is a no-op
        G = jax.lax.dynamic_update_slice(
            G, blk, zeros + (a * chunk, b * chunk))
        return jax.lax.dynamic_update_slice(
            G, jnp.swapaxes(blk, -1, -2),
            zeros + (b * chunk, a * chunk))

    def outer(a, G):
        Ra = resid(a)

        def inner(b, G):
            return put(G, pair(Ra, resid(b)), a, b)

        G = put(G, pair(Ra, Ra), a, a)
        return jax.lax.fori_loop(a + 1, nc, inner, G)

    return jax.lax.fori_loop(0, nc, outer,
                             jnp.zeros(gshape, jnp.float32))


def _chunked_apply_core(alpha, W, Vp, Pp, kind: str, chunk: int, N: int,
                        stacked: bool, *, eta: float, frac: float,
                        norm: bool, eps: float):
    """Chunk-wise Eq. 7 + Eq. 11: the Eq. 7 delta accumulates over
    chunk residuals of the ORIGINAL W (α zero-padded on dead clients),
    then a second chunk sweep rebuilds each chunk's anchors from W' —
    the full (N, out, in) residual never exists; the (N, …) V' output
    is assembled from per-chunk pieces."""
    nc = Vp.shape[0] // chunk
    npad = nc * chunk - N
    ap = alpha.astype(jnp.float32)
    if npad:
        widths = ((0, 0), (0, npad)) if stacked else ((0, npad),)
        ap = jnp.pad(ap, widths)

    def acc_body(a, acc):
        Va = jax.lax.dynamic_slice_in_dim(Vp, a * chunk, chunk, axis=0)
        Ra = _chunked_resid(W, Va, _dyn_chunk(Pp, a, chunk), kind)
        aa = jax.lax.dynamic_slice_in_dim(ap, a * chunk, chunk,
                                          axis=ap.ndim - 1)
        if stacked:
            return acc + jnp.einsum("la,al...->l...", aa, Ra)
        return acc + jnp.einsum("a,a...->...", aa, Ra)

    # same dynamic-index loops as the gram sweep (see
    # _chunked_gram_core): unrolled chunks get CSE'd into full-N
    # residency
    acc = jax.lax.fori_loop(0, nc, acc_body,
                            jnp.zeros(W.shape, jnp.float32))
    W_new = (W.astype(jnp.float32) - 2.0 * eta * acc).astype(W.dtype)

    def v_chunk(Va, Pa):
        delta = (W_new[None] - Va).astype(jnp.float32)
        Un = delta - frac * _chunked_resid(W_new, Va, Pa, kind)
        if norm:
            nrm = jnp.linalg.norm(Un, axis=-1, keepdims=True)
            Un = Un / jnp.maximum(nrm, eps)
        return (Va.astype(jnp.float32) + Un).astype(Vp.dtype)

    if nc == 1:
        return W_new, v_chunk(Vp, Pp)[:N]

    def v_body(a, Vout):
        Va = jax.lax.dynamic_slice_in_dim(Vp, a * chunk, chunk, axis=0)
        vn = v_chunk(Va, _dyn_chunk(Pp, a, chunk))
        return jax.lax.dynamic_update_slice_in_dim(Vout, vn, a * chunk,
                                                   axis=0)

    Vout = jax.lax.fori_loop(0, nc, v_body, jnp.zeros_like(Vp))
    return W_new, Vout[:N]


def _cross_pair(bd: int, interpret):
    """Pair contraction through the Pallas ``maecho_gram_cross``
    streamer (kernel-route leaves): flat rows are zero-padded to a
    ``bd`` multiple — zero feature columns add zero to every dot."""
    def pair(Ra, Rb):
        return _mg.maecho_gram_cross(_pad_to(Ra, bd, 1)[0],
                                     _pad_to(Rb, bd, 1)[0],
                                     bd=bd, interpret=interpret)
    return pair


def maecho_streaming_gram_chunked(W, V, P, *, chunk: int,
                                  use_kernel: bool = False,
                                  bd: int = 512, interpret=None):
    """Client-chunked gram half: same ``(G, ctx)`` contract as
    :func:`maecho_streaming_gram`, but the (N, N) Gram accumulates
    over client chunks — peak residual residency is O(chunk·out·in),
    not O(N·out·in), which is what lets one aggregation span
    cross-device cohorts (N in the thousands).  With ``use_kernel``
    the (chunk, chunk) pair blocks stream through the Pallas
    ``maecho_gram_cross`` kernel (the ``rank_update`` tiled-accumulator
    idiom); otherwise a jnp dot — bit-identical math either way.
    Layout "oi"; exactness of the client padding lives in
    :func:`_pad_clients`."""
    N = V.shape[0]
    kind = _proj_kind(P)
    Vp, Pp = _pad_clients(W, V, P, chunk, kind)
    pair = (_cross_pair(bd, interpret) if use_kernel
            else _pair_jnp(False))
    G = _chunked_gram_core(W, Vp, Pp, kind, chunk, False, pair)
    return G[:N, :N], ("chunk", kind, W, Vp, Pp, N, chunk)


def maecho_streaming_apply_chunked(alpha, ctx, *, eta: float = 1.0,
                                   frac: float = 0.5,
                                   norm: bool = False,
                                   eps: float = 1e-12):
    """Chunked update half on the context from
    :func:`maecho_streaming_gram_chunked`.  Returns ``(W', V')`` with
    the client axis cropped back to N."""
    _, kind, W, Vp, Pp, N, chunk = ctx
    return _chunked_apply_core(alpha, W, Vp, Pp, kind, chunk, N, False,
                               eta=eta, frac=frac, norm=norm, eps=eps)


def maecho_streaming_gram_chunked_stacked(W, V, P, *, chunk: int,
                                          interpret=None):
    """Stacked client-chunked gram half: W (L, out, in),
    V (N, L, out, in), P stacked per kind.  Returns the (L, N, N)
    Gram stack accumulated over client chunks (pair blocks batch the
    layer axis through one dot_general) plus the apply context."""
    del interpret                      # jnp contraction path
    N = V.shape[0]
    kind = _proj_kind_stacked(P)
    Vp, Pp = _pad_clients(W, V, P, chunk, kind)
    G = _chunked_gram_core(W, Vp, Pp, kind, chunk, True,
                           _pair_jnp(True))
    return G[:, :N, :N], ("stkc", kind, W, Vp, Pp, N, chunk)


def maecho_streaming_apply_chunked_stacked(alpha, ctx, *,
                                           eta: float = 1.0,
                                           frac: float = 0.5,
                                           norm: bool = False,
                                           eps: float = 1e-12):
    """Stacked chunked update half; ``alpha`` is the (L, N) per-layer
    solve stack."""
    _, kind, W, Vp, Pp, N, chunk = ctx
    return _chunked_apply_core(alpha, W, Vp, Pp, kind, chunk, N, True,
                               eta=eta, frac=frac, norm=norm, eps=eps)


def maecho_sharded_gram_chunked(W, V, P, *, mesh, axis="data",
                                chunk: int, stacked: bool = False,
                                block: int = DEFAULT_BLOCK,
                                interpret=None):
    """Out-dim-sharded client-chunked gram half.

    The two memory axes compose: each device owns an out-row shard
    (padded to ``block × axis_size`` rows like the unchunked sharded
    pipeline) AND sweeps the client axis in chunks, so per-device
    residual residency is O(chunk · out/axis_size · in).  One ``psum``
    over ``axis`` reconstructs the replicated Gram — the chunk loop
    adds no collectives.  ``stacked`` selects the (L, out, in) layout
    with the per-layer (L, N, N) Gram stack."""
    del interpret                      # jnp contraction inside the shard
    names = _axis_names(axis)
    asz = axis_size_of(mesh, axis)
    kind = _proj_kind_stacked(P) if stacked else _proj_kind(P)
    N = V.shape[0]
    oax = 1 if stacked else 0
    out_d, in_d = W.shape[-2:]
    Wp = _pad_to(W, block * asz, oax)[0]
    Vr = _pad_to(V, block * asz, oax + 1)[0]
    Vp, Pp = _pad_clients(Wp, Vr, P, chunk, kind)
    pair = _pair_jnp(stacked)
    if stacked:
        wspec = PartitionSpec(None, names, None)
        vspec = PartitionSpec(None, None, names, None)
        gspec = PartitionSpec(None, None, None)
    else:
        wspec = PartitionSpec(names, None)
        vspec = PartitionSpec(None, names, None)
        gspec = PartitionSpec(None, None)

    def rep(x):
        return PartitionSpec(*([None] * x.ndim))

    if kind == "factored":
        pargs = (Pp["U"], Pp["s"])
        pspecs = (rep(Pp["U"]), rep(Pp["s"]))

        def rebuild(U, s):
            return {"U": U, "s": s}
    else:
        pargs = (Pp,)
        pspecs = (rep(Pp),)

        def rebuild(p):
            return p

    def body(Wl, Vl, *ps):
        Gl = _chunked_gram_core(Wl, Vl, rebuild(*ps), kind, chunk,
                                stacked, pair)
        return jax.lax.psum(Gl, names)

    G = jax.shard_map(body, mesh=mesh, in_specs=(wspec, vspec) + pspecs,
                  out_specs=gspec, check_vma=False)(Wp, Vp, *pargs)
    return (G[..., :N, :N],
            ("shc", kind, Wp, Vp, Pp, N, chunk, out_d, in_d))


def maecho_sharded_apply_chunked(alpha, ctx, *, mesh, axis="data",
                                 stacked: bool = False,
                                 eta: float = 1.0, frac: float = 0.5,
                                 norm: bool = False, eps: float = 1e-12):
    """Sharded chunked update half: Eq. 7 + Eq. 11 run row-local on
    each device's owned out-rows, chunk-swept over clients — zero
    collectives (the gram psum is the iteration's only one).  Returns
    ``(W', V')`` cropped to the original out/in dims."""
    _, kind, Wp, Vp, Pp, N, chunk, out_d, in_d = ctx
    names = _axis_names(axis)
    if stacked:
        wspec = PartitionSpec(None, names, None)
        vspec = PartitionSpec(None, None, names, None)
    else:
        wspec = PartitionSpec(names, None)
        vspec = PartitionSpec(None, names, None)

    def rep(x):
        return PartitionSpec(*([None] * x.ndim))

    if kind == "factored":
        pargs = (Pp["U"], Pp["s"])
        pspecs = (rep(Pp["U"]), rep(Pp["s"]))

        def rebuild(U, s):
            return {"U": U, "s": s}
    else:
        pargs = (Pp,)
        pspecs = (rep(Pp),)

        def rebuild(p):
            return p

    def body(a, Wl, Vl, *ps):
        return _chunked_apply_core(a, Wl, Vl, rebuild(*ps), kind, chunk,
                                   N, stacked, eta=eta, frac=frac,
                                   norm=norm, eps=eps)

    Wn, Vn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rep(alpha), wspec, vspec) + pspecs,
                       out_specs=(wspec, vspec),
                       check_vma=False)(alpha, Wp, Vp, *pargs)
    if stacked:
        return Wn[:, :out_d, :in_d], Vn[:, :, :out_d, :in_d]
    return Wn[:out_d, :in_d], Vn[:, :out_d, :in_d]


def flash_attention_auto(q, k, v, *, causal: bool = True, bq: int = 256,
                         bk: int = 256, interpret=None):
    """Pad-to-block front end for the flash kernel.

    Causal self-attention (Sq == Sk): both sequences zero-pad to a
    shared block multiple — padded keys sit strictly after every real
    query, so the causal mask removes them and cropping the padded
    query rows is exact.  Non-causal: the kernel runs only when Sk is
    already a block multiple (zero-padded keys would enter an unmasked
    softmax); query rows still pad/crop freely.  Remaining shapes
    (causal with Sq != Sk — prefill-with-cache offsets) run the jnp
    oracle.
    """
    Sq, Sk = q.shape[1], k.shape[1]
    if causal and Sq == Sk:
        b = min(bq, bk)
        qp, _ = _pad_to(q, b, 1)
        kp, _ = _pad_to(k, b, 1)
        vp, _ = _pad_to(v, b, 1)
        out = flash_attention(qp, kp, vp, causal=True,
                              bq=min(bq, qp.shape[1]),
                              bk=min(bk, kp.shape[1]),
                              interpret=interpret)
        return out[:, :Sq]
    if not causal and Sk % min(bk, Sk) == 0:
        qp, _ = _pad_to(q, min(bq, Sq), 1)
        out = flash_attention(qp, k, v, causal=False,
                              bq=min(bq, qp.shape[1]),
                              bk=min(bk, Sk), interpret=interpret)
        return out[:, :Sq]
    return ref.flash_attention_ref(q, k, v, causal=causal)


def decode_window_block(W: int) -> int | None:
    """Largest supported window block dividing W (None: ineligible).

    Bigger blocks amortise per-block launch overhead; the skip
    granularity stays coarse enough that a partially-filled window
    still drops most dead blocks.
    """
    for bw in (512, 256, DEFAULT_BLOCK):
        if W % bw == 0:
            return bw
    return None


def live_window(w_live: int, W: int) -> int:
    """Round a live-slot upper bound up to a block multiple, capped at W.

    The serving fast path's static crop: a ring buffer whose highest
    written slot (host-known — the serve loop tracks positions in
    Python) is below ``w_live`` only ever has valid slots in
    ``[0, w_live)``, so the attention read can slice the cache there.
    Rounding to ``DEFAULT_BLOCK`` keeps the crop kernel-eligible and
    bounds recompiles to the caller's bucketing policy.
    """
    return min(W, -(-int(w_live) // DEFAULT_BLOCK) * DEFAULT_BLOCK)


def decode_attention_auto(q, k_cache, v_cache, valid_mask, *,
                          interpret=None, w_live: int | None = None,
                          layer=None):
    """Single-token KV-cache attention: Pallas window kernel when the
    window divides a block, dense jnp oracle otherwise (warn-once —
    the serving loop rounds its window to a block multiple precisely
    so this path stays hot).

    Caches are (B, W, Hkv, D), or the stacked (L, B, W, Hkv, D) cache
    read at ``layer`` (an int32 scalar), which the kernel indexes in
    place.

    ``w_live`` (static python int) is the serving loop's bucketed
    upper bound on written ring-buffer slots: the mask is cropped to
    it, and the kernel's grid covers only those window blocks, so a
    mostly-empty window pays only its live blocks in bytes touched,
    not just blocks skipped.  Wraparound (any position ≥ W) must pass
    ``w_live=None`` / ``>= W`` — the serve loop's bucket hits W exactly
    then.
    """
    W = k_cache.shape[-3]
    if w_live is not None:
        W = live_window(w_live, W)
        valid_mask = valid_mask[:, :W]
    bw = decode_window_block(W)
    if bw is None:
        fallback_warn(
            f"decode window W={W} is not a {DEFAULT_BLOCK}-multiple: "
            f"running the dense jnp decode oracle")
        if layer is not None:
            k_cache, v_cache = k_cache[layer], v_cache[layer]
        return ref.decode_attention_ref(q, k_cache, v_cache, valid_mask)
    return decode_attention(q, k_cache, v_cache, valid_mask, layer, bw=bw,
                            interpret=interpret)
