"""Compile-once aggregation plans: the routing layer of MA-Echo.

Every ``maecho_aggregate`` call used to re-derive, per outer iteration
and per call site, which compute path each leaf takes — if/else chains
(`_use_kernel` / `_use_sharded` / `_stacked_route` / `_dispatch_leaf`)
smeared across ``core.maecho``, with ``dispatch_summary`` maintaining a
*second* copy of the same logic that could silently drift from what
actually executed.  This module replaces all of that with a
plan-then-execute split:

  - :func:`compile_plan` runs ONCE per (treedef, shapes, projector
    kinds, convention, stack_levels, backend, mesh, config) — the key
    is memoized, so repeated aggregations over the same model reuse
    the identical :class:`AggPlan` object — and produces one frozen
    :class:`LeafPlan` per leaf: the route, the kernel-layout dims, the
    padding edge, the kernels' blocks (:func:`kernel_blocks`), and the
    mesh axes that shard (and psum) it.
  - ``core.maecho``'s outer loop is a pure executor over those plans:
    it looks up ``leaf.route`` and calls the matching gram/apply pair.
    ``dispatch_summary`` is a *view* of the same compiled plan, so the
    coverage it reports is definitionally the coverage that runs.

Routes:

  ``oracle``     the jnp reference path (vmapped over a stacked leaf's
                 layer axis); consumes no mesh axes.
  ``kernel``     the fused streaming Pallas pipeline: the leaf's
                 (flattened) scan-layer axis rides the kernel grid as
                 its outermost dimension, a 2-D leaf being the stack
                 L = 1 (``levels`` says which a leaf is).
  ``sharded``    the same pipeline with out-rows shard_map'd over
                 ``cfg.mesh_axis``; one (L, N, N) Gram psum over that
                 axis per leaf per outer iteration.
  ``sharded2d``  the 2-D (out × in) shard: out-rows over
                 ``cfg.mesh_axis`` AND in-columns over
                 ``cfg.mesh_in_axis`` ("model"), partial Grams psum'd
                 over BOTH axis groups in one collective; the apply
                 stays row/col-local.  Covers leaves whose out-dim
                 alone is too small to span the fleet.

All routing decisions are static-shape-only: arrays and
``jax.ShapeDtypeStruct`` trees are interchangeable inputs.  A forced
fast path (backend != "oracle"/"auto") that degrades to a weaker route
is surfaced once via ``ops.fallback_warn`` at plan-compile time —
silent degradation is the failure mode the plan layer exists to kill.
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache
from typing import Any, NamedTuple

import jax

from repro.utils import trees

BACKENDS = ("oracle", "kernel", "auto", "sharded", "sharded2d")
ROUTES = ("oracle", "kernel", "sharded", "sharded2d")

Pytree = Any


def _backend_error(backend) -> str:
    return (f"unknown backend {backend!r}; valid choices: "
            + ", ".join(BACKENDS))


def validate_backend(backend: str) -> None:
    """Reject unknown backend strings with the full choice list —
    shared by ``maecho_aggregate`` and the launch CLIs so a typo'd
    backend can never fall through to a default route."""
    if backend not in BACKENDS:
        raise ValueError(_backend_error(backend))


class Blocks(NamedTuple):
    """The kernels' blocks for one leaf: out-rows ``bo``, columns
    ``bi``, contraction ``bk`` (0 for the elementwise diagonal kinds);
    ``steps``, the grid steps of the leaf's largest launch; and
    ``fallback``, set where nothing larger than the 128 cube fit
    ``env.VMEM_BUDGET`` and the rule fell back to it."""
    bo: int
    bi: int
    bk: int
    steps: int
    fallback: bool = False


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Frozen per-leaf routing decision.

    ``out_d`` / ``in_d`` are the kernel-layout ("oi"-native) trailing
    dims — already convention-swapped; ``block`` is the padding edge:
    the ``_eff_block``-clamped ``cfg.kernel_block`` on the kernel
    route (``DEFAULT_BLOCK`` when it is 0), and ``DEFAULT_BLOCK`` on
    the sharded pipelines, which pad to it times the axis size;
    ``blocks`` are the kernels' :class:`Blocks`, which divide the
    padded extents (:func:`kernel_blocks`, or ``cfg.kernel_block``'s
    cube where it is set); ``out_axes`` / ``in_axes`` are the
    mesh axis names sharding the leaf (empty on unsharded routes), and
    their concatenation is exactly the psum axis set of the leaf's one
    Gram collective.  ``client_chunk`` is the effective client-axis
    chunk (``cfg.client_chunk`` clamped to N; 0 = unchunked): when set,
    the leaf's Gram accumulates over blocks of clients so only
    ``client_chunk`` projections are resident per step."""
    path: str
    levels: int                 # leading stacked-layer axes (post-flatten)
    route: str                  # one of ROUTES
    kind: str                   # scalar | diag | full | factored
    out_d: int = 0
    in_d: int = 0
    block: int = 0
    out_axes: tuple = ()
    in_axes: tuple = ()
    client_chunk: int = 0
    blocks: Blocks | None = None

    @property
    def psum_axes(self) -> tuple:
        return self.out_axes + self.in_axes

    def specs(self, mesh, convention: str, w_ndim: int, p) -> tuple:
        """``(W, V, P)`` PartitionSpecs of this leaf's executor operands
        in the caller's ``convention``: the layout both the gram's and
        the apply's ``shard_map`` take on the sharded routes, so
        operands placed in it reach the kernels with no reshard.
        ``w_ndim`` counts W's axes (leading stack axes, flattened or
        not, stay replicated); ``p`` is the stacked projector leaf, or
        the factored kind's dict, which the P spec mirrors.

        Out-rows split over ``out_axes``, V's client axis in front.
        Everything else is whole on every device: on ``sharded2d`` each
        device forms its in-column tile from whole rows and a whole
        projector, since Eq. 11 contracts full rows, and a dim the
        executor pads (out-dim not a multiple of ``block`` × its axis
        size) stays replicated, since padding a split dim would move
        data between devices."""
        from jax.sharding import PartitionSpec

        from repro.kernels.ops import axis_size_of

        def rep(x):
            return PartitionSpec(*([None] * len(x.shape)))

        w = [None] * w_ndim
        if self.out_axes and self.out_d % (
                self.block * axis_size_of(mesh, self.out_axes)) == 0:
            w[-2 if convention == "oi" else -1] = self.out_axes
        pspec = trees.tree_map(rep, p) if isinstance(p, dict) else rep(p)
        return PartitionSpec(*w), PartitionSpec(None, *w), pspec


@dataclasses.dataclass(frozen=True)
class AggPlan:
    """The compiled plan for one aggregation: per-leaf routes in
    ``tree_flatten`` order plus the dispatch inputs they were derived
    from.  Hashable — it is a static argument of the jitted executor,
    so one plan compiles to one XLA program."""
    backend: str
    convention: str
    leaves: tuple  # tuple[LeafPlan, ...]

    def per_leaf(self) -> list:
        """``dispatch_summary``'s per-leaf view: (path, levels, route)."""
        return [(lp.path, lp.levels, lp.route) for lp in self.leaves]

    def route_counts(self) -> dict:
        counts: dict = {}
        for lp in self.leaves:
            counts[lp.route] = counts.get(lp.route, 0) + 1
        return counts


# --------------------------------------------------------------------------
# static-shape predicates (ShapeDtypeStructs and arrays both work)
# --------------------------------------------------------------------------
def kernel_eligible(W, P, levels: int = 0) -> bool:
    """Leaf shapes the fused pipelines handle: a 2-D weight (plus
    ``levels`` leading stacked-layer axes) with a scalar / diagonal /
    dense / factored projector whose kind axes shift by the same
    ``levels``."""
    if getattr(W, "ndim", 0) != 2 + levels:
        return False
    if isinstance(P, dict):
        return (set(P) == {"U", "s"}
                and getattr(P["U"], "ndim", 0) == 3 + levels)
    return getattr(P, "ndim", -1) in (1 + levels, 2 + levels, 3 + levels)


def kernel_dims(W, convention: str) -> tuple:
    """(out_d, in_d) of a leaf in the "oi"-native kernel layout — the
    trailing two axes, swapped for "io" (stack axes don't matter)."""
    out_d, in_d = W.shape[-2:]
    return (out_d, in_d) if convention == "oi" else (in_d, out_d)


def proj_kind(P, levels: int = 0) -> str:
    """Kind of a *stacked* (leading client axis) projector leaf with
    ``levels`` leading layer axes."""
    if isinstance(P, dict):
        return "factored"
    nd = getattr(P, "ndim", -1) - levels
    if nd == 1:
        return "scalar"
    if nd == 2:
        return "diag"
    return "full"


def _axis_names(axis) -> tuple:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _mesh_has(mesh, axis) -> bool:
    return mesh is not None and all(
        n in mesh.shape for n in _axis_names(axis))


def leaf_route(W, P, levels: int, cfg, convention: str, backend: str,
               mesh=None, path: str = "") -> str:
    """Route of a single leaf under the given dispatch inputs — the one
    copy of the routing rules (:func:`compile_plan` maps it over the
    tree).  Static shapes only."""
    return _route_leaf(path, W, P, levels, cfg, convention, backend,
                       mesh).route


def _eff_chunk(cfg, P, eligible: bool) -> int:
    """Effective client-axis chunk for one leaf: ``cfg.client_chunk``
    clamped to the client count N (chunk ≥ N would only manufacture
    dead padded clients), 0 on ineligible leaves — 1-D biases and
    other oracle-only shapes never chunk."""
    ck = int(getattr(cfg, "client_chunk", 0) or 0)
    if not eligible or ck <= 0:
        return 0
    n = (P["U"].shape[0] if isinstance(P, dict) else P.shape[0])
    return min(ck, int(n))


def _plan_leaf(path: str, W, P, levels: int, cfg, convention: str,
               backend: str, mesh) -> LeafPlan:
    lp = _route_leaf(path, W, P, levels, cfg, convention, backend, mesh)
    if lp.route == "oracle":
        return lp
    return dataclasses.replace(lp, blocks=_leaf_blocks(lp, W, P, cfg,
                                                       mesh))


def _route_leaf(path: str, W, P, levels: int, cfg, convention: str,
                backend: str, mesh) -> LeafPlan:
    from repro.kernels import ops

    eligible = kernel_eligible(W, P, levels)
    kind = proj_kind(P, levels) if eligible else "none"
    ck = _eff_chunk(cfg, P, eligible)
    if not eligible or backend == "oracle":
        if eligible is False and backend not in ("oracle", "auto") \
                and getattr(W, "ndim", 0) > 1:
            # a forced fast path silently running the oracle is the
            # drift mode the plan layer guards — warn once at compile
            ops.fallback_warn(
                f"leaf {path or '<leaf>'} (shape={tuple(W.shape)}, "
                f"levels={levels}) ineligible for backend="
                f"{backend!r}: falling back to the "
                f"{'vmapped ' if levels else ''}jnp oracle")
        return LeafPlan(path, levels, "oracle", kind, client_chunk=ck)
    out_d, in_d = kernel_dims(W, convention)
    sub_tile = min(out_d, in_d) < ops.DEFAULT_BLOCK

    if backend == "sharded2d" and ck:
        # the 2-D shard splits the in-columns, but the chunked residual
        # sweep contracts full rows per client block — the combination
        # has no kernel.  Degrade loudly to the 1-D out-dim shard,
        # which composes with chunking (rows × client blocks).
        ops.fallback_warn(
            f"leaf {path or '<leaf>'} requests backend='sharded2d' "
            f"with client_chunk={ck}: the 2-D shard does not compose "
            f"with client chunking — degrading to the 1-D out-dim "
            f"shard")
    elif backend == "sharded2d" and _mesh_has(mesh, cfg.mesh_axis):
        if _mesh_has(mesh, cfg.mesh_in_axis):
            from repro.sharding.rules import sharded_ok2d

            osz = ops.axis_size_of(mesh, cfg.mesh_axis)
            isz = ops.axis_size_of(mesh, cfg.mesh_in_axis)
            if sharded_ok2d(out_d, in_d, osz, isz, warn=True):
                return LeafPlan(path, levels, "sharded2d", kind, out_d,
                                in_d, ops.DEFAULT_BLOCK,
                                _axis_names(cfg.mesh_axis),
                                _axis_names(cfg.mesh_in_axis))
        else:
            # the in-axis is simply absent from the mesh — still a
            # forced-2-D request degrading, so warn like every other
            # rung of the fallback chain
            ops.fallback_warn(
                f"mesh lacks the in-axis {cfg.mesh_in_axis!r} for "
                f"backend='sharded2d': leaf {path or '<leaf>'} "
                f"(out={out_d}, in={in_d}) degrading to the 1-D "
                f"out-dim shard / single-device dispatch")
    if backend in ("sharded", "sharded2d") \
            and _mesh_has(mesh, cfg.mesh_axis):
        if ops.sharded_ok(out_d, in_d,
                          ops.axis_size_of(mesh, cfg.mesh_axis),
                          warn=True):
            return LeafPlan(path, levels, "sharded", kind, out_d, in_d,
                            ops.DEFAULT_BLOCK,
                            _axis_names(cfg.mesh_axis),
                            client_chunk=ck)
    # single-device streaming rule: "kernel" forces it for any
    # tileable leaf; "auto" (and the sharded backends' fallback)
    # promotes only leaves big enough to tile.  Sub-tile leaves run
    # the oracle — the plan records what actually executes (the old
    # dispatch forced them into the streaming wrappers, which then
    # ref-fell-back internally).
    if not sub_tile:
        block = _eff_tile(cfg, out_d, in_d)
        return LeafPlan(path, levels, "kernel", kind, out_d, in_d, block,
                        client_chunk=ck)
    if backend not in ("oracle", "auto"):
        ops.fallback_warn(
            f"{'stacked ' if levels else ''}leaf {path or '<leaf>'} "
            f"(out={out_d}, in={in_d}"
            f"{f', levels={levels}' if levels else ''}) below one "
            f"{ops.DEFAULT_BLOCK}-tile for backend={backend!r}: "
            f"running the {'vmapped ' if levels else ''}jnp oracle "
            f"instead of the streaming kernels")
    return LeafPlan(path, levels, "oracle", kind, out_d, in_d,
                    client_chunk=ck)


def _eff_tile(cfg, out_d: int, in_d: int) -> int:
    from repro.kernels.ops import DEFAULT_BLOCK, _eff_block

    return _eff_block(cfg.kernel_block or DEFAULT_BLOCK, out_d, in_d)


# --------------------------------------------------------------------------
# the kernels' blocks
# --------------------------------------------------------------------------
def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _divisors(ext: int, unit: int) -> list:
    """The multiples of ``unit`` that divide ``ext``, largest first;
    ``[ext]`` where there are none (a rank below one lane tile)."""
    return [d for d in range(ext - ext % unit, 0, -unit)
            if ext % d == 0] or [ext]


def kernel_blocks(kind: str, n: int, layers: int, out_ext: int,
                  in_ext: int, in_gram: int, kd: int, norm: bool,
                  cube: int = 0) -> Blocks:
    """The blocks a leaf's three MA-Echo kernels take.

    The extents are the local ones each kernel sees, padded: ``out_ext``
    rows and ``in_ext`` columns in the Eq. 7/11 applies, ``in_gram``
    columns in the Gram (smaller on the 2-D shard), ``kd`` the
    contraction (``in_ext`` for a dense projector, the padded rank for a
    factored one, 0 for the elementwise diagonal kinds), ``n`` clients
    (or the client chunk) and ``layers`` the stack.  ``bi`` and ``bk``
    span whole rows where they fit, so each operand block is one
    contiguous run of rows and W and V cross HBM once; ``bo``, a
    multiple of 8 that divides ``out_ext``, is then as large as fits,
    so that the projectors are read ``out_ext / bo`` times.  A block
    fits when each launch's VMEM (the kernel modules' ``vmem``, with
    whole rows in Eq. 11 under ``norm``) is within ``env.VMEM_BUDGET``
    and one client's Gram tile within ``maecho_gram.PAIR_TILE``.
    ``bk``, then ``bi``, shrink where whole rows fit at no ``bo`` of at
    least 128 (or all the rows), so that no operand is read more often
    than the 128 cube reads it; ``bo`` goes below that floor only where
    nothing fits above it (a large ``n``); where nothing fits at all,
    the rule falls back to the 128 cube.  ``cube``, where set
    (``MAEchoConfig.kernel_block``), is taken as every edge."""
    from repro.kernels import env
    from repro.kernels import maecho_gram as mg
    from repro.kernels import maecho_update as mu
    from repro.kernels import maecho_v_update as mv

    op = {"full": "full", "factored": "left"}.get(kind, "diag")

    def steps(bo, bi, bk):
        rows = layers * out_ext // bo
        ks = kd // bk if kd else 1
        # the diagonal kinds' Gram and Eq. 7 take every client a step
        cl = n if kd else 1
        gram = rows * (in_gram // min(bi, in_gram)) * cl * ks
        update = rows * (in_ext // bi) * cl * ks
        v_update = rows * n * (1 if norm else in_ext // bi) * ks
        return max(gram, update, v_update)

    def fits(bo, bi, bk):
        gi = min(bi, in_gram)
        need = max(mg.vmem(op, n, bo, gi, bk), mu.vmem(op, n, bo, bi, bk),
                   mv.vmem(op, n, bo, in_ext if norm else bi, bk))
        return bo * gi <= mg.PAIR_TILE and need <= env.VMEM_BUDGET

    if cube:
        edges = (min(cube, out_ext), min(cube, in_ext), min(cube, kd))
        return Blocks(*edges, steps(*edges))
    for floor in (min(128, out_ext), 8):
        for bi in [in_ext] + [d for d in _divisors(in_gram, 128)
                              if d < in_ext]:
            for bk in _divisors(kd, 128) if kd else [0]:
                for bo in _divisors(out_ext, 8):
                    if bo >= floor and fits(bo, bi, bk):
                        return Blocks(bo, bi, bk, steps(bo, bi, bk))
    edges = (min(128, out_ext), min(128, in_ext), min(128, kd))
    return Blocks(*edges, steps(*edges), fallback=True)


def _leaf_blocks(lp: LeafPlan, W, P, cfg, mesh) -> Blocks:
    """:func:`kernel_blocks` at the extents the leaf's kernels see on
    its route: padded to ``lp.block`` (times the axis size on a mesh
    route, as ``ops`` pads), out-rows split over ``out_axes``, the
    Gram's columns over ``in_axes``."""
    from repro.kernels.ops import axis_size_of

    osz = axis_size_of(mesh, lp.out_axes) if lp.out_axes else 1
    isz = axis_size_of(mesh, lp.in_axes) if lp.in_axes else 1
    pad = lp.block
    in_ext = _round_up(lp.in_d, pad * isz)
    if lp.kind == "factored":
        rank = P["U"].shape[-1]
        kd = _round_up(rank, pad) if rank > pad else rank
    else:
        kd = in_ext if lp.kind == "full" else 0
    n = lp.client_chunk or (P["U"] if lp.kind == "factored"
                            else P).shape[0]
    cube = lp.block if lp.route == "kernel" and cfg.kernel_block else 0
    return kernel_blocks(lp.kind, int(n), math.prod(W.shape[:lp.levels]),
                         _round_up(lp.out_d, pad * osz) // osz, in_ext,
                         in_ext // isz, kd, cfg.norm, cube)


# --------------------------------------------------------------------------
# compile + memoization
# --------------------------------------------------------------------------
class _ShapeOnly:
    """Hashable stand-in for a leaf in the memo key (shape is the only
    attribute routing reads)."""
    __slots__ = ("shape", "ndim")

    def __init__(self, shape):
        self.shape = tuple(int(d) for d in shape)
        self.ndim = len(self.shape)

    def __hash__(self):
        return hash(self.shape)

    def __eq__(self, other):
        return (isinstance(other, _ShapeOnly)
                and self.shape == other.shape)


def _leaf_key(p):
    if isinstance(p, dict):
        return {"U": _ShapeOnly(p["U"].shape),
                "s": _ShapeOnly(p["s"].shape)}
    return _ShapeOnly(p.shape)


class _FrozenProj:
    """Hashable wrapper for a projector descriptor (dicts don't hash)."""
    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def _key(self):
        v = self.value
        return (("factored", v["U"].shape, v["s"].shape)
                if isinstance(v, dict) else ("array", v.shape))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return (isinstance(other, _FrozenProj)
                and self._key() == other._key())


@lru_cache(maxsize=256)
def _compile_cached(leaf_descs, cfg, convention, backend, mesh):
    leaves = tuple(
        _plan_leaf(path, w, p.value, lv, cfg, convention, backend, mesh)
        for path, w, p, lv in leaf_descs)
    return AggPlan(backend=backend, convention=convention, leaves=leaves)


def compile_plan(W0: Pytree, P: Pytree, levels_tree: Pytree, cfg,
                 convention: str = "oi", backend: str = "oracle",
                 mesh=None) -> AggPlan:
    """Compile (or fetch the memoized) :class:`AggPlan` for a model.

    ``W0`` / ``P`` are the global-weight and *stacked* (leading client
    axis) projector trees — arrays or ``jax.ShapeDtypeStruct``s both
    work, routing is static-shape-only.  ``levels_tree`` is the
    per-leaf stacked-layer-axis count (a matching pytree).  The memo
    key is (per-leaf path/shape/kind/levels, cfg, convention, backend,
    mesh): a second call over the same model returns the *same* plan
    object, so the executor's jit cache is hit instead of re-traced.
    """
    validate_backend(backend)
    treedef = jax.tree_util.tree_structure(W0)
    paths = [p for p, _ in trees.tree_paths(W0)]
    flatW = jax.tree_util.tree_leaves(W0)
    flatP = treedef.flatten_up_to(P)
    flatL = jax.tree_util.tree_leaves(levels_tree)
    descs = tuple(
        (path, _ShapeOnly(w.shape), _FrozenProj(_leaf_key(p)), int(lv))
        for path, w, p, lv in zip(paths, flatW, flatP, flatL))
    return _compile_cached(descs, cfg, convention, backend, mesh)


def plan_cache_info():
    """lru_cache stats of the plan memo (tests pin the reuse contract
    — same treedef/shapes/config must NOT recompile)."""
    return _compile_cached.cache_info()


def plan_cache_clear() -> None:
    _compile_cached.cache_clear()
