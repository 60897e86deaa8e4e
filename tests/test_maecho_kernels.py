"""Fused streaming MA-Echo pipeline: property-based kernel-vs-oracle
parity (interpret mode) across projector kinds, shapes (tiled, padded,
sub-tile), conventions, stack_levels 0–3 and ragged client masks —
the strategies live in ``tests/strategies.py``; under the container's
deterministic hypothesis stub each ``@given`` runs a fixed seeded
sample, and the real ``hypothesis`` library upgrades the same tests to
adaptive property search.  Hand-picked regression cases (rank above
one tile, exact sub-tile fallback, the "io" transposition contract,
fori_loop + norm) stay alongside.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

import strategies as strat
from repro.core import projections as proj
from repro.core.maecho import MAEchoConfig, maecho_aggregate
from repro.kernels import ops, ref


def _one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _one_device_mesh2d():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


CFG = MAEchoConfig(tau=2, eta=0.5, qp_iters=60)


# --------------------------------------------------------------------------
# kernel-level property parity: a 2-D leaf through the streaming halves
# as the stack L = 1 (kind normalisation, padding, sub-tile fallback)
# --------------------------------------------------------------------------
def _one_layer(V, P):
    """V and the stacked projector with a layer axis of 1 behind the
    clients."""
    return V[:, None], jax.tree_util.tree_map(lambda x: x[:, None], P)


def _gram1(W, V, P):
    """The (N, N) Gram of one 2-D leaf, and the apply context."""
    G, ctx = ops.maecho_streaming_gram_stacked(W[None], *_one_layer(V, P))
    return G[0], ctx


def _apply1(W, V, P, alpha, **kw):
    """Eq. 7 then Eq. 11 on one 2-D leaf: ``(W', V')``."""
    _, ctx = _gram1(W, V, P)
    Wn, Vn = ops.maecho_streaming_apply_stacked(alpha[None], ctx, **kw)
    return Wn[0], Vn[:, 0]


@given(strat.seeds(), strat.n_clients(), strat.kinds(), strat.shapes())
@settings(max_examples=8, deadline=None)
def test_gram_parity(seed, n, kind, shape):
    W, V, P = strat.build_layer(seed, n, kind, shape)
    got, _ = _gram1(W, V, P)
    want = ref.maecho_gram_ref(W, V, P)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2, rtol=1e-4)


@given(strat.seeds(), strat.n_clients(), strat.kinds(), strat.shapes(),
       strat.bools())
@settings(max_examples=8, deadline=None)
def test_v_update_parity(seed, n, kind, shape, norm):
    W, V, P = strat.build_layer(seed, n, kind, shape)
    # α = 0 and η = 0 leave W' = W exactly: Eq. 11 alone, from W
    _, got = _apply1(W, V, P, jnp.zeros((n,)), eta=0.0, frac=0.5,
                     norm=norm)
    want = ref.maecho_v_update_ref(W, V, P, 0.5, norm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@given(strat.seeds(), strat.n_clients(), strat.kinds(), strat.shapes())
@settings(max_examples=8, deadline=None)
def test_update_parity(seed, n, kind, shape):
    W, V, P = strat.build_layer(seed, n, kind, shape)
    alpha = jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(seed + 1), (n,)))
    got, _ = _apply1(W, V, P, alpha, eta=0.7)
    want = ref.maecho_update_ref_any(W, V, P, alpha, 0.7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# stacked kernel-level property parity: the layer axis on the grid
# --------------------------------------------------------------------------
@given(strat.seeds(), strat.n_clients(), strat.kinds(),
       strat.shapes(), strat.bools())
@settings(max_examples=6, deadline=None)
def test_streaming_stacked_parity(seed, n, kind, shape, norm):
    L = 2 + seed % 3
    W, V, P = strat.build_layer(seed, n, kind, shape, lead=(L,))
    alpha = jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(seed + 2), (L, n)), axis=-1)

    def step(W, V, P):
        G, ctx = ops.maecho_streaming_gram_stacked(W, V, P)
        Wn, Vn = ops.maecho_streaming_apply_stacked(
            alpha, ctx, eta=0.7, frac=0.5, norm=norm)
        return G, Wn, Vn

    G, Wn, Vn = jax.jit(step)(W, V, P)
    Gr = jax.vmap(ref.maecho_gram_ref, in_axes=(0, 1, 1))(W, V, P)
    Wr = jax.vmap(lambda w, v, p, a:
                  ref.maecho_update_ref_any(w, v, p, a, 0.7),
                  in_axes=(0, 1, 1, 0))(W, V, P, alpha)
    Vr = jax.vmap(lambda w, v, p:
                  ref.maecho_v_update_ref(w, v, p, 0.5, norm),
                  in_axes=(0, 1, 1), out_axes=1)(Wr, V, P)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               atol=1e-2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(Wn), np.asarray(Wr),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(Vn), np.asarray(Vr),
                               atol=1e-4)


@given(strat.seeds(), strat.n_clients(), strat.kinds(),
       strat.block_cases(), strat.bools())
@settings(max_examples=8, deadline=None)
def test_derived_blocks_parity(seed, n, kind, case, norm):
    """The kernels at the blocks the plan derives — whole padded rows,
    out-row blocks that are not powers of two, budgets small enough to
    split rows and columns — match the jnp oracle, on the kernel route
    and, on a one-device (1, 1) mesh, on the 2-D route."""
    from jax.sharding import Mesh

    from repro.core import plan as plan_mod
    from repro.kernels import env

    shape, budget = case
    W, V, P = strat.build_layer(seed, n, kind, shape, lead=(2,))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    route = ("kernel", "sharded2d")[seed % 2]
    cfg = MAEchoConfig(norm=norm)
    saved = env.VMEM_BUDGET
    env.VMEM_BUDGET = budget or saved
    plan_mod.plan_cache_clear()
    try:
        lp = plan_mod.compile_plan(W, P, 1, cfg, "oi", route,
                                   mesh).leaves[0]
    finally:
        env.VMEM_BUDGET = saved
        plan_mod.plan_cache_clear()
    assert lp.route == route and not lp.blocks.fallback
    alpha = jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(seed + 3), (2, n)), axis=-1)
    kw = dict(eta=0.7, frac=0.5, norm=norm)

    def step(W, V, P):
        if route == "kernel":
            G, ctx = ops.maecho_streaming_gram_stacked(W, V, P,
                                                       blocks=lp.blocks)
            return (G,) + ops.maecho_streaming_apply_stacked(alpha, ctx,
                                                             **kw)
        G, ctx = ops.maecho_sharded2d_gram_stacked(W, V, P, mesh=mesh,
                                                   blocks=lp.blocks)
        return (G,) + ops.maecho_sharded2d_apply_stacked(alpha, ctx,
                                                         mesh=mesh, **kw)

    G, Wn, Vn = jax.jit(step)(W, V, P)
    # the oracle's residuals, contracted in float64: one f32 dot over a
    # whole 4864 × 256 residual strays further from the exact Gram
    # than the kernels' per-block sums do
    R = np.asarray(jax.vmap(ref._residuals, in_axes=(0, 1, 1),
                            out_axes=1)(W, V, P), np.float64)
    Rf = R.reshape(R.shape[:2] + (-1,))
    Gr = np.einsum("nlk,mlk->lnm", Rf, Rf)
    Wr = jax.vmap(lambda w, v, p, a:
                  ref.maecho_update_ref_any(w, v, p, a, 0.7),
                  in_axes=(0, 1, 1, 0))(W, V, P, alpha)
    Vr = jax.vmap(lambda w, v, p:
                  ref.maecho_v_update_ref(w, v, p, 0.5, norm),
                  in_axes=(0, 1, 1), out_axes=1)(Wr, V, P)
    np.testing.assert_allclose(np.asarray(G), Gr, atol=1e-2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(Wn), np.asarray(Wr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(Vn), np.asarray(Vr), atol=1e-4)


# --------------------------------------------------------------------------
# full-aggregate property parity: oracle vs kernel/auto/sharded, with
# stacked leaves, mixed trees, both conventions and ragged masks
# --------------------------------------------------------------------------
def _agg(clients, projs, levels, convention, backend, mesh=None,
         mask=None, cfg=CFG):
    return maecho_aggregate(clients, projs, cfg, convention=convention,
                            stack_levels=levels, backend=backend,
                            mesh=mesh, client_mask=mask)


def _assert_close(a, b, tol=1e-3):
    for key in ("W", "b"):
        np.testing.assert_allclose(np.asarray(a[key]),
                                   np.asarray(b[key]), atol=tol)


@given(strat.seeds(), strat.n_clients(), strat.kinds(),
       strat.conventions(), strat.leads(), strat.shapes(),
       strat.masked())
@settings(max_examples=8, deadline=None)
def test_aggregate_parity_all_backends(seed, n, kind, convention, lead,
                                       shape, use_mask):
    """The acceptance property: kernel / auto / sharded all match the
    oracle to <1e-3 on a mixed pytree — any projector kind, either
    convention, stack_levels 0–3, tiled / padded / sub-tile shapes,
    with and without ragged client masks."""
    clients, projs, levels, mask = strat.build_case(
        seed, n, kind, convention, lead, shape, use_mask)
    want = _agg(clients, projs, levels, convention, "oracle", mask=mask)
    for backend, mesh in (("kernel", None), ("auto", None),
                          ("sharded", _one_device_mesh()),
                          ("sharded2d", _one_device_mesh2d())):
        got = _agg(clients, projs, levels, convention, backend,
                   mesh=mesh, mask=mask)
        _assert_close(want, got)


@pytest.mark.parametrize("convention", strat.CONVENTIONS)
@pytest.mark.parametrize("kind", strat.KINDS)
def test_aggregate_parity_each_kind_pinned(kind, convention):
    """Sampler-proof floor under the property test above: every
    projector kind × convention pair is guaranteed to exercise the
    kernel and sharded backends on a stacked leaf — in particular the
    dense-P "io" transposition contract (`_to_kernel_layout`'s
    trailing-axes swap) — whatever the (stub or real) sampler happens
    to draw."""
    clients, projs, levels, _ = strat.build_case(
        7, 3, kind, convention, (2,), (128, 128), False)
    want = _agg(clients, projs, levels, convention, "oracle")
    for backend, mesh in (("kernel", None),
                          ("sharded", _one_device_mesh()),
                          ("sharded2d", _one_device_mesh2d())):
        _assert_close(want, _agg(clients, projs, levels,
                                 backend=backend,
                                 convention=convention, mesh=mesh))


@given(strat.seeds(), strat.kinds(), strat.leads())
@settings(max_examples=4, deadline=None)
def test_aggregate_parity_sequential_qp(seed, kind, lead):
    """The ``qp_batched=False`` path dispatches per leaf (stacked
    leaves vmap the per-layer QP) — same parity bound."""
    cfg = dataclasses.replace(CFG, qp_batched=False)
    clients, projs, levels, _ = strat.build_case(
        seed, 3, kind, "oi", lead, (256, 140), False)
    want = _agg(clients, projs, levels, "oi", "oracle", cfg=cfg)
    got = _agg(clients, projs, levels, "oi", "kernel", cfg=cfg)
    _assert_close(want, got)


# --------------------------------------------------------------------------
# hand-picked regression cases
# --------------------------------------------------------------------------
def test_factored_rank_above_one_tile():
    """rank > 128 exercises the rank-axis padding/tiling path."""
    W, V, _ = strat.build_layer(31, 2, "diag", (128, 256))
    Ps = [strat.make_projector(jax.random.PRNGKey(50 + i), "factored",
                               (), 256, rank=150) for i in range(2)]
    P = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *Ps)
    got, _ = _gram1(W, V, P)
    want = ref.maecho_gram_ref(W, V, P)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2, rtol=1e-4)


def test_small_shapes_fall_back_to_oracle():
    """Below one tile the streaming gram must return the oracle result
    exactly."""
    W, V, P = strat.build_layer(37, 2, "full", (6, 4))
    np.testing.assert_allclose(
        np.asarray(_gram1(W, V, P)[0]),
        np.asarray(ref.maecho_gram_ref(W, V, P)), rtol=1e-6)


def test_backend_kernel_fori_loop_and_norm():
    """tau > 4 exercises the fori_loop outer path with kernels inside;
    norm=True exercises the fused row-norm."""
    clients, projs, levels, _ = strat.build_case(
        5, 3, "full", "oi", (), (140, 200), False)
    cfg = MAEchoConfig(tau=6, eta=0.5, qp_iters=60, norm=True, mu=2.0)
    a = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="kernel")
    np.testing.assert_allclose(np.asarray(a["W"]),
                               np.asarray(b["W"]), atol=1e-3)


def test_backend_stacked_fori_loop():
    """Stacked leaf under the fori_loop outer path (tau > 4): the
    stacked kernel grid lives inside the loop body."""
    clients, projs, levels, _ = strat.build_case(
        11, 3, "factored", "oi", (3,), (256, 140), False)
    cfg = MAEchoConfig(tau=6, eta=0.5, qp_iters=60)
    a = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="kernel")
    np.testing.assert_allclose(np.asarray(a["W"]),
                               np.asarray(b["W"]), atol=1e-3)


def test_backend_rejects_unknown():
    clients, projs, levels, _ = strat.build_case(
        11, 2, "scalar", "oi", (), (48, 64), False)
    with pytest.raises(ValueError):
        maecho_aggregate(clients, projs, MAEchoConfig(tau=1),
                         backend="gpu")


@pytest.mark.slow
def test_factor_projection_roundtrip_through_pipeline():
    """factor_projection output plugs straight into the kernel backend
    and agrees with the dense projector it factors (exact rank)."""
    d, r = 256, 256
    X = jax.random.normal(jax.random.PRNGKey(0), (40, d))
    P = proj.projection_from_features(X, 1e-3)
    clients, _, levels, _ = strat.build_case(
        13, 2, "scalar", "oi", (), (140, d), False)
    dense = [{"W": P, "b": jnp.ones(())} for _ in range(2)]
    fact = [{"W": proj.factor_projection(P, r), "b": jnp.ones(())}
            for _ in range(2)]
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=60)
    a = maecho_aggregate(clients, dense, cfg, backend="kernel")
    b = maecho_aggregate(clients, fact, cfg, backend="kernel")
    np.testing.assert_allclose(np.asarray(a["W"]),
                               np.asarray(b["W"]), atol=1e-3)
