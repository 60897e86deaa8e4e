"""Compile-once plan/executor layer (ISSUE 5 tentpole): plan caching,
summary/execution anti-drift regression, backend validation, and the
2-D (out × in) sharded route's psum/launch/parity contracts."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from jax.sharding import Mesh

import strategies as strat
from repro.core import plan as plan_mod
from repro.core.maecho import (MAEchoConfig, dispatch_summary,
                               maecho_aggregate)
from repro.core.plan import compile_plan, leaf_route
from repro.kernels import ops, ref
from repro.sharding.rules import sharded_ok2d


class FakeMesh:
    """Shape-only mesh stand-in (cf. tests/test_sharding.py)."""

    def __init__(self, shape: dict):
        self.shape = shape


def _mesh1d():
    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _mesh2d():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def _model(out_d=256, in_d=256, n=3, kind="diag", lead=()):
    clients, projs = [], []
    for i in range(n):
        k = jax.random.fold_in(jax.random.PRNGKey(out_d + in_d), i)
        clients.append({
            "W": jax.random.normal(k, lead + (out_d, in_d)) * 0.3,
            "b": jax.random.normal(jax.random.fold_in(k, 1),
                                   (out_d,)) * 0.1})
        projs.append({
            "W": strat.make_projector(jax.random.fold_in(k, 2), kind,
                                      lead, in_d),
            "b": jnp.ones(())})
    return clients, projs, {"W": len(lead), "b": 0}


def _stacked_P(projs):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *projs)


# --------------------------------------------------------------------------
# plan compilation + memoization
# --------------------------------------------------------------------------
def test_compile_plan_is_memoized():
    """Same treedef/shapes/config -> the SAME AggPlan object (the
    compile-once contract; the executor's jit cache keys off it)."""
    cfg = MAEchoConfig(qp_iters=123)
    sds = jax.ShapeDtypeStruct
    W0 = {"W": sds((256, 256), jnp.float32)}
    P = {"W": sds((3, 256), jnp.float32)}
    levels = {"W": 0}
    p1 = compile_plan(W0, P, levels, cfg, "oi", "auto", None)
    p2 = compile_plan(W0, P, levels, cfg, "oi", "auto", None)
    assert p1 is p2
    # shapes / cfg / backend each key the cache
    p3 = compile_plan({"W": sds((512, 256), jnp.float32)},
                      P, levels, cfg, "oi", "auto", None)
    assert p3 is not p1
    p4 = compile_plan(W0, P, levels,
                      dataclasses.replace(cfg, qp_iters=7),
                      "oi", "auto", None)
    assert p4 is not p1
    assert p4 == p1          # ...but routing is identical
    assert compile_plan(W0, P, levels, cfg, "oi", "kernel",
                        None) is not p1


def test_aggregate_reuses_compiled_plan():
    """Repeated maecho_aggregate calls over the same model hit the
    plan memo — no recompilation per call (and a fortiori none per
    outer iteration: the τ-loop runs inside one jitted executor)."""
    clients, projs, levels = _model(kind="full")
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=119)
    maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                     backend="auto")
    before = plan_mod.plan_cache_info()
    maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                     backend="auto")
    after = plan_mod.plan_cache_info()
    assert after.hits == before.hits + 1
    assert after.misses == before.misses


def test_plan_leaf_fields():
    """The plan records kernel layout, tile size and psum axes."""
    cfg = MAEchoConfig()
    mesh = FakeMesh({"data": 2, "model": 2})
    sds = jax.ShapeDtypeStruct
    W0 = {"W": sds((512, 256), jnp.float32),
          "b": sds((512,), jnp.float32)}
    P = {"W": sds((3, 256, 256), jnp.float32),
         "b": sds((3,), jnp.float32)}
    plan = compile_plan(W0, P, {"W": 0, "b": 0}, cfg, "oi",
                        "sharded2d", mesh)
    by_path = {lp.path: lp for lp in plan.leaves}
    w = by_path["W"]
    assert w.route == "sharded2d" and w.kind == "full"
    assert (w.out_d, w.in_d) == (512, 256)
    assert w.psum_axes == ("data", "model")
    assert w.out_axes == ("data",) and w.in_axes == ("model",)
    b = by_path["b"]
    assert b.route == "oracle" and b.psum_axes == ()
    # "io" swaps the kernel-layout dims
    plan_io = compile_plan({"W": sds((256, 512), jnp.float32)},
                           {"W": sds((3, 256, 256), jnp.float32)},
                           {"W": 0}, cfg, "io", "sharded2d", mesh)
    assert (plan_io.leaves[0].out_d, plan_io.leaves[0].in_d) == (512,
                                                                 256)


# --------------------------------------------------------------------------
# backend validation: unknown strings never fall through to a default
# --------------------------------------------------------------------------
def test_unknown_backend_rejected_with_choices():
    clients, projs, levels = _model()
    with pytest.raises(ValueError, match="sharded2d"):
        maecho_aggregate(clients, projs, MAEchoConfig(tau=1),
                         backend="warp")
    with pytest.raises(ValueError, match="valid choices"):
        compile_plan(clients[0], _stacked_P(projs), levels,
                     MAEchoConfig(), "oi", "gpu", None)
    with pytest.raises(ValueError, match="valid choices"):
        dispatch_summary(clients[0], _stacked_P(projs), levels,
                         MAEchoConfig(), "oi", "AUTO", None)


def test_dryrun_backend_rejected():
    """`dryrun_agg.run` (the programmatic entry under the CLI) rejects
    unknown backends up front instead of falling through to auto, and
    the argparse layer lists the valid choices."""
    env_before = os.environ.get("XLA_FLAGS")
    try:
        # the module import sets XLA_FLAGS for subprocess use; jax is
        # already initialized in-process, so restore it afterwards
        from repro.launch import dryrun_agg
        with pytest.raises(ValueError, match="valid choices"):
            dryrun_agg.run("llama3_8b", 2, False, backend="warp")
        argv = sys.argv
        try:
            sys.argv = ["dryrun_agg", "--backend", "warp"]
            with pytest.raises(SystemExit) as e:
                dryrun_agg.main()
            assert e.value.code == 2       # argparse usage error
        finally:
            sys.argv = argv
    finally:
        if env_before is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = env_before


# --------------------------------------------------------------------------
# anti-drift regression: the summary IS what executes
# --------------------------------------------------------------------------
_TRACE_BUST = [1000]


class _GramTap:
    """Wrap every gram entry point (plain setattr, restored in
    close()) so executing an aggregation leaves the per-leaf route
    trail it ACTUALLY took (at trace time).  Not a pytest fixture —
    @given re-runs the test body per example, and real-hypothesis
    forbids function-scoped fixtures inside property tests."""

    _NAMES = {"_leaf_gram_oracle": "oracle",
              "_leaf_gram_kernel": "kernel",
              "_leaf_gram_sharded": "sharded",
              "_leaf_gram_sharded2d": "sharded2d"}

    def __init__(self):
        import repro.core.maecho as M

        self.mod = M
        self.record = []
        self.saved = {}

        def wrap(tag, fn):
            def inner(*a, **k):
                self.record.append(tag)
                return fn(*a, **k)
            return inner

        for name, tag in self._NAMES.items():
            self.saved[name] = getattr(M, name)
            setattr(M, name, wrap(tag, self.saved[name]))
        orig_stacked = M._leaf_gram_stacked
        self.saved["_leaf_gram_stacked"] = orig_stacked

        def stacked(W, V, P, cfg, convention, route, *args, **kw):
            self.record.append(route)
            return orig_stacked(W, V, P, cfg, convention, route,
                                *args, **kw)

        M._leaf_gram_stacked = stacked

    def close(self):
        for name, fn in self.saved.items():
            setattr(self.mod, name, fn)


@given(strat.seeds(), strat.n_clients(), strat.kinds(),
       strat.conventions(), strat.leads(), strat.shapes(),
       strat.bools())
@settings(max_examples=8, deadline=None)
def test_summary_matches_execution(seed, n, kind, convention, lead,
                                   shape, batched):
    """THE drift regression (satellite 1): across the property-harness
    strategy space and every backend, the per-leaf route
    dispatch_summary reports is byte-identical to the route the
    executor's gram phase actually takes."""
    clients, projs, levels, _ = strat.build_case(
        seed, n, kind, convention, lead, shape, False)
    backends = [("kernel", None), ("auto", None),
                ("sharded", _mesh1d()), ("sharded2d", _mesh2d())]
    backend, mesh = backends[seed % len(backends)]
    _TRACE_BUST[0] += 1
    # unique qp_iters busts the executor's jit cache so the trace
    # (where dispatch happens) reruns for this exact case
    cfg = MAEchoConfig(tau=1, eta=0.5, qp_iters=_TRACE_BUST[0],
                       qp_batched=batched)
    tap = _GramTap()
    try:
        maecho_aggregate(clients, projs, cfg, convention=convention,
                         stack_levels=levels, backend=backend,
                         mesh=mesh)
    finally:
        tap.close()
    per_leaf, _ = dispatch_summary(
        clients[0], _stacked_P(projs), levels, cfg, convention,
        backend, mesh)
    assert tap.record == [r for _, _, r in per_leaf], (
        backend, tap.record, per_leaf)


def test_executor_handles_levels2_oracle_leaf_directly():
    """Regression: direct _maecho_jit callers (the dryrun driver) hand
    levels >= 2 leaves straight to the executor WITHOUT
    maecho_aggregate's multi-level flattening — the oracle route must
    collapse the leading stack axes itself (MoE expert / hybrid mamba
    layouts) instead of vmapping a still-stacked leaf."""
    from repro.core.maecho import _maecho_jit

    n, lead, out_d, in_d = 3, (2, 2), 24, 8   # sub-tile -> oracle
    clients, projs = [], []
    for i in range(n):
        k = jax.random.fold_in(jax.random.PRNGKey(5), i)
        clients.append({"W": jax.random.normal(
            k, lead + (out_d, in_d)) * 0.3})
        projs.append({"W": jnp.ones(lead)})   # stacked scalar rule
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40)
    W0 = jax.tree_util.tree_map(
        lambda *xs: sum(xs) / len(xs), *clients)
    V0 = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *clients)
    P = _stacked_P(projs)
    plan = compile_plan(W0, P, {"W": 2}, cfg, "oi", "oracle", None)
    assert plan.leaves[0].route == "oracle"
    W, _ = _maecho_jit(W0, V0, P, cfg, "oi", plan, None)
    # parity with the public path (which pre-flattens multi stacks)
    want = maecho_aggregate(clients, projs, cfg,
                            stack_levels={"W": 2}, backend="oracle")
    np.testing.assert_allclose(np.asarray(W["W"]),
                               np.asarray(want["W"]), atol=1e-5)


# --------------------------------------------------------------------------
# sharded2d: eligibility gating + fallback chain
# --------------------------------------------------------------------------
def test_sharded_ok2d_divisibility():
    # 1024 = 8 out-tiles, 512 = 4 in-tiles
    assert sharded_ok2d(1024, 512, 8, 4)
    assert sharded_ok2d(1024, 512, 2, 2)
    assert sharded_ok2d(1024, 512, 8, 1)     # degenerate 1-D
    assert not sharded_ok2d(1024, 512, 3, 4)  # out tiles % 3
    assert not sharded_ok2d(1024, 512, 8, 3)  # in tiles % 3
    # below one tile on either dim: never sharded
    assert not sharded_ok2d(64, 512, 1, 1)
    assert not sharded_ok2d(1024, 64, 1, 1)
    # the fleet-spanning case: out too small for 1-D over 8 devices
    # but fine as 2 x 4
    assert not ops.sharded_ok(256, 512, 8)
    assert sharded_ok2d(256, 512, 2, 4)


def test_sharded2d_route_fallback_chain():
    """sharded2d -> sharded -> kernel -> oracle, each gate static."""
    cfg = MAEchoConfig()
    mesh = FakeMesh({"data": 2, "model": 4})
    P = jnp.zeros((3, 512, 512))

    def route(w_shape, m=mesh, c=cfg, P=P):
        return leaf_route(jnp.zeros(w_shape), P, 0, c, "oi",
                          "sharded2d", m)

    assert route((256, 512)) == "sharded2d"   # 2x4 spans 8 devices
    # in-tiles don't divide the model axis: 1-D out-row fallback
    assert route((256, 384)) == "sharded"
    # neither axis divides (320 -> 3 out-tiles): single-device kernel
    assert route((320, 384)) == "kernel"
    # sub-tile: oracle
    assert route((64, 64)) == "oracle"
    # mesh without the in-axis: 1-D fallback
    assert route((256, 512),
                 m=FakeMesh({"data": 2})) == "sharded"
    # stacked leaves ride the same gates
    assert leaf_route(jnp.zeros((4, 256, 512)),
                      jnp.zeros((3, 4, 512, 512)), 1, cfg, "oi",
                      "sharded2d", mesh) == "sharded2d"


def test_sharded2d_missing_in_axis_warns_once():
    """A forced-2-D request on a mesh without the in-axis is still a
    degradation — it must surface via fallback_warn like every other
    rung of the chain, not silently run 1-D."""
    import warnings

    cfg = MAEchoConfig()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        r = leaf_route(jnp.zeros((768, 512)),
                       jnp.zeros((3, 512, 512)), 0, cfg, "oi",
                       "sharded2d", FakeMesh({"data": 2}))
    assert r == "sharded"
    assert any("lacks the in-axis" in str(w.message) for w in rec)


def test_agg_partition_specs_2d():
    """The plan's placement specs on the 2-D shard (``LeafPlan.specs``):
    out-rows over the data axes only — the apply contracts whole rows,
    so the devices along "model" hold the same rows — and every
    projector kind whole, dense and factored alike."""
    from jax.sharding import PartitionSpec as P

    mesh = FakeMesh({"data": 2, "model": 4})
    cfg = MAEchoConfig()
    W = {"d": jax.ShapeDtypeStruct((2, 512, 256), jnp.float32),
         "f": jax.ShapeDtypeStruct((512, 256), jnp.float32)}
    Ps = {"d": jax.ShapeDtypeStruct((3, 2, 512, 512), jnp.float32),
          "f": {"U": jax.ShapeDtypeStruct((3, 512, 8), jnp.float32),
                "s": jax.ShapeDtypeStruct((3, 8), jnp.float32)}}
    plan = compile_plan(W, Ps, {"d": 1, "f": 0}, cfg, "io", "sharded2d",
                        mesh)
    lp = {x.path: x for x in plan.leaves}
    assert {x.route for x in plan.leaves} == {"sharded2d"}
    assert lp["d"].specs(mesh, "io", 3, Ps["d"]) == (
        P(None, None, ("data",)), P(None, None, None, ("data",)),
        P(None, None, None, None))
    assert lp["f"].specs(mesh, "io", 2, Ps["f"]) == (
        P(None, ("data",)), P(None, None, ("data",)),
        {"U": P(None, None, None), "s": P(None, None)})


# --------------------------------------------------------------------------
# sharded2d contracts: ONE two-axis psum + L-independent launch count
# --------------------------------------------------------------------------
def test_exactly_one_two_axis_psum_per_leaf_per_iteration():
    """The acceptance contract: a sharded2d leaf costs exactly ONE
    psum per outer iteration, taken over BOTH mesh axis groups at
    once — and the apply phase is collective-free."""
    mesh = _mesh2d()
    tau = 2
    clients, projs, levels = _model(out_d=256, in_d=256, kind="full")
    cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=40)
    txt = str(jax.make_jaxpr(
        lambda: maecho_aggregate(clients, projs, cfg,
                                 stack_levels=levels,
                                 backend="sharded2d", mesh=mesh))())
    assert txt.count("psum") == tau, (
        f"expected {tau} psums (one per outer iteration), "
        f"found {txt.count('psum')}")
    assert txt.count("axes=('data', 'model')") == tau, (
        "every sharded2d psum must cover both axis groups in one "
        "collective")


@pytest.mark.parametrize("L", [2, 4])
def test_sharded2d_stacked_one_psum_and_three_launches(L):
    """A stacked sharded2d leaf: one (L, N, N) two-axis psum per outer
    iteration and exactly 3 Pallas launches per iteration (gram,
    Eq. 7, Eq. 11) independent of L — the layer axis rides the grid
    inside each 2-D shard."""
    mesh = _mesh2d()
    tau = 2
    clients, projs, levels = _model(out_d=256, in_d=256, kind="full",
                                    lead=(L,))
    cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=40)
    txt = str(jax.make_jaxpr(
        lambda: maecho_aggregate(clients, projs, cfg,
                                 stack_levels=levels,
                                 backend="sharded2d", mesh=mesh))())
    assert txt.count("axes=('data', 'model')") == tau
    assert txt.count("pallas_call") == 3, txt.count("pallas_call")


# --------------------------------------------------------------------------
# sharded2d parity (single device; the 8-device run rides the CI
# smoke `dryrun_agg --sharded-smoke`, which executes the 2-D stage)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["scalar", "diag", "full", "factored"])
def test_sharded2d_gram_apply_parity_one_device(kind):
    N, out_d, in_d = 3, 256, 140          # odd in-dim: padding path
    mesh = _mesh2d()
    k = jax.random.PRNGKey(out_d + in_d)
    W = jax.random.normal(k, (out_d, in_d)) * 0.3
    V = jax.random.normal(jax.random.fold_in(k, 1),
                          (N, out_d, in_d)) * 0.3
    Ps = [strat.make_projector(jax.random.fold_in(k, 10 + i), kind,
                               (), in_d) for i in range(N)]
    P = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *Ps)
    alpha = jax.nn.softmax(jax.random.normal(jax.random.fold_in(k, 9),
                                             (N,)))

    def step(W, V, P):
        G, ctx = ops.maecho_sharded2d_gram(W, V, P, mesh=mesh,
                                           axis_out="data",
                                           axis_in="model")
        Wn, Vn = ops.maecho_sharded2d_apply(
            alpha, ctx, mesh=mesh, axis_out="data", axis_in="model",
            eta=0.7, frac=0.5, norm=True)
        return G, Wn, Vn

    G, Wn, Vn = jax.jit(step)(W, V, P)
    Gr = ref.maecho_gram_ref(W, V, P)
    Wr = ref.maecho_update_ref_any(W, V, P, alpha, 0.7)
    Vr = ref.maecho_v_update_ref(Wr, V, P, 0.5, True)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               atol=1e-2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(Wn), np.asarray(Wr),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(Vn), np.asarray(Vr),
                               atol=1e-4)


def test_sharded2d_backend_aggregate_parity_sequential_qp():
    """The qp_batched=False path routes through the same plan."""
    clients, projs, levels = _model(kind="factored")
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=60, qp_batched=False)
    a = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="sharded2d", mesh=_mesh2d())
    np.testing.assert_allclose(np.asarray(a["W"]), np.asarray(b["W"]),
                               atol=1e-3)


# --------------------------------------------------------------------------
# true multi-device 2-D runs (fresh process: XLA flag precedes jax)
# --------------------------------------------------------------------------
@pytest.mark.slow
def test_sharded2d_parity_8dev_two_by_four():
    """Acceptance: a (2, 4) factored fleet aggregates a leaf whose
    out-dim cannot span 8 devices 1-D, to <1e-3 of the oracle, with
    exactly one two-axis psum per leaf per outer iteration — the
    subprocess half of the CI smoke, at pytest granularity."""
    import pathlib
    import subprocess
    import textwrap

    repo = pathlib.Path(__file__).resolve().parent.parent
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.dryrun_agg import run_sharded2d_smoke
        assert len(jax.devices()) == 8, jax.devices()
        err, counts, cov_ok = run_sharded2d_smoke(8, tau=2)
        assert err < 1e-3, err
        assert cov_ok and counts.get("sharded2d", 0) >= 3, counts
        print("SHARDED2D_OK", err)
    """)
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(repo / "src") + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED2D_OK" in r.stdout
