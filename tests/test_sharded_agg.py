"""Mesh-sharded one-shot aggregation (ISSUE 3 tentpole).

In-process tests run on the real single device (the shard_map path at
axis size 1, eligibility logic against shape-only fake meshes, psum
accounting, the debug-mesh shortfall error).  True multi-device runs
need ``XLA_FLAGS=--xla_force_host_platform_device_count`` set before
jax initializes, which a pytest session can't do retroactively — those
parity/fallback checks subprocess (marked ``slow``).
"""
import dataclasses
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.maecho import MAEchoConfig, maecho_aggregate
from repro.core.plan import leaf_route
from repro.kernels import ops, ref
from repro.launch.mesh import make_debug_mesh

REPO = pathlib.Path(__file__).resolve().parent.parent


class FakeMesh:
    """Shape-only mesh stand-in (cf. tests/test_sharding.py)."""

    def __init__(self, shape: dict):
        self.shape = shape


def _one_device_mesh():
    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _proj_of_kind(k, kind, N, in_d, rank=24):
    if kind == "scalar":
        return jax.random.uniform(jax.random.fold_in(k, 2), (N,))
    if kind == "diag":
        return jax.random.uniform(jax.random.fold_in(k, 2), (N, in_d))
    U = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(k, 2),
                                        (N, in_d, min(rank, in_d))))[0]
    s = jax.random.uniform(jax.random.fold_in(k, 3),
                           (N, min(rank, in_d)))
    if kind == "factored":
        return {"U": U, "s": s}
    return jnp.einsum("nik,nk,njk->nij", U, s, U)


# --------------------------------------------------------------------------
# eligibility: the block-granular `_ok` divisibility contract
# --------------------------------------------------------------------------
def test_sharded_ok_divisibility():
    # 1024 = 8 tiles of 128: divides over 1/2/4/8, not 3
    for asz in (1, 2, 4, 8):
        assert ops.sharded_ok(1024, 256, asz)
    assert not ops.sharded_ok(1024, 256, 3)
    # 300 -> 3 tiles: not divisible by 8
    assert not ops.sharded_ok(300, 256, 8)
    assert ops.sharded_ok(300, 256, 3)
    # below one tile on either dim: never sharded
    assert not ops.sharded_ok(64, 256, 1)
    assert not ops.sharded_ok(1024, 64, 8)
    # padding rounds 4000 up to 32 tiles
    assert ops.sharded_ok(4000, 128, 8)


def test_axis_size_of():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert ops.axis_size_of(mesh, "data") == 16
    assert ops.axis_size_of(mesh, ("pod", "data")) == 32
    assert ops.axis_size_of(mesh, "absent") == 1


def test_sharded_route_fallback_paths():
    """The routing rules `_use_sharded` used to encode, now pinned on
    the plan compiler's single copy (``plan.leaf_route``)."""
    mesh = FakeMesh({"data": 8, "model": 1})
    cfg = MAEchoConfig()
    W = jnp.zeros((1024, 256))
    P = jnp.zeros((3, 256, 256))
    def route(w, p, backend, m, conv="oi", c=cfg):
        return leaf_route(w, p, 0, c, conv, backend, m)
    assert route(W, P, "sharded", mesh) == "sharded"
    # io convention: the kernel-layout out-dim is W.shape[1]
    assert route(W.T, P, "sharded", mesh, "io") == "sharded"
    assert route(W.T, P, "sharded", mesh, "oi") != "sharded"
    # non-divisible out, wrong backend, missing mesh, 1-D leaf
    assert route(jnp.zeros((300, 256)), P, "sharded",
                 mesh) == "kernel"
    assert route(W, P, "kernel", mesh) == "kernel"
    assert route(W, P, "sharded", None) == "kernel"
    assert route(jnp.zeros((1024,)), jnp.zeros((3,)), "sharded",
                 mesh) == "oracle"
    # a mesh without the configured axis: fall back, don't KeyError
    assert route(W, P, "sharded", FakeMesh({"x": 8})) == "kernel"
    assert route(W, P, "sharded", mesh,
                 c=dataclasses.replace(
                     cfg, mesh_axis=("pod", "data"))) == "kernel"


def test_sharded_backend_mesh_without_axis_falls_back():
    """A mesh lacking cfg.mesh_axis degrades to the single-device
    path end-to-end instead of crashing inside shard_map."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    N = 3
    clients = [{"W": jax.random.normal(jax.random.PRNGKey(i),
                                       (256, 140)) * 0.3}
               for i in range(N)]
    projs = [{"W": jax.random.uniform(jax.random.PRNGKey(9 + i),
                                      (140,))}
             for i in range(N)]
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40)
    a = maecho_aggregate(clients, projs, cfg, backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, backend="sharded",
                         mesh=mesh)
    np.testing.assert_allclose(np.asarray(a["W"]), np.asarray(b["W"]),
                               atol=1e-3)


# --------------------------------------------------------------------------
# single-device mesh: the shard_map path itself (axis size 1)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["scalar", "diag", "full", "factored"])
def test_sharded_gram_apply_parity_one_device(kind):
    N, out_d, in_d = 3, 256, 140          # odd in-dim: padding path
    mesh = _one_device_mesh()
    k = jax.random.PRNGKey(out_d + in_d)
    W = jax.random.normal(k, (out_d, in_d)) * 0.3
    V = jax.random.normal(jax.random.fold_in(k, 1),
                          (N, out_d, in_d)) * 0.3
    P = _proj_of_kind(k, kind, N, in_d)
    alpha = jax.nn.softmax(jax.random.normal(jax.random.fold_in(k, 9),
                                             (N,)))

    def step(W, V, P):
        G, ctx = ops.maecho_sharded_gram(W, V, P, mesh=mesh,
                                         axis="data")
        Wn, Vn = ops.maecho_sharded_apply(alpha, ctx, mesh=mesh,
                                          axis="data", eta=0.7,
                                          frac=0.5, norm=True)
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


@pytest.mark.parametrize("convention", ["oi", "io"])
def test_sharded_backend_aggregate_parity_one_device(convention):
    """backend="sharded" through maecho_aggregate (mixed tree with a
    bias on the oracle fallback) matches the oracle."""
    N = 3
    clients, projs = [], []
    for i in range(N):
        k = jax.random.PRNGKey(11 * i + 3)
        shape = (256, 140) if convention == "oi" else (140, 256)
        clients.append({"W": jax.random.normal(k, shape) * 0.3,
                        "b": jax.random.normal(jax.random.fold_in(k, 1),
                                               (shape[0],)) * 0.1})
        U = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(k, 2),
                                            (140, 16)))[0]
        s = jax.random.uniform(jax.random.fold_in(k, 3), (16,))
        projs.append({"W": (U * s) @ U.T, "b": jnp.ones(())})
    cfg = MAEchoConfig(tau=3, eta=0.5, qp_iters=60)
    a = maecho_aggregate(clients, projs, cfg, convention=convention,
                         backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, convention=convention,
                         backend="sharded", mesh=_one_device_mesh())
    np.testing.assert_allclose(np.asarray(a["W"]), np.asarray(b["W"]),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(a["b"]), np.asarray(b["b"]),
                               atol=1e-3)


def test_exactly_one_psum_per_outer_iteration():
    """The acceptance contract: a sharded leaf costs ONE (N, N) psum
    per outer iteration — the gram reconstruction — and the apply
    phase is collective-free."""
    mesh = _one_device_mesh()
    N, tau = 3, 2
    clients = [{"W": jax.random.normal(jax.random.PRNGKey(i),
                                       (256, 140)) * 0.3}
               for i in range(N)]
    projs = [{"W": jax.random.uniform(jax.random.PRNGKey(50 + i),
                                      (140,))}
             for i in range(N)]
    cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=40)
    txt = str(jax.make_jaxpr(
        lambda: maecho_aggregate(clients, projs, cfg,
                                 backend="sharded", mesh=mesh))())
    assert txt.count("psum") == tau, (
        f"expected {tau} psums (one per outer iteration), "
        f"found {txt.count('psum')}")


def test_divisibility_fallback_eligibility():
    """A leaf whose out-dim tiles don't divide the axis is rejected by
    the eligibility check (8-way fake mesh) and the same model still
    aggregates cleanly under backend="sharded" (the real-axis psum-free
    fallback runs in the 8-device subprocess test below)."""
    mesh = FakeMesh({"data": 8, "model": 1})
    real = _one_device_mesh()
    N = 3
    clients = [{"W": jax.random.normal(jax.random.PRNGKey(i),
                                       (300, 140)) * 0.3}
               for i in range(N)]
    projs = [{"W": jax.random.uniform(jax.random.PRNGKey(9 + i),
                                      (140,))}
             for i in range(N)]
    assert leaf_route(clients[0]["W"], jnp.zeros((N, 140)), 0,
                      MAEchoConfig(), "oi", "sharded",
                      mesh) != "sharded"
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40)
    a = maecho_aggregate(clients, projs, cfg, backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, backend="sharded",
                         mesh=real)
    np.testing.assert_allclose(np.asarray(a["W"]), np.asarray(b["W"]),
                               atol=1e-3)


def test_make_debug_mesh_raises_on_shortfall():
    with pytest.raises(RuntimeError, match=r"needs 4096 devices"):
        make_debug_mesh(64, 64)


def test_agg_partition_specs():
    """The plan's placement specs on the 1-D shard (``LeafPlan.specs``):
    W's out-rows over the data axes — the last axis for "io" — V's the
    same behind its client axis, projectors whole; a leaf whose out-dim
    the executor pads, and every leaf off the mesh routes, whole."""
    from jax.sharding import PartitionSpec as P

    from repro.core.plan import compile_plan

    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    cfg = MAEchoConfig(mesh_axis=("pod", "data"))
    W = {"w": jax.ShapeDtypeStruct((8192, 1024), jnp.float32),
         "pad": jax.ShapeDtypeStruct((4000, 1024), jnp.float32),
         "b": jax.ShapeDtypeStruct((1024,), jnp.float32)}
    Ps = {"w": jax.ShapeDtypeStruct((8, 1024, 1024), jnp.float32),
          "pad": jax.ShapeDtypeStruct((8, 1024), jnp.float32),
          "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    plan = compile_plan(W, Ps, {"w": 0, "pad": 0, "b": 0}, cfg, "oi",
                        "sharded", mesh)
    lp = {x.path: x for x in plan.leaves}
    rows = ("pod", "data")
    assert lp["w"].specs(mesh, "oi", 2, Ps["w"]) == (
        P(rows, None), P(None, rows, None), P(None, None, None))
    assert lp["w"].specs(mesh, "io", 3, Ps["w"]) == (
        P(None, None, rows), P(None, None, None, rows),
        P(None, None, None))
    # 4000 rows pad to 32 tiles of 128: sharded, but placed whole
    assert lp["pad"].route == "sharded"
    assert lp["pad"].specs(mesh, "oi", 2, Ps["pad"])[0] == P(None, None)
    assert lp["b"].route == "oracle"
    assert lp["b"].specs(mesh, "oi", 1, Ps["b"]) == (
        P(None), P(None, None), P(None))


# --------------------------------------------------------------------------
# true 8-device runs (fresh process: XLA flag must precede jax init)
# --------------------------------------------------------------------------
def _run_forced(code_or_args, n_devices=8):
    env = {**os.environ,
           "REPRO_HOST_DEVICES": str(n_devices),
           "PYTHONPATH": str(REPO / "src") + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    if isinstance(code_or_args, str):
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{n_devices}")
        args = [sys.executable, "-c", code_or_args]
    else:
        args = [sys.executable] + code_or_args
    return subprocess.run(args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.slow
def test_sharded_smoke_4dev():
    """The smoke CLI at a non-CI axis size (CI's full lane runs the
    same entry point at 8 devices — 4 here keeps the coverage
    distinct instead of paying for the identical run twice)."""
    r = _run_forced(["-m", "repro.launch.dryrun_agg",
                     "--sharded-smoke", "--smoke-devices", "4"],
                    n_devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[ok] sharded smoke" in r.stdout


@pytest.mark.slow
def test_sharded_parity_kinds_conventions_8dev():
    """Acceptance: 8-way sharded aggregation matches the single-device
    oracle to <1e-3 across projector kinds and weight conventions,
    with exactly one (N, N) psum per outer iteration."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.maecho import MAEchoConfig, maecho_aggregate
        assert len(jax.devices()) == 8, jax.devices()
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        N, out_d, in_d, tau = 3, 1024, 256, 2
        cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=40)

        def mk(kind, conv):
            cs, ps = [], []
            for i in range(N):
                k = jax.random.PRNGKey(13 * i + 1)
                shape = (out_d, in_d) if conv == "oi" else (in_d, out_d)
                cs.append({"W": jax.random.normal(k, shape) * 0.3})
                if kind == "scalar":
                    pw = jnp.ones(())
                elif kind == "diag":
                    pw = jax.random.uniform(jax.random.fold_in(k, 2),
                                            (in_d,))
                else:
                    U = jnp.linalg.qr(jax.random.normal(
                        jax.random.fold_in(k, 2), (in_d, 24)))[0]
                    s = jax.random.uniform(jax.random.fold_in(k, 3),
                                           (24,))
                    pw = ({"U": U, "s": s} if kind == "factored"
                          else (U * s) @ U.T)
                ps.append({"W": pw})
            return cs, ps

        combos = ([(kind, "oi") for kind in
                   ("scalar", "diag", "full", "factored")]
                  + [("full", "io"), ("factored", "io")])
        for kind, conv in combos:
            cs, ps = mk(kind, conv)
            a = maecho_aggregate(cs, ps, cfg, convention=conv,
                                 backend="oracle")
            b = maecho_aggregate(cs, ps, cfg, convention=conv,
                                 backend="sharded", mesh=mesh)
            err = float(jnp.max(jnp.abs(a["W"] - b["W"])))
            assert err < 1e-3, (kind, conv, err)
            txt = str(jax.make_jaxpr(
                lambda cs=cs, ps=ps, conv=conv: maecho_aggregate(
                    cs, ps, cfg, convention=conv, backend="sharded",
                    mesh=mesh))())
            assert txt.count("psum") == tau, (kind, conv,
                                              txt.count("psum"))
            print(f"ok {kind}/{conv}: err={err:.2e}")
        print("ALL_OK")
    """)
    r = _run_forced(code)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_OK" in r.stdout


@pytest.mark.slow
def test_divisibility_fallback_8dev():
    """out=300 (3 tiles) over 8 devices: no crash, no psum, oracle
    parity — the clean single-device fallback at real axis size."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.maecho import MAEchoConfig, maecho_aggregate
        assert len(jax.devices()) == 8
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        N = 3
        cs = [{"W": jax.random.normal(jax.random.PRNGKey(i),
                                      (300, 140)) * 0.3}
              for i in range(N)]
        ps = [{"W": jax.random.uniform(jax.random.PRNGKey(9 + i),
                                       (140,))}
              for i in range(N)]
        cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40)
        a = maecho_aggregate(cs, ps, cfg, backend="oracle")
        b = maecho_aggregate(cs, ps, cfg, backend="sharded", mesh=mesh)
        err = float(jnp.max(jnp.abs(a["W"] - b["W"])))
        assert err < 1e-3, err
        txt = str(jax.make_jaxpr(
            lambda: maecho_aggregate(cs, ps, cfg, backend="sharded",
                                     mesh=mesh))())
        assert txt.count("psum") == 0, txt.count("psum")
        print("FALLBACK_OK", err)
    """)
    r = _run_forced(code)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FALLBACK_OK" in r.stdout


# --------------------------------------------------------------------------
# the program's own mesh and placement: aggregate_llm(backend="sharded2d")
# with no mesh, host checkpoints placed straight into their shards
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,grid", [(1, (1, 1)), (2, (2, 1)), (4, (2, 2)),
                                    (6, (3, 2)), (8, (4, 2)),
                                    (16, (4, 4))])
def test_mesh_grid_is_as_square_as_the_count_allows(n, grid):
    from repro.core.maecho import mesh_grid

    assert mesh_grid(n) == grid


def _host_case(init="average"):
    """Three host (numpy) checkpoints of a two-leaf model with a stacked
    leaf, dense and scalar projectors: the layout a federation operator
    hands over."""
    rng = np.random.default_rng(5)
    clients = [{"w": rng.standard_normal((2, 256, 128), np.float32),
                "b": rng.standard_normal((2, 128), np.float32)}
               for _ in range(3)]
    projs = []
    for _ in range(3):
        x = rng.standard_normal((2, 256, 16), np.float32)
        projs.append({"w": np.einsum("lik,ljk->lij", x, x) / 16,
                      "b": np.ones((2,), np.float32)})
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40, init=init)
    return clients, projs, cfg


@pytest.mark.parametrize("init", ["average", "first"])
def test_one_chip_placement_matches_stacking_on_the_host(init):
    """With no mesh, placing each leaf and making W0 on the device gives
    the bits of the old flow: W0 by ``init_global`` over the host
    checkpoints, anchors and projectors stacked by ``jnp.stack``."""
    from repro.core import maecho
    from repro.core.plan import compile_plan

    clients, projs, cfg = _host_case(init)
    levels = {"w": 1, "b": 1}
    got = maecho_aggregate(clients, projs, cfg, convention="io",
                           stack_levels=levels, backend="auto")
    W0 = maecho.init_global(clients, init)
    V0 = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    P = jax.tree.map(lambda *xs: jnp.stack(xs), *projs)
    plan = compile_plan(W0, P, levels, cfg, "io", "auto", None)
    want, _ = maecho._maecho_jit(W0, V0, P, cfg, "io", plan)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


def test_one_chip_placement_moves_each_client_once():
    """``maecho.place_bytes`` on one chip: every host anchor and
    projector once (W0 is made on the device, not moved again)."""
    import time

    from repro.utils import spans

    clients, projs, cfg = _host_case()
    t0 = time.perf_counter_ns()
    maecho_aggregate(clients, projs, cfg, convention="io",
                     stack_levels={"w": 1, "b": 1}, backend="auto")
    recs = [r for r in spans.records(t0) if r.name == "maecho.place_bytes"]
    host = sum(x.nbytes for t in clients + projs for x in t.values())
    assert [(r.attrs["device"], r.attrs["n"]) for r in recs] == [
        (jax.devices()[0].id, host)]
    routes = {r.attrs["route"]: r.attrs["n"] for r in spans.records(t0)
              if r.name == "maecho.route"}
    assert routes == {"stacked": 1, "oracle": 1}


_MESH4 = textwrap.dedent("""
    import json
    import re
    import time

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import maecho
    from repro.core.maecho import MAEchoConfig
    from repro.core.plan import compile_plan
    from repro.fl.llm_adapter import (aggregate_llm, build_projections,
                                      stack_levels_fn)
    from repro.models.zoo import get_model
    from repro.utils import spans, trees

    assert len(jax.devices()) == 4, jax.devices()
    # Qwen2-shaped, every tile count even: d_model 256 (2 tiles), 2 KV
    # heads of 128 (256), d_ff 512 (4 tiles); the vocabulary's 3 tiles
    # cannot split over "model", as 151936 (1187 tiles) cannot
    cfg = get_config("qwen2-1.5b").replace(
        n_layers=2, d_model=256, n_heads=2, n_kv_heads=2, head_dim=128,
        d_ff=512, vocab=384)
    model = get_model(cfg)
    base = model.init_params(jax.random.PRNGKey(0))
    clients, projs = [], []
    for i in range(2):
        keys = jax.random.split(jax.random.PRNGKey(1 + i),
                                len(jax.tree.leaves(base)))
        c = jax.tree.unflatten(jax.tree.structure(base), [
            x + 1e-2 * jax.random.normal(k, x.shape, x.dtype)
            for x, k in zip(jax.tree.leaves(base), keys)])
        rng = np.random.default_rng(i)
        probe = [{"tokens": rng.integers(0, cfg.vocab, (2, 64))}]
        projs.append(jax.device_get(build_projections(cfg, c, probe)))
        clients.append(jax.device_get(c))
    macfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40)

    t0 = time.perf_counter_ns()
    got = aggregate_llm(cfg, clients, projs, macfg, backend="sharded2d")
    recs = spans.records(t0)
    want = aggregate_llm(cfg, clients, projs, macfg, backend="oracle")
    rel = {p: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                    / np.max(np.abs(np.asarray(b))))
           for (p, a), (_, b) in zip(trees.tree_paths(got),
                                     trees.tree_paths(want))}

    # the executor as the aggregate compiled it, on the operands as
    # placed: which collectives it holds
    lv = stack_levels_fn(cfg)
    levels = trees.map_with_path(lambda p, _: lv(p), clients[0])
    mesh = maecho._default_mesh(macfg.mesh_axis, macfg.mesh_in_axis)
    P = jax.tree.map(lambda *xs: np.stack(xs), *projs)
    plan = compile_plan(clients[0], P, levels, macfg, "io", "sharded2d",
                        mesh)
    W0, V0, P = maecho.place(clients, projs, plan, mesh)
    text = maecho._maecho_jit.lower(W0, V0, P, macfg, "io", plan,
                                    mesh).compile().as_text()
    colls = [m.group(1) for m in re.finditer(
        r" (all-reduce|all-gather|all-to-all|collective-permute|"
        r"reduce-scatter)(?:-start)?[(]", text)]
    leaves = dict(trees.tree_paths(clients[0]))
    print("RESULT " + json.dumps({
        "mesh": dict(got["layers"]["wq"].sharding.mesh.shape),
        "specs": {p: str(x.sharding.spec) for p, x in
                  trees.tree_paths(got)},
        "routes": {r.attrs["route"]: r.attrs["n"] for r in recs
                   if r.name == "maecho.route"},
        "placed": {str(r.attrs["device"]): r.attrs["n"] for r in recs
                   if r.name == "maecho.place_bytes"},
        "leaves": {p: [list(x.shape), x.nbytes] for p, x in leaves.items()},
        "levels": dict(trees.tree_paths(levels)),
        "proj_bytes": sum(x.nbytes for t in projs
                          for _, x in trees.tree_paths(t)),
        "rel": rel, "collectives": colls}))
""")


@pytest.fixture(scope="module")
def mesh4():
    """One forced-four-device run of the program's own mesh path."""
    r = _run_forced(_MESH4, n_devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    import json

    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_sharded2d_without_a_mesh_builds_two_by_two(mesh4):
    assert mesh4["mesh"] == {"data": 2, "model": 2}
    # the merged weights come back in the layout they were placed in:
    # out-rows ("io": the last axis) over "data", whole along "model"
    assert mesh4["specs"]["layers.wq"] == "PartitionSpec(None, None, 'data')"
    assert mesh4["specs"]["embed"] == "PartitionSpec(None, 'data')"


@pytest.mark.slow
def test_sharded2d_sends_every_matrix_leaf_down_a_mesh_route(mesh4):
    matrices = [p for p, (shape, _) in mesh4["leaves"].items()
                if len(shape) - mesh4["levels"][p] == 2]
    assert len(matrices) == 8
    assert mesh4["routes"] == {"sharded2d": 7, "sharded": 1, "oracle": 6}


@pytest.mark.slow
def test_sharded2d_sends_each_host_byte_once(mesh4):
    """The four devices receive equal parts of the checkpoints and
    projectors from the host, together the host's bytes, and nothing
    twice but the few leaves too small to split (the final norm, the
    scalar projectors: under 1 %).  A device holding a whole stack, or
    the row shards sent to both devices along "model", would break it."""
    n = 2
    host = n * sum(b for _, b in mesh4["leaves"].values()) \
        + mesh4["proj_bytes"]
    placed = mesh4["placed"]
    assert sorted(placed) == ["0", "1", "2", "3"]
    assert len(set(placed.values())) == 1
    assert host <= sum(placed.values()) < 1.01 * host


@pytest.mark.slow
def test_sharded2d_merge_matches_the_oracle(mesh4):
    """Against the jnp oracle on one device.  The sharded Grams are sums
    of four partial contractions, the oracle's one: the fp32 rounding
    differs in the last bits (observed ≤ 5e-7 of the largest weight),
    which the QP and τ = 2 steps carry without amplifying; 1e-5 leaves
    that room twenty times over and still catches a dropped shard or a
    wrong α (errors of order 1e-2)."""
    worst = max(mesh4["rel"], key=mesh4["rel"].get)
    assert mesh4["rel"][worst] < 1e-5, (worst, mesh4["rel"][worst])


@pytest.mark.slow
def test_sharded2d_step_reduces_grams_only(mesh4):
    """The compiled step moves no anchor between devices: its only
    collectives are all-reduces (the per-leaf Gram psums)."""
    assert mesh4["collectives"]
    assert set(mesh4["collectives"]) == {"all-reduce"}, \
        mesh4["collectives"]
