"""Stacked-leaf kernel dispatch (ISSUE 4 tentpole): launch- and
psum-count contracts for the folded scan-layer grid, dispatch-summary
coverage reporting, and the silent-fallback warnings — mirroring
``tests/test_sharded_agg.py``'s contract style."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.maecho import (MAEchoConfig, dispatch_summary,
                               maecho_aggregate)
from repro.core.plan import kernel_eligible, leaf_route
from repro.kernels import ops


def _one_device_mesh():
    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _stacked_model(L, n=3, out_d=256, in_d=140, kind="full", rank=16):
    clients, projs = [], []
    for i in range(n):
        k = jax.random.PRNGKey(13 * i + 2)
        U = jnp.linalg.qr(jax.random.normal(
            jax.random.fold_in(k, 2), (L, in_d, rank)))[0]
        s = jax.random.uniform(jax.random.fold_in(k, 3), (L, rank))
        clients.append({"W": jax.random.normal(k, (L, out_d, in_d))
                        * 0.3})
        projs.append({"W": ({"U": U, "s": s} if kind == "factored"
                            else jnp.einsum("lik,lk,ljk->lij",
                                            U, s, U))})
    return clients, projs, {"W": 1}


# --------------------------------------------------------------------------
# eligibility: stacked leaves are first-class on every backend
# --------------------------------------------------------------------------
def test_stacked_kernel_eligibility():
    W3 = jnp.zeros((4, 1024, 256))
    Pfull = jnp.zeros((3, 4, 256, 256))
    assert kernel_eligible(W3, Pfull, levels=1)
    assert not kernel_eligible(W3, Pfull)           # ndim mismatch
    assert kernel_eligible(jnp.zeros((2, 4, 64, 32)),
                           jnp.zeros((3, 2, 4)), levels=2)  # scalar
    U = {"U": jnp.zeros((3, 4, 256, 16)), "s": jnp.zeros((3, 4, 16))}
    assert kernel_eligible(W3, U, levels=1)
    assert not kernel_eligible(W3, U, levels=2)


def test_stacked_sharded_eligibility():
    class FakeMesh:
        shape = {"data": 8, "model": 1}

    cfg = MAEchoConfig()
    W = jnp.zeros((4, 1024, 256))
    P = jnp.zeros((3, 4, 256, 256))
    assert leaf_route(W, P, 1, cfg, "oi", "sharded",
                      FakeMesh()) == "sharded"
    # io: kernel-layout out-dim is the trailing axis
    assert leaf_route(jnp.zeros((4, 256, 1024)), P, 1, cfg, "io",
                      "sharded", FakeMesh()) == "sharded"
    # non-divisible out-dim tiles fall back, stacked or not
    assert leaf_route(jnp.zeros((4, 300, 256)), P, 1, cfg, "oi",
                      "sharded", FakeMesh()) == "kernel"


# --------------------------------------------------------------------------
# launch-count contract: ONE stacked launch per pipeline pass per leaf
# per outer iteration, independent of L
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["full", "factored"])
def test_kernel_launches_independent_of_L(kind):
    """The traced program holds exactly 3 Pallas kernels (gram, Eq. 7,
    Eq. 11) for a stacked leaf — the same count at L=2 and L=4, i.e.
    the layer axis rides the grid instead of multiplying launches."""
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_iters=40)
    counts = {}
    for L in (2, 4):
        clients, projs, levels = _stacked_model(L, kind=kind)
        txt = str(jax.make_jaxpr(
            lambda c=clients, p=projs: maecho_aggregate(
                c, p, cfg, stack_levels=levels,
                backend="kernel"))())
        counts[L] = txt.count("pallas_call")
    assert counts[2] == counts[4] == 3, counts


def test_oracle_backend_traces_no_kernels():
    clients, projs, levels = _stacked_model(2)
    cfg = MAEchoConfig(tau=1, eta=0.5, qp_iters=40)
    txt = str(jax.make_jaxpr(
        lambda: maecho_aggregate(clients, projs, cfg,
                                 stack_levels=levels,
                                 backend="oracle"))())
    assert txt.count("pallas_call") == 0


# --------------------------------------------------------------------------
# psum-count contract: ONE (L, N, N) psum per stacked leaf per outer
# iteration on the sharded path — not one per scanned layer
# --------------------------------------------------------------------------
@pytest.mark.parametrize("L", [2, 4])
def test_exactly_one_psum_per_stacked_leaf_per_iteration(L):
    mesh = _one_device_mesh()
    tau = 2
    clients, projs, levels = _stacked_model(L)
    cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=40)
    txt = str(jax.make_jaxpr(
        lambda: maecho_aggregate(clients, projs, cfg,
                                 stack_levels=levels,
                                 backend="sharded", mesh=mesh))())
    assert txt.count("psum") == tau, (
        f"expected {tau} psums (one per outer iteration, carrying the "
        f"whole (L={L}, N, N) Gram stack), found {txt.count('psum')}")


def test_stacked_sharded_parity_one_device():
    """backend="sharded" on a stacked leaf matches the oracle through
    maecho_aggregate (axis size 1; the 8-device run rides the CI smoke
    ``dryrun_agg --sharded-smoke``, which carries a stacked leaf)."""
    clients, projs, levels = _stacked_model(3, kind="factored")
    cfg = MAEchoConfig(tau=3, eta=0.5, qp_iters=60)
    a = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="oracle")
    b = maecho_aggregate(clients, projs, cfg, stack_levels=levels,
                         backend="sharded", mesh=_one_device_mesh())
    np.testing.assert_allclose(np.asarray(a["W"]), np.asarray(b["W"]),
                               atol=1e-3)


def test_stacked_matches_per_layer_leaves_kernel_backend():
    """A stacked (L, out, in) leaf on the kernel backend aggregates
    exactly like L separate leaves (the semantics test_maecho pins for
    the oracle, now on the folded grid)."""
    L, n = 3, 2
    clients, projs, levels = _stacked_model(L, n=n, kind="full")
    cfg = MAEchoConfig(tau=4, eta=0.5, qp_iters=60)
    stacked = maecho_aggregate(clients, projs, cfg,
                               stack_levels=levels, backend="kernel")
    per_layer = []
    for layer in range(L):
        out = maecho_aggregate(
            [{"W": c["W"][layer]} for c in clients],
            [{"W": p["W"][layer]} for p in projs], cfg,
            backend="kernel")
        per_layer.append(out["W"])
    np.testing.assert_allclose(np.asarray(stacked["W"]),
                               np.asarray(jnp.stack(per_layer)),
                               atol=1e-3)


# --------------------------------------------------------------------------
# coverage + fallback visibility
# --------------------------------------------------------------------------
def test_dispatch_summary_routes():
    cfg = MAEchoConfig()
    sds = jax.ShapeDtypeStruct
    W0 = {"stack": sds((4, 256, 256), jnp.float32),
          "small": sds((4, 32, 16), jnp.float32),
          "b": sds((256,), jnp.float32)}
    P = {"stack": sds((3, 4, 256, 256), jnp.float32),
         "small": sds((3, 4, 16), jnp.float32),
         "b": sds((3,), jnp.float32)}
    levels = {"stack": 1, "small": 1, "b": 0}
    per_leaf, counts = dispatch_summary(W0, P, levels, cfg, "oi",
                                        "kernel", None)
    routes = dict((p, r) for p, _, r in per_leaf)
    # "small" is requested onto the kernel route by backend="kernel"
    # but is below one tile — the plan routes (and the summary
    # reports) the jnp oracle that actually executes; the eligible
    # stacked leaf takes the kernel route, its layer axis on the grid
    assert routes == {"stack": "kernel", "small": "oracle",
                      "b": "oracle"}
    assert counts == {"kernel": 1, "oracle": 2}
    # sharded promotes the eligible stacked leaf

    class FakeMesh:
        shape = {"data": 2}

    _, counts = dispatch_summary(W0, P, levels, cfg, "oi", "sharded",
                                 FakeMesh())
    assert counts["sharded"] == 1      # 256 = 2 tiles over 2 devices


def test_stacked_fallback_warns_once():
    """A stacked leaf that cannot take the requested fast path warns
    via ops.fallback_warn — once per distinct message."""
    # unique shape so the process-wide dedup set cannot have seen it
    clients = [{"W": jax.random.normal(jax.random.PRNGKey(i),
                                       (2, 37, 23))} for i in range(2)]
    projs = [{"W": jnp.ones((2,))} for _ in range(2)]
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        maecho_aggregate(clients, projs, MAEchoConfig(tau=1),
                         stack_levels={"W": 1}, backend="kernel")
    msgs = [str(w.message) for w in rec
            if "vmapped jnp oracle" in str(w.message)]
    assert len(msgs) >= 1, [str(w.message) for w in rec]


def test_sharded_ok_warns_on_fallback():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert not ops.sharded_ok(424, 136, 8, warn=True)
    assert any("single-device" in str(w.message) for w in rec)
    # and the dedup keeps a second identical call silent
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert not ops.sharded_ok(424, 136, 8, warn=True)
    assert not rec
