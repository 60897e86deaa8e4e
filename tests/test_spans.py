"""Program spans and counters (``repro.utils.spans``): the ring, and the
spans, counters and named scopes on the aggregate and serve paths."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import maecho
from repro.core.maecho import MAEchoConfig, maecho_aggregate
from repro.core.plan import compile_plan
from repro.launch.serve import latencies, run_arrival
from repro.utils import spans


def _since(t0, *names):
    return [r for r in spans.records(t0) if r.name in names]


def test_spans_nest_with_parent_ids():
    t0 = time.perf_counter_ns()
    with spans.span("t.outer", rid=3) as outer:
        with spans.span("t.inner") as inner:
            pass
        spans.count("t.count", 2, fn="f")
    with pytest.raises(ValueError):
        with spans.span("t.raised"):
            raise ValueError
    got = {r.name: r for r in _since(t0, "t.outer", "t.inner", "t.count",
                                     "t.raised")}
    assert got["t.outer"] is outer and got["t.inner"] is inner
    assert outer.parent_id is None and got["t.raised"].parent_id is None
    assert inner.parent_id == outer.id
    assert got["t.count"].parent_id == outer.id
    assert got["t.count"].attrs == {"fn": "f", "n": 2}
    assert outer.attrs == {"rid": 3}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert got["t.raised"].end_ns >= got["t.raised"].start_ns
    # records are appended as spans close: the inner one first
    ids = [r.id for r in _since(t0, "t.outer", "t.inner")]
    assert ids == [inner.id, outer.id]


def test_ring_keeps_the_newest_records():
    for i in range(spans.RING + 10):
        spans.count("t.ring", i=i)
    recs = spans.records()
    assert len(recs) == spans.RING
    assert recs[-1].attrs["i"] == spans.RING + 9
    assert [r.id for r in recs] == sorted(r.id for r in recs)


def test_counter_increments_cut_to_a_window():
    spans.count("t.window", 5)
    lo = time.perf_counter_ns()
    spans.count("t.window", 2)
    spans.count("t.window")
    hi = time.perf_counter_ns()
    spans.count("t.window", 7)
    inside = [r for r in spans.records(lo, hi) if r.name == "t.window"]
    assert sum(r.attrs["n"] for r in inside) == 3
    assert all(r.start_ns == r.end_ns for r in inside)


def _clients(n=2):
    out = []
    for i in range(n):
        k = jax.random.PRNGKey(i)
        out.append({"W": jax.random.normal(k, (6, 4)),
                    "b": jax.random.normal(jax.random.fold_in(k, 1), (6,))})
    return out


def test_aggregate_records_place_and_execute_per_call():
    clients = _clients()
    t0 = time.perf_counter_ns()
    for _ in range(2):
        maecho_aggregate(clients, None, MAEchoConfig(tau=2, eta=0.5))
    calls = _since(t0, "maecho.aggregate")
    assert len(calls) == 2
    for call in calls:
        kids = [r for r in spans.records(call.start_ns, call.end_ns)
                if r.parent_id == call.id]
        assert [r.name for r in kids] == ["maecho.place", "maecho.execute"]


@pytest.mark.parametrize("qp_batched", [True, False])
def test_executor_phases_carry_named_scopes(qp_batched):
    clients = _clients()
    cfg = MAEchoConfig(tau=2, eta=0.5, qp_batched=qp_batched)
    W0 = maecho.init_global(clients, "average")
    V0 = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    P = jax.tree.map(lambda *xs: jnp.stack(xs),
                     *maecho.default_projections(clients))
    plan = compile_plan(W0, P, jax.tree.map(lambda _: 0, W0), cfg, "oi",
                        "oracle", None)
    text = maecho._maecho_jit.lower(W0, V0, P, cfg, "oi", plan).as_text(
        debug_info=True)
    for phase in ("gram", "qp", "apply"):
        assert f"maecho.{phase}" in text
    # each leaf's gram and apply, and on the sequential path its QP
    for lp in plan.leaves:
        assert f"maecho.gram/{lp.path}/" in text
        assert f"maecho.apply/{lp.path}/" in text
        assert (f"maecho.qp/{lp.path}/" in text) == (not qp_batched)


def test_run_arrival_records_admissions_steps_and_traces():
    from repro.configs import get_smoke_config
    from repro.models.zoo import get_model

    cfg = get_smoke_config("qwen2-0.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    R, P, gen = 3, 8, 4
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, size=(R, P)),
        jnp.int32)
    t0 = time.perf_counter_ns()
    outs, stats = run_arrival(cfg, model, params, prompts, gen, slots=2,
                              arrival_every=1)
    (call,) = _since(t0, "serve.run_arrival")
    inside = spans.records(call.start_ns, call.end_ns)
    admits = [r for r in inside if r.name == "serve.admit"]
    steps = [r for r in inside if r.name == "serve.step"]
    syncs = [r for r in inside if r.name == "serve.sync"]
    assert sorted(r.attrs["rid"] for r in admits) == list(range(R))
    assert all(r.parent_id == call.id for r in admits + steps)
    assert [r.attrs["step"] for r in steps] == list(range(len(steps)))
    assert len(steps) == stats["decode_steps"] > 0
    assert sorted(r.parent_id for r in syncs) == sorted(r.id for r in steps)
    traced = {r.attrs["fn"] for r in inside if r.name == "serve.trace"}
    assert traced == {"prefill1", "insert", "serve_step"}
    writes = {r.attrs["path"] for r in inside if r.name == "serve.kv_write"}
    assert writes == {"in_place"}
    assert stats["t_total"] == (call.end_ns - call.start_ns) / 1e9

    # per-request times, from the same records
    assert stats["step_end_ns"] == [r.end_ns for r in steps]
    for a in admits:
        rid = a.attrs["rid"]
        assert stats["admit_ns"][rid] == a.start_ns
        assert stats["first_token_ns"][rid] == a.end_ns
        assert call.start_ns <= stats["arrive_ns"][rid] <= a.start_ns
        i, j = stats["first_step"][rid], stats["last_step"][rid]
        assert j - i + 1 == len(outs[rid]) - 1 == gen - 1
        assert stats["step_end_ns"][i] > a.end_ns
    lat = latencies(stats)
    assert len(lat["ttft_ms"]) == R and len(lat["itl_ms"]) == R * (gen - 1)
    assert min(lat["ttft_ms"]) > 0 and min(lat["itl_ms"]) > 0


def test_kv_write_counter_names_the_write_path():
    """``serve.kv_write`` counts, per trace, which cache write the step
    took: in place for a dense step at per-row positions, the per-layer
    select at a scalar position or under a sharding context."""
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models.zoo import get_model
    from repro.sharding import ctx as shard_ctx
    from repro.sharding.rules import make_rules

    cfg = get_smoke_config("qwen2-0.5b")
    model = get_model(cfg)
    params = model.param_specs()
    B, W = 2, 128
    cache = model.cache_specs(B, W)
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    per_row = jax.ShapeDtypeStruct((B,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)

    def paths(position):
        t0 = time.perf_counter_ns()
        jax.eval_shape(model.decode_step, params, cache, token, position)
        return [r.attrs["path"] for r in _since(t0, "serve.kv_write")]

    assert paths(per_row) == ["in_place"]
    assert paths(scalar) == ["select"]
    mesh = make_debug_mesh(1, 1)
    with mesh, shard_ctx.use_rules(make_rules(mesh, cfg)):
        assert paths(per_row) == ["select"]


def test_weight_round_counter_names_the_rounding_path():
    """``model.weight_round`` counts, per trace, each layer weight the
    dense blocks take into the compute dtype: rounded in the layer for
    fp32 weights and bf16 compute, the plain cast otherwise."""
    from repro.configs import get_smoke_config
    from repro.models.zoo import get_model

    def paths(cfg):
        model = get_model(cfg)
        B, W = 2, 128
        t0 = time.perf_counter_ns()
        jax.eval_shape(model.decode_step, model.param_specs(),
                       model.cache_specs(B, W),
                       jax.ShapeDtypeStruct((B, 1), jnp.int32),
                       jax.ShapeDtypeStruct((B,), jnp.int32))
        return [r.attrs["path"] for r in _since(t0, "model.weight_round")]

    cfg = get_smoke_config("qwen2-0.5b")
    # wq, wk, wv, wo and the three MLP weights, once in the layer scan
    assert paths(cfg.replace(compute_dtype="bfloat16")) == ["in_layer"] * 7
    assert paths(cfg) == ["astype"] * 7


def test_kernel_blocks_counter_reports_the_plan():
    """``maecho.kernel_blocks`` counts each trace of a leaf's kernels
    with the blocks the plan chose for it: route, kind, (bo, bi, bk),
    the grid steps of its largest launch and whether the rule fell
    back to the 128 cube.  Oracle leaves run no kernel and count
    nothing."""
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    n = 2
    W0 = {"w": sds((3, 600, 300), f32), "e": sds((256, 1024), f32),
          "b": sds((600,), f32)}
    P = {"w": sds((n, 3, 300, 300), f32), "e": sds((n, 1024), f32),
         "b": sds((n,), f32)}
    V0 = jax.tree.map(lambda w: sds((n,) + w.shape, w.dtype), W0)
    cfg = MAEchoConfig(tau=5, qp_iters=13)
    plan = compile_plan(W0, P, {"w": 1, "e": 0, "b": 0}, cfg, "oi",
                        "kernel")
    t0 = time.perf_counter_ns()
    jax.eval_shape(lambda W, V, P: maecho._maecho_jit(W, V, P, cfg, "oi",
                                                      plan), W0, V0, P)
    got = [r.attrs for r in _since(t0, "maecho.kernel_blocks")]
    want = [dict(lp.blocks._asdict(), route=lp.route, kind=lp.kind, n=1)
            for lp in plan.leaves if lp.route != "oracle"]
    assert got == want
    assert [(g["kind"], g["bo"], g["bi"], g["bk"]) for g in got] == [
        ("diag", 256, 1024, 0), ("full", 640, 384, 384)]


def test_spans_are_host_events_of_a_profile():
    from jax._src.lib import _profiler
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    session = _profiler.ProfilerSession(opts)
    with spans.span("t.profiled", rid=7) as rec:
        with spans.span("t.child") as child:
            jnp.ones(3).block_until_ready()
    pd = ProfileData.from_serialized_xspace(session.stop())
    events = {ev.name: ev for plane in pd.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert {"t.profiled", "t.child"} <= set(events)
    assert dict(events["t.profiled"].stats)["rid"] == 7
    for r in (rec, child):
        ev = events[r.name]
        # the record lies inside its annotation, within a millisecond
        assert 0 <= ev.duration_ns - (r.end_ns - r.start_ns) < 1e6
    gap_ev = events["t.child"].start_ns - events["t.profiled"].start_ns
    assert abs(gap_ev - (child.start_ns - rec.start_ns)) < 1e6
