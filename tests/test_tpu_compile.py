"""Mosaic compile rehearsals: the main path's Pallas kernels, lowered at
qwen2-0.5b widths for a described (not attached) TPU v5e chip.

Interpret-mode tests cannot see what the TPU compiler refuses —
unaligned blocks, contractions Mosaic has no lowering for, VMEM
overruns.  Each test here compiles one kernel with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology and asserts that the
program carries the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never while a module
is imported: only one process may hold the TPU library, and pytest-xdist
workers all import every test file.  The persistent compilation cache
is off around the compiles (an entry written for a described chip cannot
be read back without one).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import maecho_gram as mg
from repro.kernels import maecho_update as mu
from repro.kernels import maecho_v_update as mv

# qwen2-0.5b (arXiv:2407.10671): 24 layers, d_model 896, d_ff 4864,
# vocab 151936, 14 query / 2 KV heads of 64; two silos
L, D, F, VOCAB, HQ, HKV, HD, N = 24, 896, 4864, 151936, 14, 2, 64, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32 = jnp.float32
# the dense leaves the kernels take, in their "oi" layout (layers, out,
# in, Gram columns):
# qwen2-0.5b's w_gate, wq and wk on one chip, and one chip's shard of
# qwen2-1.5b's w_gate on the (2, 2) mesh — 4480 of its 8960 rows, whole
# 1536-wide rows, the Gram's in-columns split to 768.  "gate-128" runs
# the kernels' own 128 cube; the others the blocks the plan derives.
DENSE = {"gate-128": (L, F, D, D), "gate": (L, F, D, D),
         "wq": (L, D, D, D), "wk": (L, HKV * HD, D, D),
         "mesh-gate": (12, 4480, 1536, 768)}
# the diagonal leaves: the 0.5b's embedding as the stack L = 1, and one
# chip's shard of the 1.5b's scalar-projected w_down (768 of 1536 rows,
# 8960 columns, the Gram's split to 4480)
DIAG = {"embed-128": (1, D, VOCAB, VOCAB), "embed": (1, D, VOCAB, VOCAB),
        "mesh-w_down": (12, 768, 8960, 4480)}


def _blocks(name, leaves, kind):
    """The kernels' block arguments for a case: none for the 128 cube,
    else the plan's blocks, checked against the VMEM budget."""
    if name.endswith("-128"):
        return {}
    from repro.core.plan import kernel_blocks
    from repro.kernels import env

    layers, out_d, in_d, in_gram = leaves[name]
    kd = in_d if kind == "full" else 0
    got = kernel_blocks(kind, N, layers, out_d, in_d, in_gram, kd, False)
    assert not got.fallback
    op = "diag" if kind == "diag" else "full"
    need = max(mod.vmem(op, N, got.bo, b, got.bk)
               for mod, b in ((mg, min(got.bi, in_gram)), (mu, got.bi),
                              (mv, got.bi)))
    assert need <= env.VMEM_BUDGET
    return dict(bo=got.bo, bi=got.bi, **({"bk": got.bk} if kd else {}))


def _dense(name):
    layers, out_d, in_d, _ = DENSE[name]
    return (((layers, out_d, in_d), F32), ((N, layers, out_d, in_d), F32),
            ((N, layers, in_d, in_d), F32))


@pytest.mark.parametrize("name", ["gate-128", "gate", "wq", "wk"])
def test_gram_stacked_compiles(one_chip, name):
    kw = _blocks(name, DENSE, "full")
    _compile(lambda W, V, P: mg.maecho_gram_stacked(W, V, P, **kw,
                                                    interpret=False),
             one_chip, *_dense(name))


@pytest.mark.parametrize("name", ["mesh-gate"])
def test_gram_left_stacked_compiles(one_chip, name):
    """The 2-D route's Gram on one chip's shard: Δ's whole rows against
    the projector's owned in-columns."""
    layers, out_d, in_d, in_gram = DENSE[name]
    kw = _blocks(name, DENSE, "full")
    _compile(lambda A, UT: mg.maecho_gram_left_stacked(A, UT, **kw,
                                                       interpret=False),
             one_chip, ((N, layers, out_d, in_d), F32),
             ((N, layers, in_d, in_gram), F32))


@pytest.mark.parametrize("name", ["embed-128", "embed", "mesh-w_down"])
def test_gram_diag_compiles(one_chip, name):
    # the embedding in "oi" layout: out = d_model, in = vocab, as the
    # stack L = 1 the executor hands the kernel
    layers, out_d, _, in_gram = DIAG[name]
    kw = _blocks(name, DIAG, "diag")
    _compile(lambda W, V, p: mg.maecho_gram_diag_stacked(
        W, V, p, **kw, interpret=False),
        one_chip, ((layers, out_d, in_gram), F32),
        ((N, layers, out_d, in_gram), F32), ((N, layers, in_gram), F32))


@pytest.mark.parametrize("name", ["embed", "mesh-w_down"])
def test_diag_apply_compiles(one_chip, name):
    layers, out_d, in_d, _ = DIAG[name]
    kw = _blocks(name, DIAG, "diag")
    shapes = (((layers, out_d, in_d), F32),
              ((N, layers, out_d, in_d), F32), ((N, layers, in_d), F32))
    _compile(lambda W, V, p, a: mu.maecho_update_diag_stacked(
        W, V, p, a, eta=0.5, **kw, interpret=False),
        one_chip, *shapes, ((layers, N), F32))
    _compile(lambda W, V, p: mv.maecho_v_update_diag_stacked(
        W, V, p, frac=0.5, **kw, interpret=False), one_chip, *shapes)


@pytest.mark.parametrize("name", sorted(DENSE))
def test_update_stacked_compiles(one_chip, name):
    kw = _blocks(name, DENSE, "full")
    _compile(lambda W, V, P, a: mu.maecho_update_stacked(
        W, V, P, a, eta=0.5, **kw, interpret=False),
        one_chip, *_dense(name), ((DENSE[name][0], N), F32))


@pytest.mark.parametrize("name", sorted(DENSE))
def test_v_update_stacked_compiles(one_chip, name):
    kw = _blocks(name, DENSE, "full")
    _compile(lambda W, V, P: mv.maecho_v_update_stacked(
        W, V, P, frac=0.5, **kw, interpret=False),
        one_chip, *_dense(name))


def test_gram_cross_compiles(one_chip):
    flat = D * D                     # one wq layer's residual, flattened
    _compile(lambda a, b: mg.maecho_gram_cross(a, b, interpret=False),
             one_chip, ((N, flat), F32), ((N, flat), F32))


def test_decode_fine_grid_compiles(one_chip):
    B, W = 4, 256                    # serving window of two blocks
    bf = jnp.bfloat16
    _compile(lambda q, k, v, m: da.decode_attention(
        q, k, v, m, bw=128, interpret=False, fold_batch=False),
        one_chip, ((B, 1, HQ, HD), bf), ((B, W, HKV, HD), bf),
        ((B, W, HKV, HD), bf), ((B, W), jnp.bool_))


# the serving cell's cache: 64 slots, a 1280-slot window
SERVE_B, SERVE_W = 64, 1280


def test_decode_layer_indexed_compiles(one_chip):
    """The stacked cache read at a scalar-prefetched layer index, in
    window blocks of 256, at the serving shapes."""
    bf = jnp.bfloat16
    cache = ((L, SERVE_B, SERVE_W, HKV, HD), bf)
    _compile(lambda q, k, v, m, layer: da.decode_attention(
        q, k, v, m, layer, bw=256, interpret=False, fold_batch=False),
        one_chip, ((SERVE_B, 1, HQ, HD), bf), cache, cache,
        ((SERVE_B, SERVE_W), jnp.bool_), ((), jnp.int32))


def test_cache_write_compiles(one_chip):
    bf = jnp.bfloat16
    cache = ((L, SERVE_B, SERVE_W, HKV, HD), bf)
    new = ((SERVE_B, HKV, HD), bf)
    text = _compile(lambda k, v, kn, vn, layer, slots: da.cache_write(
        k, v, kn, vn, layer, slots, interpret=False),
        one_chip, cache, cache, new, new, ((), jnp.int32),
        ((SERVE_B,), jnp.int32))
    assert "output_to_operand_aliasing" in text


def test_serve_step_writes_the_cache_in_place(one_chip, monkeypatch):
    """The slot loop's serve step at smoke widths and the serving
    cache's shape, with the chip's kernels: the donated cache is aliased
    to the output, and no more than one layer's K+V of temporaries is
    live — no second cache, no whole-cache select or layout copy."""
    from repro.configs import get_smoke_config
    from repro.kernels import env, ops
    from repro.launch.serve import slot_fns
    from repro.models.zoo import get_model

    # the dispatch asks the default backend, which is the CPU here
    monkeypatch.setattr(env, "interpret_default", lambda: False)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    cfg = get_smoke_config("qwen2-0.5b").replace(compute_dtype="bfloat16")
    model = get_model(cfg)
    on_chip = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    cache = on_chip(model.cache_specs(SERVE_B, SERVE_W))
    token, position = on_chip((jax.ShapeDtypeStruct((SERVE_B, 1), jnp.int32),
                               jax.ShapeDtypeStruct((SERVE_B,), jnp.int32)))
    _, _, serve_step = slot_fns(model)
    compiled = serve_step.lower(on_chip(model.param_specs()), cache, token,
                                position, w_live=SERVE_W).compile()
    text = compiled.as_text()
    assert "kv_cache_write" in text and "decode_attention" in text
    mem = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // cfg.n_layers


@pytest.mark.parametrize("program", ["serve_step", "prefill1"])
def test_serving_programs_round_weights_in_layer(one_chip, monkeypatch,
                                                 program):
    """The slot loop's serve step (64 slots, the serving window) and
    batch-1 prefill (a 1024-token prompt) at qwen2-0.5b widths, fp32
    weights and bf16 compute: no convert narrows a whole layer stack or
    the embedding table to bf16 ahead of the layer scan, and the
    temporaries stay below one layer's fp32 weights."""
    import re

    from repro.configs import get_config
    from repro.kernels import env, ops
    from repro.launch.serve import slot_fns
    from repro.models.zoo import get_model

    monkeypatch.setattr(env, "interpret_default", lambda: False)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    cfg = get_config("qwen2-0.5b").replace(param_dtype="float32",
                                           compute_dtype="bfloat16")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (L, D, VOCAB)
    model = get_model(cfg)
    on_chip = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    params = on_chip(model.param_specs())
    prefill1, _, serve_step = slot_fns(model)
    if program == "serve_step":
        token, position = on_chip((
            jax.ShapeDtypeStruct((SERVE_B, 1), jnp.int32),
            jax.ShapeDtypeStruct((SERVE_B,), jnp.int32)))
        lowered = serve_step.lower(
            params, on_chip(model.cache_specs(SERVE_B, SERVE_W)), token,
            position, w_live=SERVE_W)
    else:
        lowered = prefill1.lower(params, on_chip(
            {"tokens": jax.ShapeDtypeStruct((1, 1024), jnp.int32)}))
    compiled = lowered.compile()
    to_bf16 = r"= bf16\[([\d,]+)\]\{[^}]*\} convert\("
    narrowed = [tuple(int(n) for n in dims.split(","))
                for dims in re.findall(to_bf16, compiled.as_text())]
    assert narrowed
    stacks = [s for s in narrowed if len(s) >= 3 and s[0] == L]
    assert not stacks and (VOCAB, D) not in narrowed, stacks
    layer_bytes = sum(w.size * w.dtype.itemsize for w in
                      jax.tree.leaves(params["layers"])) // L
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_flash_attention_compiles(one_chip, grad):
    B, S = 4, 128
    bf = jnp.bfloat16

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, bq=128, bk=128,
                                  interpret=False)

    # value_and_grad, as a train step returns its loss: with grad alone
    # the forward's output is dead and XLA drops the kernel
    fn = fwd if not grad else jax.value_and_grad(
        lambda q, k, v: fwd(q, k, v).astype(F32).sum(), argnums=(0, 1, 2))
    _compile(fn, one_chip, ((B, S, HQ, HD), bf), ((B, S, HKV, HD), bf),
             ((B, S, HKV, HD), bf))


def test_sharded2d_step_compiles_for_the_2x2_mesh(topo, one_chip,
                                                  monkeypatch):
    """The sharded2d executor for four described v5e chips on the mesh
    the program derives from four devices, at Qwen2-shaped smoke widths
    whose tiles split 2 × 2, its operands in the layout ``place`` puts
    them in (``LeafPlan.specs``): it fits a chip, and its collectives
    are the Gram all-reduces alone — no gather or exchange of anchors."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.configs import get_config
    from repro.core import maecho
    from repro.core.plan import compile_plan
    from repro.fl.llm_adapter import stack_levels_fn
    from repro.kernels import env, ops
    from repro.models.zoo import get_model
    from repro.utils import trees

    monkeypatch.setattr(env, "interpret_default", lambda: False)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    cfg = get_config("qwen2-1.5b").replace(
        n_layers=2, d_model=256, n_heads=2, n_kv_heads=2, head_dim=128,
        d_ff=512, vocab=384)
    n = 2
    macfg = maecho.MAEchoConfig(tau=20, eta=0.5, qp_iters=200)
    shapes = jax.eval_shape(get_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    full = ("layers.wq", "layers.wk", "layers.wv", "layers.w_gate",
            "layers.w_up")
    lv = stack_levels_fn(cfg)

    def proj(path, w):
        if path in full:
            return (n,) + w.shape[:-1] + w.shape[-2:-1]
        if path == "embed":
            return (n, w.shape[0])
        return (n,) + w.shape[:lv(path)]

    P = trees.map_with_path(lambda path, w: jax.ShapeDtypeStruct(
        proj(path, w), F32), shapes)
    levels = trees.map_with_path(lambda path, _: lv(path), shapes)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(maecho.mesh_grid(4)),
                ("data", "model"))
    plan = compile_plan(shapes, P, levels, macfg, "io", "sharded2d", mesh)
    assert plan.route_counts() == {"sharded2d": 7, "sharded": 1,
                                   "oracle": 6}
    W0, V0, Ps = [], [], []
    for w, p, lp in zip(jax.tree.leaves(shapes), jax.tree.leaves(P),
                        plan.leaves):
        ws, vs, ps = lp.specs(mesh, "io", w.ndim, p)
        W0.append(jax.ShapeDtypeStruct(w.shape, w.dtype,
                                       sharding=NamedSharding(mesh, ws)))
        V0.append(jax.ShapeDtypeStruct((n,) + w.shape, w.dtype,
                                       sharding=NamedSharding(mesh, vs)))
        Ps.append(jax.ShapeDtypeStruct(p.shape, p.dtype,
                                       sharding=NamedSharding(mesh, ps)))
    tdef = jax.tree.structure(shapes)
    W0, V0, Ps = (jax.tree.unflatten(tdef, x) for x in (W0, V0, Ps))
    compiled = maecho._maecho_jit.lower(W0, V0, Ps, macfg, "io", plan,
                                        mesh, None).compile()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert per_chip < 15.75 * 2 ** 30
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    ops_ = re.findall(r"= (.+?) (all-reduce|all-gather|all-to-all|"
                      r"collective-permute|reduce-scatter)(?:-start)?\(",
                      text)
    assert ops_ and {op for _, op in ops_} == {"all-reduce"}, ops_
    # each reduces Grams, alone or in a tuple: (L, N, N), or (1, N, N)
    # for a 2-D leaf, which the kernels take as the stack L = 1
    grams = {f"f32[{cfg.n_layers},{n},{n}]", f"f32[1,{n},{n}]"}
    for result, _ in ops_:
        shapes_ = re.findall(r"f32\[[\d,]*\]", result)
        assert shapes_ and set(shapes_) <= grams, result
