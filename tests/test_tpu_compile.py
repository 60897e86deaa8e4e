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
# the w_gate leaf in the kernels' "oi" layout: (L, out=d_ff, in=d_model)
W_GATE = ((L, F, D), F32)
V_GATE = ((N, L, F, D), F32)
P_GATE = ((N, L, D, D), F32)


def test_gram_stacked_compiles(one_chip):
    _compile(lambda W, V, P: mg.maecho_gram_stacked(W, V, P,
                                                    interpret=False),
             one_chip, W_GATE, V_GATE, P_GATE)


def test_gram_diag_compiles(one_chip):
    # the embedding in "oi" layout: out = d_model, in = vocab
    _compile(lambda W, V, p: mg.maecho_gram_diag(W, V, p,
                                                 interpret=False),
             one_chip, ((D, VOCAB), F32), ((N, D, VOCAB), F32),
             ((N, VOCAB), F32))


def test_update_stacked_compiles(one_chip):
    _compile(lambda W, V, P, a: mu.maecho_update_stacked(
        W, V, P, a, eta=0.5, interpret=False),
        one_chip, W_GATE, V_GATE, P_GATE, ((L, N), F32))


def test_v_update_stacked_compiles(one_chip):
    _compile(lambda W, V, P: mv.maecho_v_update_stacked(
        W, V, P, frac=0.5, interpret=False),
        one_chip, W_GATE, V_GATE, P_GATE)


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
    # each reduces Grams: (L, N, N) or (N, N), alone or in a tuple
    grams = {f"f32[{cfg.n_layers},{n},{n}]", f"f32[{n},{n}]"}
    for result, _ in ops_:
        shapes_ = re.findall(r"f32\[[\d,]*\]", result)
        assert shapes_ and set(shapes_) <= grams, result
