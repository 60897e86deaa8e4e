"""Serving loop: window helpers, the decode step, the donated cache, and
continuous-batching token parity.

The continuous-batching loop (``launch/serve.py --arrival``) must emit
exactly the tokens the lockstep fixed-batch loop emits per request —
admission order, slot reuse, batch-1 prefill insertion and the
bucketed live-window crop must all be invisible to the outputs.  The
decode step with per-slot positions carries the stacked cache through
its layer scan and writes it in place: it must compute exactly what the
per-layer formulation (each layer's slice scanned in and out, written
by a one-hot select) computes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import (live_bucket, pad_kv_to_window,
                                round_window, run_arrival, run_fixed)


def test_round_window():
    assert round_window(1) == 128
    assert round_window(128) == 128
    assert round_window(129) == 256
    assert round_window(1000) == 1024


def test_live_bucket():
    assert live_bucket(1, 4096) == 256          # floor 2 x block
    assert live_bucket(256, 4096) == 256
    assert live_bucket(257, 4096) == 512
    assert live_bucket(900, 4096) == 1024
    assert live_bucket(5000, 4096) == 4096      # capped at the window


def test_pad_kv_to_window_pads_only_ring_leaves():
    cache = {
        "k": jnp.ones((2, 3, 16, 4, 8)),
        "v": jnp.ones((2, 3, 16, 4, 8)),
        "xk": jnp.ones((2, 3, 50, 4, 8)),       # cross-attn: untouched
        "nested": {"k": jnp.ones((4, 1, 16, 2, 8))},
    }
    out = pad_kv_to_window(cache, 64)
    assert out["k"].shape == (2, 3, 64, 4, 8)
    assert out["v"].shape == (2, 3, 64, 4, 8)
    assert out["xk"].shape == (2, 3, 50, 4, 8)
    assert out["nested"]["k"].shape == (4, 1, 64, 2, 8)
    # padded slots are zeros, original slots preserved
    np.testing.assert_array_equal(np.asarray(out["k"][:, :, :16]), 1.0)
    np.testing.assert_array_equal(np.asarray(out["k"][:, :, 16:]), 0.0)


def test_serve_logits_are_fp32():
    """Greedy serving reads fp32 logits from prefill and decode: in bf16
    a large vocabulary ties at the top, and on a TPU the last bit
    depends on the batch shape (batch-1 admission vs fixed batch)."""
    from repro.configs import get_smoke_config
    from repro.models.zoo import get_model

    cfg = get_smoke_config("qwen2-0.5b").replace(compute_dtype="bfloat16")
    model = get_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    B, P, W = 2, 12, 128
    logits, _ = jax.eval_shape(
        model.prefill, params,
        {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)})
    step, _ = jax.eval_shape(
        model.decode_step, params, model.cache_specs(B, W),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32))
    assert logits.dtype == step.dtype == jnp.float32
    assert logits.shape == step.shape == (B, 1, cfg.vocab)


def test_qkv_bias_rounds_once():
    """q/k/v with bias = one rounding of the fp32 product plus bias.  A
    bf16 product plus a bf16 bias rounds twice, and on a TPU whether the
    inner rounding survives fusion depends on the batch shape."""
    from repro.configs import get_smoke_config
    from repro.models import dense

    cfg = get_smoke_config("qwen2-0.5b").replace(compute_dtype="bfloat16")
    assert cfg.qkv_bias
    d, hd = cfg.d_model, cfg.hd()
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    lp = {n: jax.random.normal(k, (d, h * hd))
          for n, k, h in zip(("wq", "wk", "wv"), ks[:3],
                             (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads))}
    lp.update({"b" + n[1]: jax.random.normal(k, (lp[n].shape[1],))
               for n, k in zip(("wq", "wk", "wv"), ks[3:6])})
    x = jax.random.normal(ks[6], (2, 5, d)).astype(cfg.cdtype)
    for got, w in zip(dense._qkv(lp, x, cfg), ("wq", "wk", "wv")):
        want = (x.astype(jnp.float32)
                @ lp[w].astype(cfg.cdtype).astype(jnp.float32)
                + lp["b" + w[1]]).astype(cfg.cdtype)
        np.testing.assert_array_equal(
            np.asarray(got.reshape(want.shape), np.float32),
            np.asarray(want, np.float32))


def _bits(x):
    return np.asarray(x).view(np.uint16)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_layer_weight_rounds_like_astype(jit):
    """fp32 -> bf16 in the layer: bit for bit ``astype`` on random bit
    patterns over the whole exponent range and on the edge cases; NaN
    stays NaN."""
    from repro.models.dense import layer_weight

    rng = np.random.default_rng(0)
    f32 = np.finfo(np.float32)
    edges = np.array([
        0.0, -0.0, np.inf, -np.inf, f32.max, -f32.max,   # max rounds to inf
        f32.smallest_subnormal, -f32.smallest_subnormal, 1e-40, -3e-39,
        f32.tiny, 1e-38, 1e38, -1e38,
    ], np.float32)
    ties = np.array([0x3F808000, 0x3F818000, 0xBF808000, 0xBF818000,
                     0x00008000, 0x00018000, 0x7F7F8000, 0x7F7E8000],
                    np.uint32).view(np.float32)          # halfway: to even
    w = np.concatenate([
        rng.integers(0, 2**32, 2**20, dtype=np.uint32).view(np.float32),
        edges, ties])
    round_ = jax.jit(layer_weight, static_argnums=1) if jit else layer_weight
    cast = jax.jit(lambda x: x.astype(jnp.bfloat16)) if jit else (
        lambda x: x.astype(jnp.bfloat16))
    got, want = round_(jnp.asarray(w), jnp.bfloat16), cast(jnp.asarray(w))
    assert got.dtype == jnp.bfloat16
    nan = np.isnan(w)
    assert nan.any() and np.isnan(np.asarray(got, np.float32)[nan]).all()
    np.testing.assert_array_equal(_bits(got)[~nan], _bits(want)[~nan])
    top = np.asarray(got, np.float32)[np.abs(w) == f32.max]
    assert np.isinf(top).all() and len(top) == 2
    # any other pair of dtypes is the plain cast
    for x, dtype in ((w[:64], jnp.float32), (want[:64], jnp.float32),
                     (want[:64], jnp.bfloat16)):
        np.testing.assert_array_equal(
            np.asarray(layer_weight(jnp.asarray(x), dtype), np.float32),
            np.asarray(jnp.asarray(x).astype(dtype), np.float32))


def _astype_weight(w, dtype):
    return w.astype(dtype)


def _layer_params(cfg, seed):
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.hd()
    shapes = {"wq": (d, cfg.n_heads * hd), "wk": (d, cfg.n_kv_heads * hd),
              "wv": (d, cfg.n_kv_heads * hd), "w_gate": (d, f),
              "w_up": (d, f), "w_down": (f, d)}
    shapes.update({"b" + n[1]: (shapes[n][1],) for n in ("wq", "wk", "wv")})
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {n: jax.random.normal(k, s) / np.sqrt(s[0])
            for (n, s), k in zip(shapes.items(), keys)}


@pytest.mark.parametrize("block", ["mlp_block", "_qkv"])
def test_layer_weight_grads_match_astype(block, monkeypatch):
    """``jax.grad`` through the dense blocks with fp32 weights and bf16
    compute equals the gradient with a plain ``astype``, bit for bit,
    and is not zero: a rounding written without a derivative (say, on
    the integer bits) would hand the weights zeros silently."""
    from repro.configs import get_smoke_config
    from repro.models import dense

    cfg = get_smoke_config("qwen2-0.5b").replace(compute_dtype="bfloat16")
    lp = _layer_params(cfg, 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg.d_model))
    fn = getattr(dense, block)

    def loss(lp, x):
        out = fn(lp, x.astype(cfg.cdtype), cfg)
        return sum(jnp.sum(jnp.sin(o.astype(jnp.float32)))
                   for o in jax.tree.leaves(out))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    got = grad(lp, x)
    monkeypatch.setattr(dense, "layer_weight", _astype_weight)
    want = jax.jit(jax.grad(loss, argnums=(0, 1)))(lp, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    weights = "wq" if block == "_qkv" else "w_down"
    assert np.abs(np.asarray(got[0][weights])).max() > 0


@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_serving_logits_match_astype(step, monkeypatch):
    """prefill and decode logits with fp32 weights and bf16 compute are
    bit for bit those of the same blocks with a plain ``astype``."""
    from repro.configs import get_smoke_config
    from repro.models import dense
    from repro.models.zoo import get_model

    cfg = get_smoke_config("qwen2-0.5b").replace(compute_dtype="bfloat16")
    assert cfg.pdtype == jnp.float32
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, P, W = 3, 12, 128
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab)
    cache = _random_cache(model, B, W, 2)
    position = jnp.asarray([3, 40, W + 9], jnp.int32)

    def logits():
        if step == "prefill":
            fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[0])
            return fn(params, tokens)
        fn = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos)[0])
        return fn(params, cache, tokens[:, :1], position)

    got = logits()
    monkeypatch.setattr(dense, "layer_weight", _astype_weight)
    want = logits()
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_arrival_matches_fixed_batch_tokens():
    """Per-request tokens from the slot loop == the fixed-batch run,
    with requests trickling in mid-decode and slots being reused."""
    from repro.configs import get_smoke_config
    from repro.models.zoo import get_model

    cfg = get_smoke_config("qwen2-0.5b")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    R, P, gen = 5, 12, 6
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, size=(R, P)),
        jnp.int32)

    fixed, _ = run_fixed(cfg, model, params, prompts, gen)
    outs, stats = run_arrival(cfg, model, params, prompts, gen,
                              slots=2, arrival_every=2)
    assert stats["decode_steps"] >= gen - 1     # ran past one batch
    for r in range(R):
        assert len(outs[r]) == gen
        np.testing.assert_array_equal(
            np.asarray(fixed[r]), np.asarray(outs[r], np.int32))


# --------------------------------------------------------------------------
# the decode step: the carried, in-place cache against the per-layer form
# --------------------------------------------------------------------------
def _per_layer_decode_step(cfg, params, cache, token, position, mlp_fn,
                           w_live=None):
    """Each layer's cache slice scanned as xs/ys and written by the
    one-hot select of ``update_kv_cache``."""
    from repro.models import dense
    from repro.models import layers as L

    x = params["embed"].astype(cfg.cdtype)[token]

    def body(x, scanned):
        lp, layer_cache = scanned
        a, layer_cache = dense.attn_block_decode(
            lp, L.rms_norm(x, lp["ln1"], cfg.norm_eps), layer_cache,
            position, cfg, w_live=w_live)
        h = x + a
        h = h + mlp_fn(lp, L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, layer_cache

    x, cache = jax.lax.scan(body, x, (params["layers"], cache))
    return dense._serve_logits(cfg, params, x), cache


def _random_cache(model, B, W, seed):
    leaves, tree = jax.tree.flatten(model.init_cache(B, W))
    k = jax.random.PRNGKey(seed)
    return tree.unflatten(
        [jax.random.normal(jax.random.fold_in(k, i), c.shape).astype(c.dtype)
         for i, c in enumerate(leaves)])


@pytest.mark.parametrize("backend", ["auto", "kernel"])
@pytest.mark.parametrize("arch", ["qwen2_0_5b", "qwen2_moe_a2_7b",
                                  "phi3_vision_4_2b"])
def test_decode_step_matches_per_layer_formulation(arch, backend):
    """dense, moe and vlm: logits and cache of the carried decode step
    equal the per-layer formulation's exactly, with rows at different
    depths and one past the wraparound point."""
    from repro.configs import get_smoke_config
    from repro.models import dense, moe
    from repro.models.zoo import get_model

    cfg = get_smoke_config(arch).replace(attn_backend=backend)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, W = 3, 256
    cache = _random_cache(model, B, W, 1)
    token = jnp.asarray([[7], [11], [13]], jnp.int32)
    position = jnp.asarray([5, W + 30, 200], jnp.int32)
    mlp_fn = ((lambda lp, y: moe.moe_block(lp, y, cfg)[0])
              if cfg.family == "moe" else
              (lambda lp, y: dense.mlp_block(lp, y, cfg)))
    want = _per_layer_decode_step(cfg, params, cache, token, position,
                                  mlp_fn, w_live=W)
    got = model.decode_step(params, cache, token, position, w_live=W)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("arch", ["zamba2_2_7b", "whisper_tiny"])
def test_hybrid_and_encdec_decode_keep_the_per_layer_cache(arch):
    """hybrid (its own group scan over dense.attn_block_decode) and
    encdec (update_kv_cache at a scalar position) keep one layer's
    4-D cache: the kernel and the oracle read it alike, and the cache
    they write is the same."""
    from repro.configs import get_smoke_config
    from repro.models.zoo import get_model

    outs = {}
    for backend in ("oracle", "kernel"):
        cfg = get_smoke_config(arch).replace(attn_backend=backend)
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        cache = _random_cache(model, 2, 256, 2)
        outs[backend] = model.decode_step(
            params, cache, jnp.ones((2, 1), jnp.int32), jnp.int32(9))
    (lo, co), (lk, ck) = outs["oracle"], outs["kernel"]
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lo),
                               atol=1e-4, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(ck), jax.tree.leaves(co)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_slot_fns_donate_the_cache():
    """The slot loop's serve step and insert alias the cache to their
    output, and the serve step holds no second cache: compiled at smoke
    widths, alias bytes cover the cache and temp bytes stay below it.
    (On XLA:CPU the oracle's layer read materialises that layer's
    slice; the v5e compile in test_tpu_compile.py, with the Pallas read
    and write, pins the serve step's temp bytes below one layer's
    K+V.)"""
    from repro.configs import get_smoke_config
    from repro.launch.serve import slot_fns
    from repro.models.zoo import get_model

    # the oracle backend, XLA's scatter and einsum: in the Pallas
    # interpreter every kernel operand is a copy
    cfg = get_smoke_config("qwen2-0.5b").replace(attn_backend="oracle")
    model = get_model(cfg)
    B, W = 32, 1024
    params = model.param_specs()
    cache = model.cache_specs(B, W)
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(cache))
    _, insert, serve_step = slot_fns(model)
    i32 = jnp.int32
    step = serve_step.lower(params, cache, jax.ShapeDtypeStruct((B, 1), i32),
                            jax.ShapeDtypeStruct((B,), i32), w_live=W
                            ).compile().memory_analysis()
    small = jax.tree.map(
        lambda c: jax.ShapeDtypeStruct(c.shape[:1] + (1,) + c.shape[2:],
                                       c.dtype), cache)
    ins = insert.lower(cache, small, jax.ShapeDtypeStruct((), i32)
                       ).compile().memory_analysis()
    assert step.alias_size_in_bytes >= cache_bytes
    assert ins.alias_size_in_bytes >= cache_bytes
    assert step.temp_size_in_bytes < cache_bytes
