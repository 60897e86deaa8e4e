"""Serving loop: window helpers + continuous-batching token parity.

The continuous-batching loop (``launch/serve.py --arrival``) must emit
exactly the tokens the lockstep fixed-batch loop emits per request —
admission order, slot reuse, batch-1 prefill insertion and the
bucketed live-window crop must all be invisible to the outputs.
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
