"""Decode-attention serving fast path vs the dense full-window oracle.

Property parity over the shared case space in ``tests/strategies.py``
(MHA/GQA/MQA shapes, fills past the ring-buffer wraparound point) plus
hand-picked regressions: the two Pallas grid layouts, the static
live-window crop, all-invalid masks, real ``update_kv_cache``-driven
wraparound, vector-vs-scalar cache updates, the layer-indexed read and
in-place write of the stacked cache the decode scan carries, and the
prefill backend dispatch (kernel parity + forced-kernel warn-once
fallback).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

import strategies as strat
from repro.kernels import ops
from repro.models import layers as L


def _oracle(q, kc, vc, valid):
    return L.decode_attention_oracle(q, kc, vc, valid)


# --------------------------------------------------------------------------
# property parity: kernel auto path vs oracle over the case space
# --------------------------------------------------------------------------
@given(strat.seeds(), strat.decode_shapes(), strat.fills())
@settings(max_examples=8, deadline=None)
def test_decode_attention_property_parity(seed, shape, fill):
    q, kc, vc, valid, _ = strat.build_decode_case(seed, shape, fill)
    got = ops.decode_attention_auto(q, kc, vc, valid)
    want = _oracle(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@given(strat.seeds(), strat.decode_shapes(), strat.fills())
@settings(max_examples=8, deadline=None)
def test_decode_attention_model_dispatcher_parity(seed, shape, fill):
    """The layers.decode_attention backend dispatcher ("kernel") agrees
    with its own oracle, including the w_live cropped variant."""
    q, kc, vc, valid, pos = strat.build_decode_case(seed, shape, fill)
    W = shape[1]
    want = _oracle(q, kc, vc, valid)
    got = L.decode_attention(q, kc, vc, valid, backend="kernel",
                             w_live=min(pos + 1, W))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# hand-picked regressions
# --------------------------------------------------------------------------
def test_wraparound_via_real_cache_updates():
    """Drive a (W=128)-slot ring past wraparound with the real
    update_kv_cache, checking kernel/oracle parity at each probe."""
    B, W, Hkv, Hq, D = 2, 128, 2, 4, 16
    k = jax.random.PRNGKey(0)
    cache = {"k": jnp.zeros((B, W, Hkv, D)),
             "v": jnp.zeros((B, W, Hkv, D))}
    valid = None
    for pos in range(W + 40):                 # wraps at pos >= W
        kk = jax.random.fold_in(k, pos)
        k_new = jax.random.normal(kk, (B, 1, Hkv, D))
        v_new = jax.random.normal(jax.random.fold_in(kk, 1),
                                  (B, 1, Hkv, D))
        cache, valid = L.update_kv_cache(cache, k_new, v_new,
                                         jnp.int32(pos))
    assert bool(jnp.all(valid))               # fully wrapped: all valid
    q = jax.random.normal(jax.random.fold_in(k, 999), (B, 1, Hq, D))
    got = ops.decode_attention_auto(q, cache["k"], cache["v"], valid)
    want = _oracle(q, cache["k"], cache["v"], valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_all_invalid_rows_are_zero_and_finite():
    """A row with no valid slot returns exact zeros from the kernel
    (documented divergence: the oracle averages v).  No NaNs either
    way — the contract the serve loop relies on for idle slots."""
    B, W, Hq, Hkv, D = 2, 256, 8, 2, 64
    k = jax.random.PRNGKey(1)
    q = jax.random.normal(k, (B, 1, Hq, D))
    kc = jax.random.normal(jax.random.fold_in(k, 1), (B, W, Hkv, D))
    vc = jax.random.normal(jax.random.fold_in(k, 2), (B, W, Hkv, D))
    valid = jnp.zeros((B, W), bool).at[1, :5].set(True)  # row 0 empty
    got = np.asarray(ops.decode_attention_auto(q, kc, vc, valid))
    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
    want = np.asarray(_oracle(q, kc, vc, valid))
    np.testing.assert_allclose(got[1], want[1], atol=1e-4, rtol=1e-4)


def test_gqa_grouping_matches_oracle_per_head():
    """GQA group of 4: each q head must attend through ITS kv head —
    a transposed grouping would still have matching shapes."""
    B, W, Hq, Hkv, D = 1, 128, 8, 2, 32
    k = jax.random.PRNGKey(2)
    q = jax.random.normal(k, (B, 1, Hq, D))
    kc = jax.random.normal(jax.random.fold_in(k, 1), (B, W, Hkv, D))
    vc = jax.random.normal(jax.random.fold_in(k, 2), (B, W, Hkv, D))
    valid = jnp.ones((B, W), bool)
    got = np.asarray(ops.decode_attention_auto(q, kc, vc, valid))
    # per-head dense reference: head h uses kv head h // (Hq // Hkv)
    g = Hq // Hkv
    for h in range(Hq):
        s = np.einsum("d,wd->w", np.asarray(q)[0, 0, h],
                      np.asarray(kc)[0, :, h // g]) / np.sqrt(D)
        p = np.exp(s - s.max())
        p /= p.sum()
        want_h = np.einsum("w,wd->d", p, np.asarray(vc)[0, :, h // g])
        np.testing.assert_allclose(got[0, 0, h], want_h, atol=1e-4,
                                   rtol=1e-4)


def test_fold_batch_layouts_agree():
    """The interpret-oriented whole-batch grid and the fine
    (TPU-shaped) per-(b,h) grid compute the same thing."""
    B, W, Hq, Hkv, D = 2, 256, 8, 2, 64
    q, kc, vc, valid, _ = strat.build_decode_case(7, (B, W, Hq, Hkv, D),
                                                  200)
    batched = ops.decode_attention(q, kc, vc, valid, bw=128,
                                   fold_batch=True)
    fine = ops.decode_attention(q, kc, vc, valid, bw=128,
                                fold_batch=False)
    np.testing.assert_allclose(np.asarray(batched), np.asarray(fine),
                               atol=1e-5, rtol=1e-5)


def test_w_live_crop_parity():
    """Static live-window crop (the serving fast path) is exact when
    every valid slot lies below the crop."""
    B, W, Hq, Hkv, D = 2, 512, 8, 2, 64
    fill = 130                                 # bucket -> 256 < W
    q, kc, vc, valid, pos = strat.build_decode_case(11,
                                                    (B, W, Hq, Hkv, D),
                                                    fill)
    assert ops.live_window(fill, W) == 256
    got = ops.decode_attention_auto(q, kc, vc, valid, w_live=pos + 1)
    want = _oracle(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_vector_and_scalar_cache_updates_agree():
    """Per-row (B,) positions (slot loop) write the same cache and
    mask as the scalar lockstep path when all rows share a position."""
    B, W, Hkv, D = 3, 64, 2, 16
    k = jax.random.PRNGKey(4)
    cache = {"k": jax.random.normal(k, (B, W, Hkv, D)),
             "v": jax.random.normal(jax.random.fold_in(k, 1),
                                    (B, W, Hkv, D))}
    k_new = jax.random.normal(jax.random.fold_in(k, 2), (B, 1, Hkv, D))
    v_new = jax.random.normal(jax.random.fold_in(k, 3), (B, 1, Hkv, D))
    for pos in (5, W + 7):                     # pre- and post-wrap
        c_s, m_s = L.update_kv_cache(cache, k_new, v_new,
                                     jnp.int32(pos))
        c_v, m_v = L.update_kv_cache(cache, k_new, v_new,
                                     jnp.full((B,), pos, jnp.int32))
        np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_v))
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(c_s[leaf]),
                                          np.asarray(c_v[leaf]))


# --------------------------------------------------------------------------
# the stacked (L, B, W, Hkv, D) cache: layer-indexed read, in-place write
# --------------------------------------------------------------------------
# D = 64 is read through the window-minor view, D = 128 directly
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("fold_batch", [True, False])
@pytest.mark.parametrize("w_live", [None, 200])
def test_layer_indexed_kernel_matches_layer_slice(D, fold_batch, w_live):
    """The kernel on the stacked cache at layer l is the kernel on that
    layer's slice, bit for bit; with a live-window crop, the shorter
    grid over the whole cache is the kernel on the cropped slice."""
    n_l, B, W, Hq, Hkv = 3, 2, 512, 8, 2
    k = jax.random.PRNGKey(D)
    q = jax.random.normal(k, (B, 1, Hq, D)).astype(jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(k, 1),
                           (n_l, B, W, Hkv, D)).astype(jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(k, 2),
                           (n_l, B, W, Hkv, D)).astype(jnp.bfloat16)
    fill = jnp.array([[150], [190]])                 # rows at their depths
    valid = jnp.arange(W)[None, :] < fill
    wl = W if w_live is None else ops.live_window(w_live, W)
    for layer in range(n_l):
        got = ops.decode_attention(q, kc, vc, valid[:, :wl],
                                   jnp.int32(layer), bw=128,
                                   fold_batch=fold_batch)
        want = ops.decode_attention(q, kc[layer, :, :wl],
                                    vc[layer, :, :wl], valid[:, :wl],
                                    bw=128, fold_batch=fold_batch)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        auto = ops.decode_attention_auto(q, kc, vc, valid, w_live=w_live,
                                         layer=jnp.int32(layer))
        sliced = ops.decode_attention_auto(q, kc[layer], vc[layer], valid,
                                           w_live=w_live)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(sliced))


def _slot_positions():
    """Per-row positions over a few steps of a W = 128 ring: rows at
    different depths, rows past the wraparound point, and row 2
    re-admitted (its position back to the prompt's end) after it
    finished."""
    W = 128
    start = np.array([3, 40, W + 90, 2 * W - 2])
    steps = [start + t for t in range(4)]
    readmit = steps[-1].copy()
    readmit[2] = 5
    return W, steps + [readmit, readmit + 1]


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("backend", ["kernel", "oracle"])
def test_in_place_write_matches_select(D, backend):
    """The layer-indexed write of the stacked cache (the Pallas write
    under "kernel", XLA's scatter under "oracle") leaves exactly the
    cache and mask that the one-hot select leaves in that layer's
    slice, and every other layer untouched."""
    W, steps = _slot_positions()
    n_l, B, Hkv = 3, len(steps[0]), 2
    k = jax.random.PRNGKey(D)
    stacked = {n: jax.random.normal(jax.random.fold_in(k, i),
                                    (n_l, B, W, Hkv, D)).astype(jnp.bfloat16)
               for i, n in enumerate("kv")}
    for t, pos in enumerate(steps):
        pos = jnp.asarray(pos, jnp.int32)
        for layer in range(n_l):
            kk = jax.random.fold_in(k, 100 * t + layer)
            k_new, v_new = (jax.random.normal(jax.random.fold_in(kk, i),
                                              (B, 1, Hkv, D))
                            .astype(jnp.bfloat16) for i in range(2))
            want, m_want = L.update_kv_cache(
                {n: stacked[n][layer] for n in "kv"}, k_new, v_new, pos)
            got, m_got = L.update_kv_cache(stacked, k_new, v_new, pos,
                                           layer=jnp.int32(layer),
                                           backend=backend)
            np.testing.assert_array_equal(np.asarray(m_got),
                                          np.asarray(m_want))
            for n in "kv":
                np.testing.assert_array_equal(np.asarray(got[n][layer]),
                                              np.asarray(want[n]))
                others = np.array([i for i in range(n_l) if i != layer])
                np.testing.assert_array_equal(
                    np.asarray(got[n][others]),
                    np.asarray(stacked[n][others]))
            stacked = got


def test_in_place_write_takes_per_row_positions():
    cache = {n: jnp.zeros((2, 3, 128, 2, 64)) for n in "kv"}
    new = jnp.ones((3, 1, 2, 64))
    with pytest.raises(ValueError, match="per-row"):
        L.update_kv_cache(cache, new, new, jnp.int32(4), layer=jnp.int32(0))


# --------------------------------------------------------------------------
# prefill backend dispatch
# --------------------------------------------------------------------------
def test_prefill_backend_kernel_matches_oracle():
    B, S, Hq, Hkv, D = 2, 256, 8, 2, 64
    k = jax.random.PRNGKey(5)
    q = jax.random.normal(k, (B, S, Hq, D))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, Hkv, D))
    want = L.prefill_attention(q, kk, v, causal=True, backend="oracle")
    got = L.prefill_attention(q, kk, v, causal=True, backend="kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_prefill_forced_kernel_warns_on_ineligible_shape():
    """backend="kernel" on a shape the flash kernel cannot express
    (non-causal, Sk not a block multiple) falls back with a warning —
    never silently."""
    import repro.kernels.ops as ops_mod
    B, Sq, Sk, H, D = 1, 64, 100, 2, 32
    k = jax.random.PRNGKey(6)
    q = jax.random.normal(k, (B, Sq, H, D))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, Sk, H, D))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, Sk, H, D))
    ops_mod._warned_fallbacks.clear()
    with pytest.warns(RuntimeWarning):
        got = L.prefill_attention(q, kk, v, causal=False,
                                  backend="kernel")
    want = L.prefill_attention(q, kk, v, causal=False, backend="oracle")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
