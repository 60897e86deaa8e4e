"""Shared neural-net building blocks (pure functions on param dicts).

Everything here is jit/scan/vmap-friendly and shape-polymorphic over
batch/sequence.  Attention is implemented flash-style (chunked online
softmax) in pure jnp so that 32k-sequence prefill lowers with O(S·chunk)
activation memory; the Pallas kernel in ``repro.kernels.flash_attention``
is the TPU-target version of the same computation and is validated
against :func:`chunked_attention` as its oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.utils import spans

NEG_INF = -1e30


# --------------------------------------------------------------------------
# initialisers
# --------------------------------------------------------------------------
def dense_init(rng, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out)) * scale).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype):
    return (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rms_norm(x, gamma, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * gamma.astype(dt)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * gamma.astype(dt) + beta.astype(dt)


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)                      # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention — chunked online-softmax (training / prefill)
# --------------------------------------------------------------------------
def _repeat_kv(k, n_rep: int):
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D)."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def chunked_attention(
    q, k, v, *, causal: bool = True, q_chunk: int = 512, k_chunk: int = 1024,
    q_offset=0, unroll: bool = False,
):
    """Flash-style attention in pure jnp.

    q: (B, Sq, Hq, D);  k, v: (B, Sk, Hkv, D) with Hq % Hkv == 0.
    ``q_offset`` is the absolute position of q[0] (for prefill-with-cache).
    Returns (B, Sq, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    n_rep = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    if q_chunk >= Sq and k_chunk >= Sk:
        # single-chunk fast path (also used by the roofline probe
        # lowerings, which must avoid while-loops for exact HLO costs)
        k_r = _repeat_kv(k, n_rep)
        v_r = _repeat_kv(v, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_r,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_offset + jnp.arange(Sq)
            mask = q_pos[:, None] >= jnp.arange(Sk)[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_r.dtype), v_r,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)
    # pad to multiples
    pq = (-Sq) % q_chunk
    pk = (-Sk) % k_chunk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_chunk, kp.shape[1] // k_chunk

    qp = qp.reshape(B, nq, q_chunk, Hq, D)
    kp = kp.reshape(B, nk, k_chunk, Hkv, D)
    vp = vp.reshape(B, nk, k_chunk, Hkv, D)

    q_pos = (q_offset + jnp.arange(nq * q_chunk)).reshape(nq, q_chunk)
    k_pos = jnp.arange(nk * k_chunk).reshape(nk, k_chunk)
    k_valid = (jnp.arange(nk * k_chunk) < Sk).reshape(nk, k_chunk)

    def q_block(qi, q_c):
        # q_c: (B, q_chunk, Hq, D)
        qpos = q_pos[qi]                                     # (q_chunk,)

        def kv_step(carry, inputs):
            acc, m, l = carry
            k_c, v_c, kpos, kval = inputs
            k_r = _repeat_kv(k_c, n_rep)                     # (B, kc, Hq, D)
            v_r = _repeat_kv(v_c, n_rep)
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q_c, k_r,
                preferred_element_type=jnp.float32) * scale  # (B,Hq,qc,kc)
            mask = kval[None, :]
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))      # (B,Hq,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_r.dtype), v_r,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, Hq, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, Hq, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hq, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kp.transpose(1, 0, 2, 3, 4), vp.transpose(1, 0, 2, 3, 4),
             k_pos, k_valid), unroll=nk if unroll else 1)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 2, 1, 3)                     # (B, qc, Hq, D)

    if unroll:
        outs = jnp.stack([q_block(i, qp[:, i]) for i in range(nq)])
    else:
        outs = jax.lax.map(lambda args: q_block(*args),
                           (jnp.arange(nq), qp.transpose(1, 0, 2, 3, 4)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, Hq, D)
    return out[:, :Sq].astype(q.dtype)


def prefill_attention(q, k, v, *, causal: bool = True, q_chunk: int = 512,
                      k_chunk: int = 1024, q_offset=0, unroll: bool = False,
                      backend: str = "auto"):
    """Prefill/train attention with backend dispatch (same contract as
    :func:`chunked_attention`).

    ``backend`` (``ModelConfig.attn_backend``): "oracle" always runs the
    jnp chunked path; "kernel" forces the Pallas flash kernel whenever
    the shape is expressible (warn-once fallback otherwise); "auto"
    takes the kernel on compiled (non-interpret) runs — under the CPU
    interpreter the scanned kernel body always loses to fused jnp, so
    auto stays on the oracle there.  Eligible shapes: causal
    self-attention with Sq == Sk and no query offset (both sequences
    zero-pad to a block multiple exactly — padded keys are causally
    masked for every real query), or non-causal with Sk already a block
    multiple (zero-padded keys would enter the softmax; query rows
    pad/crop freely).  Sharded tracing (shard_ctx active) stays on the
    oracle, whose GSPMD layout is tuned (§Perf H4).
    """
    from repro.kernels import ops
    from repro.sharding import ctx as shard_ctx

    want_kernel = backend == "kernel" or (
        backend == "auto" and not ops.interpret_default())
    if want_kernel and not shard_ctx.active():
        Sq, Sk = q.shape[1], k.shape[1]
        offset_free = isinstance(q_offset, int) and q_offset == 0
        eligible = ((causal and Sq == Sk and offset_free)
                    or (not causal and Sk % ops.DEFAULT_BLOCK == 0))
        if eligible:
            return ops.flash_attention_auto(q, k, v, causal=causal)
        if backend == "kernel":
            ops.fallback_warn(
                f"prefill attention (Sq={Sq}, Sk={Sk}, causal={causal}, "
                f"q_offset={q_offset}) not expressible by the flash "
                f"kernel: running the jnp chunked oracle")
    return chunked_attention(q, k, v, causal=causal, q_chunk=q_chunk,
                             k_chunk=k_chunk, q_offset=q_offset,
                             unroll=unroll)


def decode_attention(q, k_cache, v_cache, valid_mask, *,
                     backend: str = "auto", w_live: int | None = None,
                     layer=None):
    """Single-token attention against a (possibly ring-buffer) KV cache,
    with backend dispatch.

    q: (B, 1, Hq, D); caches: (B, W, Hkv, D), or the stacked
    (L, B, W, Hkv, D) cache read at ``layer`` (an int32 scalar);
    valid_mask: (B, W) bool.
    ``backend`` (``ModelConfig.attn_backend``): "oracle" forces the
    dense full-window einsum; "kernel" forces the Pallas window kernel
    whenever W divides a block (warn-once fallback otherwise); "auto"
    takes the kernel when the window is blocked AND spans at least two
    blocks, where skipping invalid window blocks pays for the launch.
    Sharded decode (shard_ctx active) always runs the oracle — its
    GSPMD cache pinning is tuned there (§Perf H2).  The kernel reads
    the stacked cache at its layer in place; the oracle reads the
    layer's slice.

    ``w_live`` is the serving loop's static upper bound on written
    ring-buffer slots (see ``ops.decode_attention_auto``): the kernel
    path crops the cache read to the live bucket.  The oracle path
    ignores it — backend="oracle" is the pristine pre-kernel full-window
    einsum, which is what the serve benchmark baselines.
    """
    from repro.sharding import ctx as shard_ctx

    if backend != "oracle" and not shard_ctx.active():
        from repro.kernels import ops

        W = k_cache.shape[-3]
        blocked = W % ops.DEFAULT_BLOCK == 0
        # "auto" under the CPU interpreter needs the crop to win (the
        # grid scan re-copies the carried cache every step); compiled
        # runs take the kernel whenever the window spans ≥ 2 blocks
        wins = W >= 2 * ops.DEFAULT_BLOCK and (
            not ops.interpret_default() or w_live is not None)
        if blocked and (backend == "kernel" or wins):
            return ops.decode_attention_auto(q, k_cache, v_cache,
                                             valid_mask, w_live=w_live,
                                             layer=layer)
        if backend == "kernel":
            ops.fallback_warn(
                f"decode window W={W} is not a {ops.DEFAULT_BLOCK}-"
                f"multiple: running the dense jnp decode oracle")
    if layer is not None:
        k_cache, v_cache = k_cache[layer], v_cache[layer]
    return decode_attention_oracle(q, k_cache, v_cache, valid_mask)


def decode_attention_oracle(q, k_cache, v_cache, valid_mask):
    """Dense full-window decode attention (the jnp oracle: one einsum
    over all W slots regardless of fill).

    q: (B, 1, Hq, D); caches: (B, W, Hkv, D); valid_mask: (B, W) bool.
    """
    from repro.sharding import ctx as shard_ctx

    B, _, Hq, D = q.shape
    _, W, Hkv, _ = k_cache.shape
    n_rep = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    # grouped-GQA form: contract against the cache directly — no
    # repeat_kv materialisation (whose broadcast forced GSPMD into a
    # full cache reshard on the hd-sharded layout; §Perf H2)
    qg = q.reshape(B, 1, Hkv, n_rep, D)
    # pin q's hd to the cache's sharded layout: forces a partial
    # contraction + scores-AR instead of a 1 GB K gather (§Perf H2)
    qg = shard_ctx.constrain_lastdim(qg)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    # replicate the (small) scores: partial-contraction + AR beats
    # all-gathering the hd-sharded cache
    s = shard_ctx.constrain_scores(s)
    s = jnp.where(valid_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# KV cache (ring buffer for sliding-window long-context decode)
# --------------------------------------------------------------------------
def init_kv_cache(batch: int, window: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, window, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, window, n_kv, head_dim), dtype),
    }


def update_kv_cache(cache, k_new, v_new, position, layer=None, *,
                    backend: str = "auto"):
    """Insert one token per row at ``position % window`` (ring buffer).

    k_new/v_new: (B, 1, Hkv, D); position: scalar int32 (every row at
    the same absolute position — the lockstep fixed-batch loop) or (B,)
    int32 per-row positions (the continuous-batching slot loop, where
    each slot decodes at its own depth).  The cache is one layer's
    (B, W, Hkv, D) leaves, or with ``layer`` (an int32 scalar, per-row
    positions only) the stacked (L, B, W, Hkv, D) leaves that the decode
    scan carries: then the B new positions ``(layer, row, position % W)``
    are written and nothing else, in place in the carried (and donated)
    buffer.  ``backend`` picks that write as ``prefill_attention`` picks
    its kernel: the Pallas ``ops.cache_write``, which reads and writes
    the cache in the layout the device holds it in, or an XLA scatter
    (the CPU's, and the "oracle" backend's).  Returns
    (cache, valid_mask (B, W)).

    Counts ``serve.kv_write`` at trace time, ``path="in_place"`` for
    the stacked write and ``"select"`` for a write into one layer's
    leaves (the one-hot select of per-row positions, or the dynamic
    update of a scalar one), which the layer scan then stacks anew.
    """
    from repro.kernels import ops
    from repro.sharding import ctx as shard_ctx

    B, W = cache["k"].shape[-4], cache["k"].shape[-3]
    position = jnp.asarray(position, jnp.int32)
    if layer is not None:
        if position.ndim != 1:
            raise ValueError("a layer-indexed cache write takes per-row "
                             f"positions, got shape {position.shape}")
        spans.count("serve.kv_write", path="in_place")
        slots = jnp.mod(position, W)
        if backend == "kernel" or (backend == "auto"
                                   and not ops.interpret_default()):
            k, v = ops.cache_write(cache["k"], cache["v"], k_new[:, 0],
                                   v_new[:, 0], layer, slots)
        else:
            rows = jnp.arange(B)
            k, v = (cache[n].at[layer, rows, slots].set(
                        new[:, 0], unique_indices=True,
                        indices_are_sorted=True)
                    for n, new in (("k", k_new), ("v", v_new)))
        return {"k": k, "v": v}, _valid_slots(position[:, None], W)
    spans.count("serve.kv_write", path="select")
    # pin cache sharding across the update (EXPERIMENTS.md §Perf H2:
    # GSPMD otherwise fully rematerialises the cache — 1.1 GB AG/layer)
    k_new = shard_ctx.constrain_cache(k_new, "k")
    v_new = shard_ctx.constrain_cache(v_new, "v")
    kc = shard_ctx.constrain_cache(cache["k"], "k")
    vc = shard_ctx.constrain_cache(cache["v"], "v")
    if position.ndim == 0:
        slot = jnp.mod(position, W)
        k = jax.lax.dynamic_update_slice_in_dim(kc, k_new, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(vc, v_new, slot, axis=1)
        pos = position[None]                                  # (1,) rows
    else:
        # per-row slots: one-hot where-write over the layer's leaves
        hit = jnp.arange(W)[None, :] == jnp.mod(position, W)[:, None]
        k = jnp.where(hit[:, :, None, None], k_new, kc)
        v = jnp.where(hit[:, :, None, None], v_new, vc)
        pos = position
    k = shard_ctx.constrain_cache(k, "k")
    v = shard_ctx.constrain_cache(v, "v")
    valid = jnp.broadcast_to(_valid_slots(pos[:, None], W), (B, W))
    return {"k": k, "v": v}, valid


def _valid_slots(pos, W: int):
    """(rows, W) validity of ring slots after writing position ``pos``
    (rows, 1): slot i holds absolute position p with p % W == i and
    p <= pos; valid iff that p > pos - W (within window) and p >= 0."""
    last_abs = pos - jnp.mod(pos - jnp.arange(W)[None, :], W)
    return (last_abs >= 0) & (last_abs > pos - W)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = jax.nn.gelu(x @ w_in + b_in, approximate=True)
    return h @ w_out + b_out


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------
def softmax_xent(logits, labels, mask=None):
    """Mean token-level cross entropy; labels (…,) int32; mask same shape."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
