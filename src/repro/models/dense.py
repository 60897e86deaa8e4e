"""Dense decoder-only transformer (llama/qwen family) + VLM variant.

Parameters are stored **stacked over layers** (leading L axis) and the
forward pass is a ``jax.lax.scan`` over that axis, so compiled-HLO size
is independent of depth (llama3-405b's 126 layers compile like 2).

The VLM family (phi-3-vision backbone) reuses everything here; its stub
vision frontend supplies precomputed patch embeddings which are
projected and prepended to the token embeddings (see DESIGN.md — the
modality frontend is the one allowed stub).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def attn_init(rng, cfg: ModelConfig, n_layers: int):
    d, hd = cfg.d_model, cfg.hd()
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(rng, 4)
    p = {
        "wq": _stacked(ks[0], n_layers, d, Hq * hd, cfg),
        "wk": _stacked(ks[1], n_layers, d, Hkv * hd, cfg),
        "wv": _stacked(ks[2], n_layers, d, Hkv * hd, cfg),
        "wo": _stacked(ks[3], n_layers, Hq * hd, d, cfg),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_layers, Hq * hd), cfg.pdtype)
        p["bk"] = jnp.zeros((n_layers, Hkv * hd), cfg.pdtype)
        p["bv"] = jnp.zeros((n_layers, Hkv * hd), cfg.pdtype)
    return p


def _stacked(rng, n_layers, d_in, d_out, cfg: ModelConfig):
    ks = jax.random.split(rng, n_layers)
    return jnp.stack([L.dense_init(k, d_in, d_out, cfg.pdtype) for k in ks])


def mlp_init(rng, cfg: ModelConfig, n_layers: int):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": _stacked(ks[0], n_layers, d, f, cfg),
        "w_up": _stacked(ks[1], n_layers, d, f, cfg),
        "w_down": _stacked(ks[2], n_layers, f, d, cfg),
    }


def init_params(cfg: ModelConfig, rng):
    keys = jax.random.split(rng, 6)
    nL, d = cfg.n_layers, cfg.d_model
    params = {
        "embed": L.embed_init(keys[0], cfg.vocab, d, cfg.pdtype),
        "layers": {
            "ln1": jnp.ones((nL, d), cfg.pdtype),
            "ln2": jnp.ones((nL, d), cfg.pdtype),
            **attn_init(keys[1], cfg, nL),
            **mlp_init(keys[2], cfg, nL),
        },
        "ln_f": jnp.ones((d,), cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[3], d, cfg.vocab, cfg.pdtype)
    if cfg.family == "vlm":
        params["vision_proj"] = L.dense_init(
            keys[4], cfg.vlm.d_vision, d, cfg.pdtype)
    return params


# --------------------------------------------------------------------------
# per-layer blocks (operate on the scanned per-layer param slice ``lp``)
# --------------------------------------------------------------------------
def _qkv(lp, x, cfg: ModelConfig):
    B, S, _ = x.shape
    hd = cfg.hd()

    def proj(w, b):
        if not cfg.qkv_bias:
            return x @ lp[w].astype(cfg.cdtype)
        # the bias is added to the fp32 product and rounded once: with
        # a bf16 product plus a bf16 bias the TPU compiler drops the
        # inner rounding in some fusions and not in others, by batch
        # shape, and a batch-1 prefill then writes other K/V than the
        # same prompt in a bigger batch
        y = jnp.matmul(x, lp[w].astype(cfg.cdtype),
                       preferred_element_type=jnp.float32)
        return (y + lp[b].astype(jnp.float32)).astype(cfg.cdtype)

    q, k, v = proj("wq", "bq"), proj("wk", "bk"), proj("wv", "bv")
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def attn_block(lp, x, positions, cfg: ModelConfig, *, causal=True):
    """Full-sequence self attention (train / prefill)."""
    from repro.sharding import ctx as shard_ctx

    B, S, _ = x.shape
    q, k, v = _qkv(lp, x, cfg)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if cfg.seq_shard and shard_ctx.active():
        # explicit seq->heads reshard (all-to-all) around attention
        # instead of letting GSPMD replicate the S^2 compute (§Perf H4)
        q, k, v = (shard_ctx.constrain_heads(t) for t in (q, k, v))
    o = L.prefill_attention(q, k, v, causal=causal,
                            q_chunk=cfg.attn_chunk_q, k_chunk=cfg.attn_chunk_k,
                            unroll=cfg.unroll_layers,
                            backend=cfg.attn_backend)
    o = o.reshape(B, S, cfg.n_heads * cfg.hd()) @ lp["wo"].astype(cfg.cdtype)
    if cfg.seq_shard and shard_ctx.active():
        o = shard_ctx.constrain_seq(o)
    return o


def attn_block_decode(lp, x, cache, position, cfg: ModelConfig, *,
                      w_live: int | None = None):
    """One-token self attention against a ring-buffer KV cache.

    cache: {"k": (B, W, Hkv, hd), "v": ...}; position: scalar int32
    (lockstep fixed batch) or (B,) int32 per-slot positions (the
    continuous-batching serve loop).  ``w_live`` is the loop's static
    live-slot bound for the cropped decode fast path.
    """
    B, S, _ = x.shape  # S == 1
    q, k, v = _qkv(lp, x, cfg)
    position = jnp.asarray(position, jnp.int32)
    pos = (jnp.full((B, 1), position, jnp.int32) if position.ndim == 0
           else position[:, None])
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)
    cache, valid = L.update_kv_cache(cache, k, v, position)
    o = L.decode_attention(q, cache["k"], cache["v"], valid,
                           backend=cfg.attn_backend, w_live=w_live)
    y = o.reshape(B, 1, cfg.n_heads * cfg.hd()) @ lp["wo"].astype(cfg.cdtype)
    return y, cache


def mlp_block(lp, x, cfg: ModelConfig):
    return L.swiglu(x, lp["w_gate"].astype(cfg.cdtype),
                    lp["w_up"].astype(cfg.cdtype),
                    lp["w_down"].astype(cfg.cdtype))


def layer_fn(lp, x, positions, cfg: ModelConfig):
    x = x + attn_block(lp, L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                       positions, cfg)
    x = x + mlp_block(lp, L.rms_norm(x, lp["ln2"], cfg.norm_eps), cfg)
    return x


def layer_fn_decode(lp, x, cache, position, cfg: ModelConfig, *,
                    w_live: int | None = None):
    a, cache = attn_block_decode(lp, L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                                 cache, position, cfg, w_live=w_live)
    x = x + a
    x = x + mlp_block(lp, L.rms_norm(x, lp["ln2"], cfg.norm_eps), cfg)
    return x, cache


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------
def embed_inputs(cfg: ModelConfig, params, batch):
    """Token (+ optional patch) embedding.  Returns (x, positions)."""
    tok = params["embed"].astype(cfg.cdtype)[batch["tokens"]]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.cdtype) @ \
            params["vision_proj"].astype(cfg.cdtype)
        x = jnp.concatenate([pe, tok], axis=1)
    else:
        x = tok
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions


def forward(cfg: ModelConfig, params, batch, mlp_fn=None):
    """Returns logits (B, S, V).  ``mlp_fn`` hook lets MoE reuse this."""
    x, positions = embed_inputs(cfg, params, batch)

    def body(x, lp):
        h = x + attn_block(lp, L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                           positions, cfg)
        fn = mlp_fn or (lambda lp, y: mlp_block(lp, y, cfg))
        h = h + fn(lp, L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, None

    body_ = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_, x, params["layers"], unroll=cfg.n_layers if cfg.unroll_layers else 1)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.cdtype)
    return x @ head


def loss_fn(cfg: ModelConfig, params, batch):
    logits = forward(cfg, params, batch)
    labels, mask = batch["labels"], batch.get("loss_mask")
    if cfg.family == "vlm" and "patch_embeds" in batch:
        # patch positions carry no next-token target
        P = batch["patch_embeds"].shape[1]
        logits = logits[:, P:]
    return L.softmax_xent(logits, labels, mask)


def prefill(cfg: ModelConfig, params, batch, mlp_fn=None):
    """Forward over the prompt, returning (last_logits, kv_cache).

    Only the final position's logits are formed (materialising
    (B, 32k, 128k) logits would be ~34 GB/device); the per-layer K/V
    streams become the decode cache.
    """
    x, positions = embed_inputs(cfg, params, batch)

    def body(x, lp):
        h1 = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        B, S, _ = h1.shape
        q, k, v = _qkv(lp, h1, cfg)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.prefill_attention(q, k, v, causal=True,
                                q_chunk=cfg.attn_chunk_q,
                                k_chunk=cfg.attn_chunk_k,
                                unroll=cfg.unroll_layers,
                                backend=cfg.attn_backend)
        a = o.reshape(B, S, cfg.n_heads * cfg.hd()) @ \
            lp["wo"].astype(cfg.cdtype)
        h = x + a
        fn = mlp_fn or (lambda lp, y: mlp_block(lp, y, cfg))
        h = h + fn(lp, L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, {"k": k, "v": v}

    body_ = jax.checkpoint(body) if cfg.remat else body
    x, cache = jax.lax.scan(body_, x, params["layers"], unroll=cfg.n_layers if cfg.unroll_layers else 1)
    return _serve_logits(cfg, params, x[:, -1:]), cache


def _serve_logits(cfg: ModelConfig, params, x):
    """fp32 logits of the final norm and head, for greedy serving.

    In bf16 a large vocabulary ties at the top often, and on a TPU
    the head of a batch-1 prefill rounds differently from the same row
    in a bigger batch, so admission and fixed-batch serving could pick
    different tokens.  Kept in fp32 at full matmul precision, the two
    agree to fp32 rounding; the head is read once per token either way.
    """
    x = L.rms_norm(x.astype(jnp.float32), params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.matmul(x, head.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


# ----- decode -------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, window: int):
    nL, hd = cfg.n_layers, cfg.hd()
    return {
        "k": jnp.zeros((nL, batch, window, cfg.n_kv_heads, hd), cfg.cdtype),
        "v": jnp.zeros((nL, batch, window, cfg.n_kv_heads, hd), cfg.cdtype),
    }


def decode_step(cfg: ModelConfig, params, cache, token, position,
                mlp_fn=None, *, w_live: int | None = None):
    """token: (B, 1) int32; position: scalar int32 (absolute, lockstep)
    or (B,) int32 per-slot positions (continuous batching).

    Returns (logits (B, 1, V), new_cache).  ``w_live`` is the serving
    loop's static live-slot bound (see ``layers.decode_attention``).
    """
    x = params["embed"].astype(cfg.cdtype)[token]

    def body(x, scanned):
        lp, layer_cache = scanned
        a, layer_cache = attn_block_decode(
            lp, L.rms_norm(x, lp["ln1"], cfg.norm_eps), layer_cache,
            position, cfg, w_live=w_live)
        h = x + a
        fn = mlp_fn or (lambda lp, y: mlp_block(lp, y, cfg))
        h = h + fn(lp, L.rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, layer_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache), unroll=cfg.n_layers if cfg.unroll_layers else 1)
    return _serve_logits(cfg, params, x), new_cache
