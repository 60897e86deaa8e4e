"""Plain Qwen2 forward pass (arXiv:2407.10671; the Hugging Face
``Qwen2ForCausalLM`` equations), in float32.

    x = embed[tokens]
    per layer:  h = rms(x) * g1
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                q, k = rope(q), rope(k)        (rotate-half, theta)
                x += softmax(q k^T / sqrt(hd) + causal) v  Wo
                h = rms(x) * g2
                x += (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * gf) embed^T             (tied head)

Query head j reads key/value head j // (Hq / Hkv).  Every matmul runs at
``HIGHEST`` precision.  Imports nothing of the program.

``quant`` is the control's knob: with ``"fp8"`` every matmul operand of
the layers (weights and activations, attention included) is rounded to
float8 e4m3 first, a precision below the bfloat16 the configuration
serves in; the head stays as the configuration states it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _q(x, quant):
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, conf, quant):
    S, d = x.shape
    Hq, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // Hq
    eps = conf["rms_norm_eps"]
    h = _rms(x, lp["ln1"], eps)
    q = (_mm(h, lp["wq"], quant) + lp["bq"]).reshape(S, Hq, hd)
    k = (_mm(h, lp["wk"], quant) + lp["bk"]).reshape(S, Hkv, hd)
    v = (_mm(h, lp["wv"], quant) + lp["bv"]).reshape(S, Hkv, hd)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _q(q, quant), _q(k, quant),
                   precision=HI) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _q(p, quant), _q(v, quant),
                   precision=HI).reshape(S, Hq * hd)
    x = x + _mm(o, lp["wo"], quant)
    h = _rms(x, lp["ln2"], eps)
    g = jax.nn.silu(_mm(h, lp["w_gate"], quant)) * _mm(h, lp["w_up"], quant)
    return x + _mm(g, lp["w_down"], quant)


@partial(jax.jit, static_argnames=("conf_items", "first", "quant"))
def _logits(params, tokens, conf_items, first, quant):
    conf = dict(conf_items)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = f32["embed"][tokens]

    def body(x, lp):
        return _layer(x, lp, conf, quant), None

    x, _ = jax.lax.scan(body, x, f32["layers"])
    x = _rms(x[first:], f32["ln_f"], conf["rms_norm_eps"])
    head = f32["embed"].T if conf["tie_word_embeddings"] else f32["lm_head"]
    return jnp.matmul(x, head, precision=HI)


def logits(params, tokens, conf: dict, first: int = 0, quant=None):
    """Logits (S - first, vocab) of one sequence ``tokens`` (S,) at
    positions ``first`` .. S - 1."""
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings")
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   tuple((k, conf[k]) for k in keys), first, quant)
