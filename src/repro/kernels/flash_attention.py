"""Pallas TPU kernel: causal flash attention with GQA.

The model zoo's training/prefill hot spot.  Online-softmax over KV
blocks with running (m, l, acc) in VMEM scratch; grid
(batch, q_heads, n_q_blocks, n_kv_blocks) with scratch carried across
the innermost axis.  Oracle: ``repro.models.layers.chunked_attention``
(pure jnp, same math) — swept in tests/test_kernels.py.

Blocks: q (bq, d), k/v (bk, d); MXU-aligned when bq, bk, d are
multiples of 128 (head_dim 64/80/96 still lower, at reduced MXU
utilisation — noted in the roofline).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.env import resolve

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, bq: int, bk: int, n_k: int, causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip fully-masked blocks (causal: kv block strictly after q block)
    run = True
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)            # (bq, d)
        k = k_ref[...].astype(jnp.float32)            # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot(q, k.T,
                        preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot(p, v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...][:, None], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bk: int = 256, interpret: bool | None = None):
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D); returns (B, Sq, Hq, D).

    GQA is handled by an index_map trick: kv head = q head // group.
    Sequences must be multiples of the block sizes (caller pads).
    ``interpret=None`` runs the Pallas interpreter on a CPU backend and
    Mosaic on a TPU (``env.resolve``).  Differentiable: the backward
    pass is the VJP of the jnp reference (``ref.flash_attention_ref``),
    so a train step keeps the kernel forward.
    """
    return _flash(q, k, v, causal, bq, bk, resolve(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, bq, bk, interpret):
    return _flash_forward(q, k, v, causal, bq, bk, interpret)


def _flash_fwd(q, k, v, causal, bq, bk, interpret):
    return _flash_forward(q, k, v, causal, bq, bk, interpret), (q, k, v)


def _flash_bwd(causal, bq, bk, interpret, res, g):
    from repro.kernels.ref import flash_attention_ref

    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_ref(q, k, v, causal=causal), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_forward(q, k, v, causal, bq, bk, interpret):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    n_q, n_k = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(D)

    qt = q.transpose(0, 2, 1, 3)      # (B, Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3)      # (B, Hkv, Sk, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, scale=scale, bq=bq, bk=bk,
                               n_k=n_k, causal=causal)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, None, bq, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
