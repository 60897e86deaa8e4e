"""Pallas TPU kernel: single-token decode attention over the KV cache.

One generated token's q attends the (B, W, Hkv, D) sliding-window ring
buffer.  The window is blocked (``bw`` slots per grid step) with online
softmax, and the validity mask rides the grid: a window block holding no
valid slot is skipped entirely (``pl.when``), so a mostly-empty ring
buffer costs only its live blocks — unlike the dense oracle einsum in
``repro.models.layers.decode_attention_oracle``, which recomputes
O(B·W·H·D) every generated token regardless of fill.

GQA folds the query-head group into the q block's row axis: head
h = hkv * group + g matches the oracle's grouped reshape and the
``h // group`` index-map trick in ``flash_attention``.

Two grid layouts share the math:

* ``fold_batch=False`` — grid (B, n_w), blocks (Hkv, group, D) /
  (bw, Hkv, D) with a static loop over KV heads in the body.  The TPU
  shape: VMEM-sized blocks, 2-D MXU dots per head, one pass over each
  cache block regardless of the q:kv ratio.
* ``fold_batch=True`` — grid (n_w,), whole-batch blocks with batched
  einsums in the body.  The interpreter shape: interpret mode lowers
  the grid to a ``lax.while_loop`` whose carry holds the *full* input
  arrays and re-writes them every step, so wall-clock is roughly
  grid_steps × operand_bytes — folding (B, Hkv) into the block cuts
  the step count by B·Hkv while XLA fuses the larger per-step compute.

``fold_batch=None`` resolves to the interpret flag.
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


def _kernel_fine(q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref,
                 l_ref, *, scale: float, n_w: int, n_kv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = mask_ref[...] > 0                        # (1, bw)

    # skip window blocks with no valid slot — a ring buffer filled to
    # S of W slots only pays ceil(S / bw) blocks
    @pl.when(jnp.any(valid))
    def _compute():
        k_all = k_ref[...].astype(jnp.float32)       # (bw, Hkv, D)
        v_all = v_ref[...].astype(jnp.float32)
        for h in range(n_kv):                        # one cache pass
            q = q_ref[h].astype(jnp.float32)         # (group, D)
            k = k_all[:, h, :]                       # (bw, D)
            v = v_all[:, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)         # (group, bw)
            m_prev = m_ref[h]                        # (group, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # explicit zero (not just exp(NEG_INF - m)): with m == NEG_INF
            # (row empty so far) exp(s - m) would be exp(0) = 1 per slot
            p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_w - 1)
    def _finalize():
        # an all-invalid mask leaves l == 0: the clamp returns zeros
        # (finite), where the oracle's softmax-over-NEG_INF degrades to
        # mean(v) — callers never read attention at position < 0, so
        # only the no-NaN contract matters (pinned in tests)
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _kernel_batched(q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref,
                    l_ref, *, scale: float, n_w: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = mask_ref[...] > 0                        # (B, bw)

    @pl.when(jnp.any(valid))
    def _compute():
        q = q_ref[...].astype(jnp.float32)           # (B, Hkv, group, D)
        k = k_ref[...].astype(jnp.float32)           # (B, bw, Hkv, D)
        v = v_ref[...].astype(jnp.float32)
        s = jnp.einsum("bhgd,bwhd->bhgw", q, k) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_prev = m_ref[...]                          # (B, Hkv, group)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # zero invalid slots explicitly: an all-invalid row in a mixed
        # block has m == NEG_INF, where exp(s - m) alone would give 1
        p = jnp.exp(s - m_new[..., None]) * valid[:, None, None, :]
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[..., None]
                        + jnp.einsum("bhgw,bwhd->bhgd", p, v))
        m_ref[...] = m_new

    @pl.when(j == n_w - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...][..., None], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bw", "interpret", "fold_batch"))
def decode_attention(q, k_cache, v_cache, valid_mask, *, bw: int = 512,
                     interpret: bool | None = None,
                     fold_batch: bool | None = None):
    """q: (B, 1, Hq, D); caches: (B, W, Hkv, D); valid_mask: (B, W).

    Returns (B, 1, Hq, D).  W must be a multiple of ``bw``
    (``ops.decode_attention_auto`` picks a dividing block or falls back
    to the oracle).  The caches are consumed in their native serving
    layout — no transpose materialisation on the decode hot path.
    """
    interpret = resolve(interpret)
    if fold_batch is None:
        fold_batch = interpret
    B, one, Hq, D = q.shape
    _, W, Hkv, _ = k_cache.shape
    assert one == 1 and Hq % Hkv == 0
    group = Hq // Hkv
    bw = min(bw, W)
    assert W % bw == 0
    n_w = W // bw
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, group, D)                 # h = hkv*group + g
    mask = (valid_mask != 0).astype(jnp.int32)       # (B, W)

    if fold_batch:
        kernel = functools.partial(_kernel_batched, scale=scale, n_w=n_w)
        grid = (n_w,)
        in_specs = [
            pl.BlockSpec((B, Hkv, group, D), lambda j: (0, 0, 0, 0)),
            pl.BlockSpec((B, bw, Hkv, D), lambda j: (0, j, 0, 0)),
            pl.BlockSpec((B, bw, Hkv, D), lambda j: (0, j, 0, 0)),
            pl.BlockSpec((B, bw), lambda j: (0, j)),
        ]
        out_spec = pl.BlockSpec((B, Hkv, group, D), lambda j: (0, 0, 0, 0))
        scratch = [
            pltpu.VMEM((B, Hkv, group, D), jnp.float32),
            pltpu.VMEM((B, Hkv, group), jnp.float32),
            pltpu.VMEM((B, Hkv, group), jnp.float32),
        ]
    else:
        kernel = functools.partial(_kernel_fine, scale=scale, n_w=n_w,
                                   n_kv=Hkv)
        grid = (B, n_w)
        # all KV heads per block: the trailing (Hkv, D) / (group, D)
        # block dims are then whole array dims, which Mosaic accepts
        # for any head count and head size
        in_specs = [
            pl.BlockSpec((None, Hkv, group, D), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((None, bw, Hkv, D), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((None, bw, Hkv, D), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((None, 1, bw), lambda b, j: (b, 0, j)),
        ]
        mask = mask.reshape(B, 1, W)
        out_spec = pl.BlockSpec((None, Hkv, group, D),
                                lambda b, j: (b, 0, 0, 0))
        scratch = [
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
        ]

    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qg, k_cache, v_cache, mask)
    return out.reshape(B, 1, Hq, D)
