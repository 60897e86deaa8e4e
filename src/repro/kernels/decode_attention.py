"""Pallas TPU kernels: single-token decode attention over the KV cache,
and the in-place write of each row's new K/V.

One generated token's q attends the (B, W, Hkv, D) sliding-window ring
buffer, or layer ``l`` of the stacked (L, B, W, Hkv, D) cache that the
decode scan carries: the layer index is a scalar-prefetch argument, so
the K/V index maps read ``(l, b, j)`` blocks of the stacked array and no
per-layer slice is materialised (a custom call cannot fuse one into its
operand).  The window is blocked (``bw`` slots per grid step) with online
softmax, and the validity mask rides the grid: a window block holding no
valid slot is skipped entirely (``pl.when``), so a mostly-empty ring
buffer costs only its live blocks — unlike the dense oracle einsum in
``repro.models.layers.decode_attention_oracle``, which recomputes
O(B·W·H·D) every generated token regardless of fill.

Cache view.  A TPU lays a (..., W, Hkv, D) array out row-major when the
head dim fills whole 128-lane tiles.  When it does not (D = 64), it puts
W minor-most and tiles over (D, W), so no (Hkv, D) row is padded to 128
lanes.  A custom call takes its operand row-major, so both kernels read
the cache through the view whose row-major layout is the one the device
holds (:func:`window_minor`): the (..., Hkv, D, W) transpose there, a
bitcast, and the cache itself otherwise.  Reading the other view would
copy the whole cache on every call.

GQA folds the query-head group into the q block's row axis: head
h = hkv * group + g matches the oracle's grouped reshape and the
``h // group`` index-map trick in ``flash_attention``.

Two grid layouts share the math:

* ``fold_batch=False`` — grid (B, n_w), blocks (Hkv, group, D) /
  (bw, Hkv, D) or (Hkv, D, bw) with a static loop over KV heads in the
  body.  The TPU shape: VMEM-sized blocks, 2-D MXU dots per head, one
  pass over each cache block regardless of the q:kv ratio.
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
LANES = 128


def window_minor(head_dim: int) -> bool:
    """Whether a TPU holds a (..., W, Hkv, D) cache with W minor-most: it
    does when D fills no whole lane tile."""
    return head_dim % LANES != 0


def _view(cache, w_minor: bool):
    """(L, B, W, Hkv, D) -> the kernels' view of it (module docstring)."""
    return cache.transpose(0, 1, 3, 4, 2) if w_minor else cache


def _unview(cache, w_minor: bool):
    return cache.transpose(0, 1, 4, 2, 3) if w_minor else cache


def _kernel_fine(layer_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref,
                 m_ref, l_ref, *, scale: float, n_w: int, n_kv: int,
                 w_minor: bool):
    del layer_ref                                    # read by the index maps
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = mask_ref[...] > 0                        # (1, bw)
    # the window axis of one head's (bw, D) or (D, bw) K/V block
    w_ax = 1 if w_minor else 0

    # skip window blocks with no valid slot — a ring buffer filled to
    # S of W slots only pays ceil(S / bw) blocks
    @pl.when(jnp.any(valid))
    def _compute():
        k_all = k_ref[...].astype(jnp.float32)       # (Hkv, D, bw) or
        v_all = v_ref[...].astype(jnp.float32)       # (bw, Hkv, D)
        for h in range(n_kv):                        # one cache pass
            q = q_ref[h].astype(jnp.float32)         # (group, D)
            k = k_all[h] if w_minor else k_all[:, h, :]
            v = v_all[h] if w_minor else v_all[:, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1 - w_ax,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)         # (group, bw)
            m_prev = m_ref[h]                        # (group, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # explicit zero (not just exp(NEG_INF - m)): with m == NEG_INF
            # (row empty so far) exp(s - m) would be exp(0) = 1 per slot
            p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (w_ax,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_w - 1)
    def _finalize():
        # an all-invalid mask leaves l == 0: the clamp returns zeros
        # (finite), where the oracle's softmax-over-NEG_INF degrades to
        # mean(v) — callers never read attention at position < 0, so
        # only the no-NaN contract matters (pinned in tests)
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _kernel_batched(layer_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, scale: float, n_w: int,
                    w_minor: bool):
    del layer_ref
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = mask_ref[...] > 0                        # (B, bw)
    kv = "bhdw" if w_minor else "bwhd"

    @pl.when(jnp.any(valid))
    def _compute():
        q = q_ref[...].astype(jnp.float32)           # (B, Hkv, group, D)
        k = k_ref[...].astype(jnp.float32)           # (B, Hkv, D, bw) or
        v = v_ref[...].astype(jnp.float32)           # (B, bw, Hkv, D)
        s = jnp.einsum(f"bhgd,{kv}->bhgw", q, k) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_prev = m_ref[...]                          # (B, Hkv, group)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # zero invalid slots explicitly: an all-invalid row in a mixed
        # block has m == NEG_INF, where exp(s - m) alone would give 1
        p = jnp.exp(s - m_new[..., None]) * valid[:, None, None, :]
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[..., None]
                        + jnp.einsum(f"bhgw,{kv}->bhgd", p, v))
        m_ref[...] = m_new

    @pl.when(j == n_w - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...][..., None], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bw", "interpret", "fold_batch"))
def decode_attention(q, k_cache, v_cache, valid_mask, layer=None, *,
                     bw: int = 512, interpret: bool | None = None,
                     fold_batch: bool | None = None):
    """q: (B, 1, Hq, D); caches: (B, W, Hkv, D), or (L, B, W, Hkv, D)
    read at ``layer`` (an int32 scalar); valid_mask: (B, Wr).

    Returns (B, 1, Hq, D).  The kernel reads window slots ``[0, Wr)``:
    a mask narrower than the cache (``ops.decode_attention_auto``'s
    live-window crop) shortens the grid, not the array.  Wr must be a
    multiple of ``bw`` and at most W.  The caches are read in the view
    the device holds them in — no transpose, slice or layout copy on
    the decode hot path.
    """
    interpret = resolve(interpret)
    if fold_batch is None:
        fold_batch = interpret
    if k_cache.ndim == 4:                            # one layer: L = 1
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    B, one, Hq, D = q.shape
    _, _, W, Hkv, _ = k_cache.shape
    Wr = valid_mask.shape[1]
    assert one == 1 and Hq % Hkv == 0
    group = Hq // Hkv
    bw = min(bw, Wr)
    assert Wr % bw == 0 and Wr <= W
    n_w = Wr // bw
    scale = 1.0 / math.sqrt(D)
    w_minor = window_minor(D)
    k_cache, v_cache = _view(k_cache, w_minor), _view(v_cache, w_minor)

    qg = q.reshape(B, Hkv, group, D)                 # h = hkv*group + g
    mask = (valid_mask != 0).astype(jnp.int32)       # (B, Wr)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    if fold_batch:
        kernel = functools.partial(_kernel_batched, scale=scale, n_w=n_w,
                                   w_minor=w_minor)
        grid = (n_w,)
        kv_spec = (pl.BlockSpec((None, B, Hkv, D, bw),
                                lambda j, l: (l[0], 0, 0, 0, j))
                   if w_minor else
                   pl.BlockSpec((None, B, bw, Hkv, D),
                                lambda j, l: (l[0], 0, j, 0, 0)))
        in_specs = [
            pl.BlockSpec((B, Hkv, group, D), lambda j, l: (0, 0, 0, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((B, bw), lambda j, l: (0, j)),
        ]
        out_spec = pl.BlockSpec((B, Hkv, group, D),
                                lambda j, l: (0, 0, 0, 0))
        scratch = [
            pltpu.VMEM((B, Hkv, group, D), jnp.float32),
            pltpu.VMEM((B, Hkv, group), jnp.float32),
            pltpu.VMEM((B, Hkv, group), jnp.float32),
        ]
    else:
        kernel = functools.partial(_kernel_fine, scale=scale, n_w=n_w,
                                   n_kv=Hkv, w_minor=w_minor)
        grid = (B, n_w)
        # all KV heads per block: the trailing (Hkv, D) or (D, bw) block
        # dims are whole head dims and lane-aligned window blocks, which
        # Mosaic accepts for any head count and head size
        kv_spec = (pl.BlockSpec((None, None, Hkv, D, bw),
                                lambda b, j, l: (l[0], b, 0, 0, j))
                   if w_minor else
                   pl.BlockSpec((None, None, bw, Hkv, D),
                                lambda b, j, l: (l[0], b, j, 0, 0)))
        in_specs = [
            pl.BlockSpec((None, Hkv, group, D),
                         lambda b, j, l: (b, 0, 0, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((None, 1, bw), lambda b, j, l: (b, 0, j)),
        ]
        mask = mask.reshape(B, 1, Wr)
        out_spec = pl.BlockSpec((None, Hkv, group, D),
                                lambda b, j, l: (b, 0, 0, 0))
        scratch = [
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
        ]

    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(layer, qg, k_cache, v_cache, mask)
    return out.reshape(B, 1, Hq, D)


# --------------------------------------------------------------------------
# the in-place write of one new position per row
# --------------------------------------------------------------------------
def _write_kernel(layer_ref, slot_ref, k_new_ref, v_new_ref, k_ref, v_ref,
                  k_out, v_out, *, w_minor: bool, wb: int):
    del layer_ref
    if not w_minor:                    # the block is the slot's (Hkv, D)
        k_out[...] = k_new_ref[...]
        v_out[...] = v_new_ref[...]
        return
    # the block is the (Hkv, D, wb) window block holding the slot: set
    # the slot's lane from the (Hkv, D, 1) new values, keep the rest
    lane = slot_ref[pl.program_id(0)] % wb
    hit = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 2) == lane
    for new, old, out in ((k_new_ref, k_ref, k_out), (v_new_ref, v_ref, v_out)):
        out[...] = jnp.where(hit, new[...].astype(jnp.float32),
                             old[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_write(k_cache, v_cache, k_new, v_new, layer, slots, *,
                interpret: bool | None = None):
    """Write row b's new K/V at ``(layer, b, slots[b])`` of the stacked
    (L, B, W, Hkv, D) caches, in place.

    k_new/v_new: (B, Hkv, D); layer: int32 scalar; slots: (B,) int32 in
    ``[0, W)``.  The caches are aliased to the outputs, and the grid
    visits one block per row — the slot's (Hkv, D) entry, or in the
    window-minor view the 128-slot block that holds it — so nothing
    else of the cache is read or written.  In place where the caller's
    cache buffer is its own to overwrite (a carried, donated cache);
    XLA copies it first otherwise.  Returns (k_cache, v_cache).
    """
    interpret = resolve(interpret)
    L, B, W, Hkv, D = k_cache.shape
    w_minor = window_minor(D)
    wb = LANES if W % LANES == 0 else W
    if w_minor:
        new_spec = pl.BlockSpec((None, Hkv, D, 1), lambda b, l, s: (b, 0, 0, 0))
        kv_spec = pl.BlockSpec((None, None, Hkv, D, wb),
                               lambda b, l, s: (l[0], b, 0, 0, s[b] // wb))
        k_new, v_new = k_new[..., None], v_new[..., None]
    else:
        new_spec = pl.BlockSpec((None, Hkv, D), lambda b, l, s: (b, 0, 0))
        kv_spec = pl.BlockSpec((None, None, None, Hkv, D),
                               lambda b, l, s: (l[0], b, s[b], 0, 0))
    k_view, v_view = _view(k_cache, w_minor), _view(v_cache, w_minor)
    k_out, v_out = pl.pallas_call(
        functools.partial(_write_kernel, w_minor=w_minor, wb=wb),
        name="kv_cache_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[new_spec, new_spec, kv_spec, kv_spec],
            out_specs=[kv_spec, kv_spec]),
        out_shape=[jax.ShapeDtypeStruct(k_view.shape, k_view.dtype),
                   jax.ShapeDtypeStruct(v_view.shape, v_view.dtype)],
        # operands count the two scalar-prefetch arguments
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      jnp.asarray(slots, jnp.int32), k_new.astype(k_cache.dtype),
      v_new.astype(v_cache.dtype), k_view, v_view)
    return _unview(k_out, w_minor), _unview(v_out, w_minor)
