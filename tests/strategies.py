"""Strategies + builders for property-based MA-Echo parity tests.

The strategy layer draws only from the primitives the deterministic
stub in ``_hypothesis_stub.py`` implements (``integers``, ``floats``,
``sampled_from``, ``booleans``, ``lists``) — under the stub each
``@given`` test runs a fixed seeded sample of the same ranges, and
``pip install hypothesis`` upgrades the identical tests to adaptive
search with shrinking.  Strategies therefore draw compact *case
descriptors* (seeds, kind names, shape tuples); the ``build_*``
functions below materialize them into concrete client pytrees with
jax PRNG — mixed leaf shapes (tile-aligned, odd-padding and sub-tile),
both weight conventions, all four projector kinds, stacked-layer
leading axes for stack_levels 0–3, and ragged client masks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from hypothesis import strategies as st

KINDS = ("scalar", "diag", "full", "factored")
CONVENTIONS = ("oi", "io")
# (out_d, in_d) in "oi" terms: one direct-tiling shape, two padding
# shapes, one below a 128-tile (the jnp-oracle ref fallback)
SHAPES = ((128, 128), (256, 140), (200, 256), (48, 64))
# (out_d, in_d) and the VMEM budget the block rule is held to (None: the
# kernels' own) whose derived blocks are not powers of two: under 2 MiB
# 4864 = 19 × 256 rows split into 152-row blocks and a 4480-row mesh
# shard into 152- to 320-row ones; 600 × 300 pads to whole 640 × 384
# blocks; under 4 MiB 640 × 1536 takes 384 to 768 columns, or 16 to 64
# rows of whole ones where Eq. 11 normalises
BLOCK_CASES = (((4864, 256), 2 ** 21), ((600, 300), None),
               ((4480, 128), 2 ** 21), ((640, 1536), 2 ** 22))
# leading stacked-layer axes: stack_levels 0 through 3
LEADS = ((), (2,), (3,), (2, 2), (2, 1, 2))
RANK = 16


def seeds():
    return st.integers(0, 2 ** 20)


def n_clients():
    return st.integers(2, 4)


def kinds():
    return st.sampled_from(KINDS)


def conventions():
    return st.sampled_from(CONVENTIONS)


def shapes():
    return st.sampled_from(SHAPES)


def leads():
    return st.sampled_from(LEADS)


def block_cases():
    return st.sampled_from(BLOCK_CASES)


def masked():
    return st.booleans()


def bools():
    return st.booleans()


# --------------------------------------------------------------------------
# builders: descriptor -> concrete pytrees
# --------------------------------------------------------------------------
def make_projector(key, kind: str, lead: tuple, in_d: int,
                   rank: int = RANK):
    """One client's projector leaf of ``kind`` with leading stacked
    axes ``lead`` acting on an ``in_d``-dim input space."""
    if kind == "scalar":
        return (jnp.ones(lead) if lead
                else jnp.ones((), jnp.float32))
    if kind == "diag":
        return jax.random.uniform(key, lead + (in_d,),
                                  minval=0.1, maxval=1.0)
    r = min(rank, in_d)
    U = jnp.linalg.qr(jax.random.normal(key, lead + (in_d, r)))[0]
    s = jax.random.uniform(jax.random.fold_in(key, 1), lead + (r,),
                           minval=0.1, maxval=1.0)
    if kind == "factored":
        return {"U": U, "s": s}
    return jnp.einsum("...ik,...k,...jk->...ij", U, s, U)


def build_case(seed: int, n: int, kind: str, convention: str,
               lead: tuple, shape: tuple, use_mask: bool):
    """Materialize one aggregation case.

    Returns ``(clients, projs, stack_levels, client_mask)``: ``n``
    clients of a two-leaf pytree — the (possibly stacked) matmul leaf
    "W" plus a 1-D bias "b" on the scalar rule, so every case mixes an
    eligible and an always-oracle leaf — with per-leaf stack_levels
    and an optional ragged participation mask (≥1 client kept).
    """
    out_d, in_d = shape
    wshape = lead + ((out_d, in_d) if convention == "oi"
                     else (in_d, out_d))
    clients, projs = [], []
    for i in range(n):
        k = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        clients.append({
            "W": jax.random.normal(k, wshape) * 0.3,
            "b": jax.random.normal(jax.random.fold_in(k, 1),
                                   (out_d,)) * 0.1,
        })
        projs.append({
            "W": make_projector(jax.random.fold_in(k, 2), kind, lead,
                                in_d),
            "b": jnp.ones(()),
        })
    levels = {"W": len(lead), "b": 0}
    mask = None
    if use_mask:
        bits = jax.random.bernoulli(
            jax.random.PRNGKey(seed ^ 0x5EED), 0.6, (n,))
        mask = bits.at[seed % n].set(True)   # ≥1 participant
    return clients, projs, levels, mask


# --------------------------------------------------------------------------
# decode-attention case space (serving fast path vs dense oracle)
# --------------------------------------------------------------------------
# (B, W, Hq, Hkv, D): MHA, GQA 4:1, GQA 8:2, MQA with sub-128 head_dim
DECODE_SHAPES = ((1, 128, 4, 4, 64), (2, 256, 8, 2, 64),
                 (2, 256, 16, 4, 64), (2, 128, 4, 1, 32))


def decode_shapes():
    return st.sampled_from(DECODE_SHAPES)


def fills():
    """Tokens written into the ring buffer so far; the builder lets
    this exceed W to exercise wraparound (position = fill - 1 > W)."""
    return st.integers(1, 640)


def build_decode_case(seed: int, shape: tuple, fill: int):
    """(q, k_cache, v_cache, valid_mask, position) for one decode step.

    ``fill`` tokens have been written into the W-slot ring buffer;
    ``position = fill - 1`` is the slot of the newest token.  When
    ``fill > W`` the buffer has wrapped and every slot is valid —
    the mask uses the ring-distance formula the model layer derives
    from the scalar position.
    """
    B, W, Hq, Hkv, D = shape
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (B, 1, Hq, D))
    kc = jax.random.normal(jax.random.fold_in(k, 1), (B, W, Hkv, D))
    vc = jax.random.normal(jax.random.fold_in(k, 2), (B, W, Hkv, D))
    pos = fill - 1
    idx = jnp.arange(W)
    last_abs = pos - jnp.mod(pos - idx, W)
    valid = jnp.broadcast_to((last_abs >= 0) & (last_abs > pos - W),
                             (B, W))
    return q, kc, vc, valid, pos


def build_layer(seed: int, n: int, kind: str, shape: tuple,
                lead: tuple = ()):
    """Materialize one bare (W, V, P) layer in "oi" kernel layout for
    kernel-level parity tests: W (lead..., out, in), V with the client
    axis in front, P stacked per kind."""
    out_d, in_d = shape
    k = jax.random.PRNGKey(seed)
    W = jax.random.normal(k, lead + (out_d, in_d)) * 0.5
    V = jax.random.normal(jax.random.fold_in(k, 1),
                          (n,) + lead + (out_d, in_d)) * 0.5
    Ps = [make_projector(jax.random.fold_in(k, 10 + i), kind, lead,
                         in_d) for i in range(n)]
    P = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, 0), *Ps)
    return W, V, P
