"""Mosaic compile rehearsals: the main path's Pallas kernels, lowered at
qwen2-0.5b widths for a described (not attached) TPU v5e chip.

Interpret-mode tests cannot see what the TPU compiler refuses —
unaligned blocks, contractions Mosaic has no lowering for, VMEM
overruns.  Each test here compiles one kernel with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology and asserts that the
program carries the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never while a module
is imported: only one process may hold the TPU library, and pytest-xdist
workers all import every test file.  The persistent compilation cache
is off around the compiles (an entry written for a described chip cannot
be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import maecho_gram as mg
from repro.kernels import maecho_update as mu
from repro.kernels import maecho_v_update as mv

# qwen2-0.5b (arXiv:2407.10671): 24 layers, d_model 896, d_ff 4864,
# vocab 151936, 14 query / 2 KV heads of 64; two silos
L, D, F, VOCAB, HQ, HKV, HD, N = 24, 896, 4864, 151936, 14, 2, 64, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32 = jnp.float32
# the w_gate leaf in the kernels' "oi" layout: (L, out=d_ff, in=d_model)
W_GATE = ((L, F, D), F32)
V_GATE = ((N, L, F, D), F32)
P_GATE = ((N, L, D, D), F32)


def test_gram_stacked_compiles(one_chip):
    _compile(lambda W, V, P: mg.maecho_gram_stacked(W, V, P,
                                                    interpret=False),
             one_chip, W_GATE, V_GATE, P_GATE)


def test_gram_diag_compiles(one_chip):
    # the embedding in "oi" layout: out = d_model, in = vocab
    _compile(lambda W, V, p: mg.maecho_gram_diag(W, V, p,
                                                 interpret=False),
             one_chip, ((D, VOCAB), F32), ((N, D, VOCAB), F32),
             ((N, VOCAB), F32))


def test_update_stacked_compiles(one_chip):
    _compile(lambda W, V, P, a: mu.maecho_update_stacked(
        W, V, P, a, eta=0.5, interpret=False),
        one_chip, W_GATE, V_GATE, P_GATE, ((L, N), F32))


def test_v_update_stacked_compiles(one_chip):
    _compile(lambda W, V, P: mv.maecho_v_update_stacked(
        W, V, P, frac=0.5, interpret=False),
        one_chip, W_GATE, V_GATE, P_GATE)


def test_gram_cross_compiles(one_chip):
    flat = D * D                     # one wq layer's residual, flattened
    _compile(lambda a, b: mg.maecho_gram_cross(a, b, interpret=False),
             one_chip, ((N, flat), F32), ((N, flat), F32))


def test_decode_fine_grid_compiles(one_chip):
    B, W = 4, 256                    # serving window of two blocks
    bf = jnp.bfloat16
    _compile(lambda q, k, v, m: da.decode_attention(
        q, k, v, m, bw=128, interpret=False, fold_batch=False),
        one_chip, ((B, 1, HQ, HD), bf), ((B, W, HKV, HD), bf),
        ((B, W, HKV, HD), bf), ((B, W), jnp.bool_))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_flash_attention_compiles(one_chip, grad):
    B, S = 4, 128
    bf = jnp.bfloat16

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, bq=128, bk=128,
                                  interpret=False)

    # value_and_grad, as a train step returns its loss: with grad alone
    # the forward's output is dead and XLA drops the kernel
    fn = fwd if not grad else jax.value_and_grad(
        lambda q, k, v: fwd(q, k, v).astype(F32).sum(), argnums=(0, 1, 2))
    _compile(fn, one_chip, ((B, S, HQ, HD), bf), ((B, S, HKV, HD), bf),
             ((B, S, HKV, HD), bf))
