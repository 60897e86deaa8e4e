"""Shared pieces of the benchmark: file lookup by name, the seeded key,
the model configuration handed to the program, and the weights,
client checkpoints and projectors the benchmark makes from the seed.

Everything here is the benchmark's own code.  From the program it takes
only ``repro.models.config.ModelConfig`` (the system under test's
configuration type); the weights are made here, so that the plain
references under ``chipbench/reference`` can share them without taking
anything the program made.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in spec["workloads"])
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"workload names configuration {name!r}, which "
                     f"BENCHMARK.json does not list")


def load_module(path: Path, name: str):
    """Import a file by path (metric and generator files carry dots and
    dashes in their names, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flatten(tree, prefix: str = ""):
    """(path, leaf) pairs of a nested dict, paths dotted: "layers.wq"."""
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from flatten(v, path + ".")
        else:
            yield path, v


def seed_key(seed: int, *salt: int):
    """A PRNG key from any whole number up to 64 bits, folded with
    ``salt`` so that each use of the seed draws its own stream."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def host_rng(seed: int, *salt: int):
    import numpy as np

    return np.random.default_rng([int(seed) & (2**63 - 1), *salt])


# --------------------------------------------------------------------------
# the configuration as the program takes it
# --------------------------------------------------------------------------
def program_config(conf: dict):
    """``ModelConfig`` for a Qwen2-style dense decoder from the config
    file's published keys."""
    from repro.models.config import ModelConfig

    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        head_dim=d // heads, qkv_bias=True, rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        param_dtype=conf["torch_dtype"],
        compute_dtype=conf["compute_dtype"], source=conf["source"])


def dims(conf: dict) -> dict:
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return dict(L=conf["num_hidden_layers"], d=d, f=conf["intermediate_size"],
                V=conf["vocab_size"], Hq=heads,
                Hkv=conf["num_key_value_heads"], hd=d // heads)


# --------------------------------------------------------------------------
# weights, clients, projectors — each one jitted call on the device
# --------------------------------------------------------------------------
def _params(key, conf: dict, dtype):
    import jax
    import jax.numpy as jnp

    m = dims(conf)
    L, d, f, V, hd = m["L"], m["d"], m["f"], m["V"], m["hd"]
    q, kv = m["Hq"] * hd, m["Hkv"] * hd
    ks = iter(jax.random.split(key, 16))

    def lin(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / jnp.sqrt(jnp.float32(shape[-2]))).astype(dtype)

    def gauss(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(ks), shape,
                                               jnp.float32)).astype(dtype)

    p = {
        "embed": gauss((V, d), 0.02),
        "layers": {
            "ln1": gauss((L, d), 0.1, 1.0), "ln2": gauss((L, d), 0.1, 1.0),
            "wq": lin((L, d, q)), "wk": lin((L, d, kv)),
            "wv": lin((L, d, kv)), "wo": lin((L, q, d)),
            "bq": gauss((L, q), 0.1), "bk": gauss((L, kv), 0.1),
            "bv": gauss((L, kv), 0.1),
            "w_gate": lin((L, d, f)), "w_up": lin((L, d, f)),
            "w_down": lin((L, f, d)),
        },
        "ln_f": gauss((d,), 0.1, 1.0),
    }
    if not conf["tie_word_embeddings"]:
        p["lm_head"] = lin((d, V))
    return p


def make_params(conf: dict, seed: int, device=None):
    """The model's weights from the seed, in the type they are served
    in, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(conf["torch_dtype"])
    fn = jax.jit(lambda k: _params(k, conf, dtype), device=device)
    return fn(seed_key(seed, 1))


def make_client(conf: dict, seed: int, client: int, std: float):
    """Client ``client``'s checkpoint: the seeded base weights plus a
    seeded N(0, std²) perturbation on every leaf, in one jitted call."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(conf["torch_dtype"])

    def fn(kb, kc):
        base = _params(kb, conf, dtype)
        leaves, tdef = jax.tree.flatten(base)
        keys = jax.random.split(kc, len(leaves))
        return jax.tree.unflatten(tdef, [
            (x.astype(jnp.float32) + std * jax.random.normal(
                k, x.shape, jnp.float32)).astype(dtype)
            for x, k in zip(leaves, keys)])

    return jax.jit(fn)(seed_key(seed, 1), seed_key(seed, 2, client))


def make_projectors(conf: dict, seed: int, client: int, rows: int,
                    tokens: int, ridge: float):
    """Client ``client``'s MA-Echo projectors, the kinds the LLM path
    uses: full (d, d) row-space projectors P = Xᵀ(XXᵀ + zI)⁻¹X for the
    q/k/v and gate/up inputs of every layer, from ``rows`` seeded,
    row-normalised feature vectors; the token-support diagonal for the
    embedding, from ``tokens`` seeded token ids; scalar 1 everywhere
    else.  One jitted call."""
    import jax
    import jax.numpy as jnp

    m = dims(conf)
    L, d, V = m["L"], m["d"], m["V"]
    hi = jax.lax.Precision.HIGHEST

    def full_p(k):
        X = jax.random.normal(k, (rows, d), jnp.float32)
        X = X / jnp.linalg.norm(X, axis=-1, keepdims=True)
        G = jnp.matmul(X, X.T, precision=hi) + ridge * jnp.eye(rows)
        cf = jax.scipy.linalg.cho_factor(G)
        return jnp.matmul(X.T, jax.scipy.linalg.cho_solve(cf, X),
                          precision=hi)

    def fn(key):
        kq, km, kt = jax.random.split(key, 3)
        p_qkv = jax.vmap(full_p)(jax.random.split(kq, L))
        p_mlp = jax.vmap(full_p)(jax.random.split(km, L))
        ids = jax.random.randint(kt, (tokens,), 0, V)
        support = jnp.zeros((V,), jnp.float32).at[ids].set(1.0)
        one = jnp.ones((L,), jnp.float32)
        p = {
            "embed": support,
            "layers": {"ln1": one, "ln2": one, "wq": p_qkv, "wk": p_qkv,
                       "wv": p_qkv, "wo": one, "bq": one, "bk": one,
                       "bv": one, "w_gate": p_mlp, "w_up": p_mlp,
                       "w_down": one},
            "ln_f": jnp.ones((), jnp.float32),
        }
        if not conf["tie_word_embeddings"]:
            p["lm_head"] = jnp.ones((), jnp.float32)
        return p

    return jax.jit(fn)(seed_key(seed, 3, client))


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------
def device_info(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:n_chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}
