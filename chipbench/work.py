"""Work the algorithms require, counted from shapes, and the table of
peaks.

Nothing here reads what the compiled program does: a kernel that is
rewritten changes the time and never the count.

MA-Echo (Algorithm 1, arXiv:2204.12493), per layer of a leaf whose
weight is (in, out), per outer iteration, with N clients:

- Eq. 6/7 residual R_i = P_i (W - V_i): N products (full P: 2·in²·out
  FLOPs each; diagonal or scalar P: in·out);
- Eq. 6 Gram G = R Rᵀ: 2·N²·in·out;
- Eq. 7 update W += -2η Σ a_i R_i: 2·N·in·out;
- Eq. 11 anchors V_i += (W - V_i) - μ/(1+μ) P_i (W - V_i): N more
  products, like the residual;
- elementwise: two differences W - V_i and three operations of the
  anchor update per client, two of the W update: (5N + 2)·in·out.

Bytes: the QP sits between the Gram and the update, so each iteration
reads W, every V_i and every P_i twice (once for the Gram, once for the
update) and writes W and every V_i once, in float32.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4
BF16 = 2


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``.  A device missing from
    the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def maecho_leaf(inp: int, out: int, kind: str, n: int, layers: int = 1,
                tau: int = 1) -> dict:
    """FLOPs and bytes MA-Echo requires for one leaf: ``layers`` layers
    of an (in, out) weight (``out`` 1 for a vector leaf), projector
    ``kind`` "full", "diag" or "scalar", ``tau`` outer iterations."""
    io = inp * out
    prod = 2 * inp * io if kind == "full" else io
    flops = 2 * n * prod + 2 * n * n * io + 2 * n * io + (5 * n + 2) * io
    p_elems = {"full": inp * inp, "diag": inp, "scalar": 1}[kind]
    nbytes = F32 * (2 * (1 + n) * io + 2 * n * p_elems + (1 + n) * io)
    return {"flops": flops * layers * tau, "bytes": nbytes * layers * tau}


def maecho_qwen2_leaves(conf: dict) -> dict:
    """Shape and projector kind of every leaf of a Qwen2 checkpoint, as
    the LLM path aggregates it: full projectors on the q/k/v and
    gate/up inputs, the token-support diagonal on the embedding, scalar
    elsewhere.  Returns {path: (in, out, kind, layers)}."""
    L, d, f = (conf["num_hidden_layers"], conf["hidden_size"],
               conf["intermediate_size"])
    hd = d // conf["num_attention_heads"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    leaves = {
        "embed": (conf["vocab_size"], d, "diag", 1),
        "ln_f": (d, 1, "scalar", 1),
        "layers.wq": (d, q, "full", L), "layers.wk": (d, kv, "full", L),
        "layers.wv": (d, kv, "full", L), "layers.wo": (q, d, "scalar", L),
        "layers.w_gate": (d, f, "full", L), "layers.w_up": (d, f, "full", L),
        "layers.w_down": (f, d, "scalar", L),
        "layers.bq": (q, 1, "scalar", L), "layers.bk": (kv, 1, "scalar", L),
        "layers.bv": (kv, 1, "scalar", L), "layers.ln1": (d, 1, "scalar", L),
        "layers.ln2": (d, 1, "scalar", L),
    }
    if not conf["tie_word_embeddings"]:
        leaves["lm_head"] = (d, conf["vocab_size"], "scalar", 1)
    return leaves


def maecho_aggregate(conf: dict, n: int, tau: int, paths=None) -> dict:
    """Required FLOPs and bytes of one aggregate, over the leaves in
    ``paths`` (all leaves where None)."""
    tot = {"flops": 0, "bytes": 0}
    for path, (i, o, kind, layers) in maecho_qwen2_leaves(conf).items():
        if paths is None or path in paths:
            w = maecho_leaf(i, o, kind, n, layers, tau)
            tot["flops"] += w["flops"]
            tot["bytes"] += w["bytes"]
    return tot


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def _layer_params(conf: dict) -> int:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hd = d // conf["num_attention_heads"]
    q, kv = conf["num_attention_heads"] * hd, conf["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def forward_flops(conf: dict, context: int, head: bool) -> int:
    """FLOPs of one token through the model, attending to ``context``
    positions (itself included); ``head`` where its logits are formed."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    hd = d // conf["num_attention_heads"]
    attn = 4 * conf["num_attention_heads"] * hd * context
    out = L * (2 * _layer_params(conf) + attn)
    return out + (2 * d * conf["vocab_size"] if head else 0)


def request_flops(conf: dict, prompt: int, gen: int) -> int:
    """FLOPs one request requires: a prefill of ``prompt`` tokens that
    forms the last position's logits, then ``gen - 1`` decode steps."""
    pre = sum(forward_flops(conf, c, c == prompt)
              for c in range(1, prompt + 1))
    dec = sum(forward_flops(conf, p + 1, True)
              for p in range(prompt, prompt + gen - 1))
    return pre + dec


def decode_attention_request(conf: dict, prompt: int, gen: int,
                             kv_bytes: int = BF16) -> dict:
    """Bytes of live K/V and FLOPs that decode attention requires over
    one request's ``gen - 1`` decode steps: the step at position p reads
    the p + 1 cached positions of every layer."""
    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    Hq, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // Hq
    live = sum(p + 1 for p in range(prompt, prompt + gen - 1))
    return {"bytes": L * 2 * Hkv * hd * kv_bytes * live,
            "flops": L * 4 * Hq * hd * live}


def least_seconds(w: dict, peak: dict, chips: int = 1) -> tuple:
    """(seconds, bound): the least time the chips need for ``w``, and
    whether compute or HBM bandwidth bounds it."""
    t_c = w["flops"] / (chips * peak["bf16_flops_per_s"])
    t_m = w["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
