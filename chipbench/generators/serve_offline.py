"""Traffic kind ``serve_offline``: offline batch serving of a held-out
prompt set through ``repro.launch.serve.run_arrival``.

Each call serves ``requests_per_call`` requests of ``prompt_len`` seeded
token ids with a budget of ``gen`` tokens and no end token, over
``slots`` decode slots, every request queued at the start
(``arrival_every`` 0).  Calls repeat until the window is spent; call c
draws its prompts from (seed, c), so every seed serves the same sizes.

Correctness: ``check_requests`` requests drawn from the seed among those
the window finished.  The plain float32 forward pass of
``chipbench/reference/qwen2.py`` runs over each prompt and its served
tokens, and ``served_logit_gap`` is the widest gap by which a served
token's reference logit lies below the reference's best at that
position.  Greedy tokens only: the traffic samples none.
"""
from __future__ import annotations

import numpy as np

from chipbench import harness

SPAN = "chipbench.run_arrival"


def _prompts(r, c: int):
    tr = r.traffic
    rng = harness.host_rng(r.seed, 7, c + 1)
    return rng.integers(0, r.conf["vocab_size"],
                        size=(tr["requests_per_call"], tr["prompt_len"]),
                        dtype=np.int32)


def setup(r) -> dict:
    from repro.models.zoo import get_model

    cfg = harness.program_config(r.conf)
    st = dict(cfg=cfg, model=get_model(cfg),
              params=harness.make_params(r.conf, r.seed), outputs=[])
    # warm-up: one request of the window's sizes compiles (or loads)
    # the batch-1 prefill, the slot insert and the serve step
    _serve(r, st, _prompts(r, -1)[:1])
    st["outputs"] = []
    return st


def _serve(r, st, prompts):
    from repro.launch import serve

    tr = r.traffic
    outs, _ = serve.run_arrival(st["cfg"], st["model"], st["params"],
                                prompts, tr["gen"],
                                slots=tr["slots"],
                                arrival_every=tr["arrival_every"],
                                eos_id=None)
    st["outputs"].append(outs)
    return outs


def call(r, st, i: int) -> None:
    _serve(r, st, _prompts(r, i))


def release(r, st) -> None:
    pass


def end_to_end(r, st, elapsed: float) -> dict:
    """``serve_tok_s``: every token the window's calls generated over
    their elapsed time."""
    tokens = sum(len(o) for outs in st["outputs"] for o in outs)
    return {"serve_tok_s": {"value": tokens / elapsed, "unit": "tokens/s"}}


def attempts(r, st) -> tuple:
    """(requests the window served, those that came back short)."""
    gen = r.traffic["gen"]
    outs = [o for call in st["outputs"] for o in call]
    return len(outs), sum(len(o) != gen for o in outs)


CONTROLS = ("fp8",)


def check(r, st, control: str = "") -> dict:
    """Served tokens against the reference's logits.  ``control``
    ("fp8") puts the reference in float8 in the program's place: its own
    first choice at each position is scored instead of the served
    token."""
    import jax.numpy as jnp

    from chipbench.reference import qwen2 as ref

    tr = r.traffic
    P, gen = tr["prompt_len"], tr["gen"]
    done = [(c, q) for c, outs in enumerate(st["outputs"])
            for q, o in enumerate(outs) if len(o) == gen]
    short = sum(len(outs) for outs in st["outputs"]) - len(done)
    if not done:
        return {"served_logit_gap": float("inf"), "short_requests": short}
    rng = harness.host_rng(r.seed, 11)
    pick = rng.choice(len(done), size=min(tr["check_requests"], len(done)),
                      replace=False)
    worst = 0.0
    for j in sorted(pick):
        c, q = done[j]
        served = np.asarray(st["outputs"][c][q], np.int32)
        seq = np.concatenate([_prompts(r, c)[q], served[:-1]])
        want = ref.logits(st["params"], seq, r.conf, first=P - 1)
        best = jnp.max(want, axis=-1)
        if control:
            low = ref.logits(st["params"], seq, r.conf, first=P - 1,
                             quant=control)
            pick_tok = jnp.argmax(low, axis=-1)
        else:
            pick_tok = jnp.asarray(served)
        chosen = jnp.take_along_axis(want, pick_tok[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(best - chosen)))
    return {"served_logit_gap": worst, "short_requests": short}


def context(r, st) -> dict:
    from chipbench import work

    tr = r.traffic
    P, gen = tr["prompt_len"], tr["gen"]
    return {"requests_per_call": tr["requests_per_call"],
            "flops_per_request": work.request_flops(r.conf, P, gen),
            "decode_attention_per_request": work.decode_attention_request(
                r.conf, P, gen),
            "tokens_per_call": tr["requests_per_call"] * gen}
