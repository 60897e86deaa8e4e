"""The correctness check fails what it has to fail.

Each test drives a whole run of a cell (set-up, window, check) on the
CPU at a tiny size, past the harness's look for a chip, with the timed
path broken underneath, and sees ``correct`` come out false; a sound run
comes out true, and no control does.  The limits are the cells' own
(``chipbench/workloads/<cell>.json``), but for the serving control: at
this size the fp8 reference's first choices lie only 0.07 below the best
logit (the full-size model's 24 layers and 151936-token vocabulary put
them 1.0-1.4 below), so that test holds it to a limit that lies between
this size's sound and control readings as the cell's lies between the
full-size ones.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402
from chipbench import run as R  # noqa: E402

AGG = "agg-qwen2-0.5b-silo2"
SERVE = "serve-qwen2-0.5b-offline"
TINY = dict(hidden_size=256, intermediate_size=384, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
# at TINY, on the CPU: sound runs read served_logit_gap ~0.001, the fp8
# control ~0.07
TINY_SERVE_LIMIT = 0.01


def tiny_run(name: str, seed: int = 2**31 + 7) -> R.Run:
    r = R.load_run(name, seed, 0.05, False)
    r.conf = dict(r.conf, **TINY)
    if r.traffic["kind"] == "aggregate":
        r.traffic = dict(r.traffic, probe_rows=64, probe_tokens=64,
                         maecho=dict(r.traffic["maecho"], tau=3, qp_iters=40))
    else:
        r.traffic = dict(r.traffic, slots=2, requests_per_call=3,
                         prompt_len=24, gen=8, check_requests=3)
    return r


def execute(r: R.Run) -> dict:
    return R.execute(r, harness.benchmark_spec(ROOT), require_tpu=False)


def control(r: R.Run) -> dict:
    """The run's check with the reference in a lower precision put in
    the program's place."""
    generator = harness.load_module(R.BENCH / "generators" /
                                 f"{r.traffic['kind']}.py", "ctl_generator")
    st = generator.setup(r)
    R.run_window(r, generator, st)
    generator.release(r, st)
    ok, checks = R.judge(r, generator.check(r, st, control=generator.CONTROLS[-1]))
    return {"correct": ok, "checks": checks}


@pytest.mark.parametrize("name", [AGG, SERVE])
def test_sound_run_is_correct(name):
    out = execute(tiny_run(name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", [AGG, SERVE])
def test_control_is_not_correct(name):
    r = tiny_run(name)
    if name == SERVE:
        r.limits = dict(r.limits, served_logit_gap=TINY_SERVE_LIMIT)
    out = control(r)
    assert not out["correct"], out["checks"]


def test_sound_serving_is_within_the_tiny_limit():
    r = tiny_run(SERVE)
    r.limits = dict(r.limits, served_logit_gap=TINY_SERVE_LIMIT)
    out = execute(r)
    assert out["correct"], out["checks"]


def _unchanged(monkeypatch):
    from repro.core import maecho

    monkeypatch.setattr(maecho, "_maecho_jit",
                        lambda W0, V0, *a, **k: (W0, V0))


def _half_clients(monkeypatch):
    from repro.fl import llm_adapter

    real = llm_adapter.maecho_aggregate

    def half(clients, projs, *a, **k):
        n = max(1, len(clients) // 2)
        return real(clients[:n], projs[:n], *a, **k)

    monkeypatch.setattr(llm_adapter, "maecho_aggregate", half)


def _altered_leaf(monkeypatch):
    from repro.fl import llm_adapter

    real = llm_adapter.aggregate_llm

    def altered(*a, **k):
        out = real(*a, **k)
        wq = out["layers"]["wq"]
        out["layers"]["wq"] = wq.at[0, 0].add(0.05)
        return out

    monkeypatch.setattr(llm_adapter, "aggregate_llm", altered)


@pytest.mark.parametrize("fault", [_unchanged, _half_clients, _altered_leaf])
def test_aggregate_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = execute(tiny_run(AGG))
    assert not out["correct"], out["checks"]


def _altered_token(monkeypatch):
    from repro.launch import serve

    real = serve.run_arrival

    def altered(cfg, *a, **k):
        outs, stats = real(cfg, *a, **k)
        for o in outs:
            o[3] = (o[3] + 1 + int(np.argmax(o))) % cfg.vocab
        return outs, stats

    monkeypatch.setattr(serve, "run_arrival", altered)


def _half_requests(monkeypatch):
    from repro.launch import serve

    real = serve.run_arrival

    def half(cfg, model, params, prompts, *a, **k):
        n = max(1, prompts.shape[0] // 2)
        outs, stats = real(cfg, model, params, prompts[:n], *a, **k)
        return outs + [[] for _ in range(prompts.shape[0] - n)], stats

    monkeypatch.setattr(serve, "run_arrival", half)


def _stuck_step(monkeypatch):
    from repro.launch import serve

    real = serve.run_arrival

    def stuck(*a, **k):
        outs, stats = real(*a, **k)
        return [[o[0]] * len(o) for o in outs], stats

    monkeypatch.setattr(serve, "run_arrival", stuck)


@pytest.mark.parametrize("fault", [_altered_token, _half_requests,
                                   _stuck_step])
def test_serve_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = execute(tiny_run(SERVE))
    assert not out["correct"], out["checks"]
