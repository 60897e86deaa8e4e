"""The readers of the program's spans and counters, on made-up records:
each keeps the records of the traced call alone, and reads nothing from
an empty window or from a program that records no spans."""
from __future__ import annotations

import collections
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness  # noqa: E402
from repro.utils import spans  # noqa: E402

S = 10 ** 9
MS = 10 ** 6
# the warm-up before the window, the traced call in [10 s, 20 s], and an
# untraced call after it
CALLS = [(10.0, 20.0, True), (20.0, 30.0, False)]


def _rec(i, name, start, end, parent=None, **attrs):
    return spans.Record(i, parent, name, start, end, attrs)


def _count(i, name, t, parent=None, n=1, **attrs):
    return spans.Record(i, parent, name, t, t, dict(attrs, n=n))


RECORDS = [
    # warm-up
    _count(1, "serve.trace", 5 * S, fn="prefill1"),
    _rec(2, "serve.admit", 5 * S, 6 * S, rid=0, slot=0),
    # the traced call
    _rec(3, "maecho.place", 10 * S + 1, 10 * S + 500 * MS, parent=5),
    _rec(4, "maecho.execute", 10 * S + 500 * MS, 10 * S + 510 * MS,
         parent=5),
    _rec(5, "maecho.aggregate", 10 * S, 19 * S),
    _count(6, "serve.trace", 11 * S, fn="prefill1"),
    _count(7, "serve.trace", 11 * S + 1, fn="insert"),
    _rec(8, "serve.admit", 11 * S, 11 * S + 100 * MS, rid=0, slot=0),
    _rec(9, "serve.admit", 11 * S + 100 * MS, 11 * S + 150 * MS, rid=1,
         slot=1),
    _count(10, "serve.trace", 12 * S, fn="serve_step", n=2),
    _rec(11, "serve.sync", 12 * S + 10 * MS, 12 * S + 50 * MS, parent=12),
    _rec(12, "serve.step", 12 * S, 12 * S + 100 * MS, step=0, live=2),
    _rec(13, "serve.step", 13 * S, 13 * S + 50 * MS, step=1, live=1),
    # the untraced call
    _rec(14, "maecho.place", 21 * S, 22 * S),
    _rec(15, "serve.admit", 21 * S, 22 * S, rid=0, slot=0),
    _rec(16, "serve.sync", 23 * S, 23 * S + 10 * MS, parent=17),
    _rec(17, "serve.step", 23 * S, 24 * S, step=0, live=1),
    _count(18, "serve.trace", 25 * S, fn="serve_step"),
]

WANT = {"place_ms.agg": 499.999999,        # the traced call's span alone
        "admit_ms.serve": 150.0,
        "step_host_ms.serve": 110.0,       # 100 + 50 less the 40 of sync
        "retraces.serve": 4}


def _reader(name):
    return harness.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py",
                               f"chipbench_metric_{name.replace('.', '_')}")


@pytest.fixture
def ring(monkeypatch):
    def fill(records):
        monkeypatch.setattr(spans, "_ring", collections.deque(
            records, maxlen=spans.RING))
    return fill


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_keeps_the_traced_call_alone(name, ring):
    ring(RECORDS)
    assert _reader(name).read({"calls": CALLS}) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_an_empty_window(name, ring):
    ring([r for r in RECORDS if not 10 * S <= r.start_ns <= 20 * S])
    assert _reader(name).read({"calls": CALLS}) is None


def test_retraces_read_zero_where_the_call_traced_nothing(ring):
    ring([r for r in RECORDS if r.name != "serve.trace"])
    assert _reader("retraces.serve").read({"calls": CALLS}) == 0


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_program_without_spans(name,
                                                         monkeypatch):
    import repro.utils

    monkeypatch.delattr(repro.utils, "spans")
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    assert _reader(name).read({"calls": CALLS}) is None
