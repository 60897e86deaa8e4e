"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on small traces recorded on a TPU v5e
(``chipbench/testdata/record.py``)."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace as T  # noqa: E402

DATA = ROOT / "chipbench" / "testdata"


def test_union_merges_overlaps():
    assert T._union([(0, 10), (5, 15), (20, 30)]) == 25
    assert T._union([]) == 0


def test_gaps_fill_the_window():
    assert T._gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8),
                                                         (9, 10)]


def test_innermost_drops_enclosing_events():
    evs = [(0, 100, "while", ""), (10, 20, "a", ""), (40, 30, "b", ""),
           (200, 5, "c", "")]
    assert [e[2] for e in T._innermost(evs)] == ["a", "b", "c"]


def test_gaps_take_the_shortest_covering_host_event():
    host = [(0, 1000, "chipbench.call"), (100, 50, "PjitFunction(step)"),
            (400, 300, "host loop")]
    got = T._label_gaps([(110, 130), (500, 520), (900, 950)], host)
    assert got == [("PjitFunction(step)", 20), ("host loop", 20),
                   ("chipbench.call", 50)]


RECORDED = sorted(DATA.glob("*.xplane.pb.gz"))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[p.name.split(".")[0] for p in RECORDED])
def test_recorded_trace_reduces(path):
    """A trace recorded on the chip reduces to the numbers recorded with
    it: the window is the harness's span, busy time lies inside it, and
    device operations and kernels are found."""
    cell = path.name[: -len(".xplane.pb.gz")]
    want = json.loads((DATA / f"{cell}.json").read_text())
    span = ("chipbench.aggregate_llm" if cell.startswith("agg")
            else "chipbench.run_arrival")
    tr = T.reduce_trace(gzip.decompress(path.read_bytes()), span)
    assert tr is not None
    assert tr.devices and all(d.name.startswith("/device:TPU:")
                              for d in tr.devices)
    assert 0 < tr.busy_s() <= tr.window_ns / 1e9
    assert tr.busy_s() == pytest.approx(want["device"]["busy_s"], rel=1e-9)
    assert tr.window_ns / 1e9 == pytest.approx(want["device"]["window_s"],
                                               rel=1e-9)
    assert tr.op_ns(T.is_kernel) > 0
    b = T.breakdown(tr)
    assert b == want["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
