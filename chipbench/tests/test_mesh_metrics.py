"""The readers of the mesh aggregate's per-layer metrics: the plan's
routes and the placement's bytes per device (program counters), and the
all-reduce time on the fullest chip (device trace).  Each keeps the
traced call alone, and reads nothing where the program or the trace
holds nothing."""
from __future__ import annotations

import collections
import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness  # noqa: E402
from chipbench import trace as T  # noqa: E402
from repro.utils import spans  # noqa: E402

S = 10 ** 9
MS = 10 ** 6
GIB = 2 ** 30
# the warm-up before the window, the traced call in [10 s, 20 s], and an
# untraced call after it
CALLS = [(10.0, 20.0, True), (20.0, 30.0, False)]


def _count(i, name, t, parent=None, n=1, **attrs):
    return spans.Record(i, parent, name, t, t, dict(attrs, n=n))


RECORDS = [
    # warm-up
    _count(1, "maecho.place_bytes", 5 * S, n=8 * GIB, device=0),
    _count(2, "maecho.route", 5 * S, n=14, route="sharded2d"),
    # the traced call: placement onto two devices, then the plan's routes
    spans.Record(3, 5, "maecho.place", 10 * S + 1, 10 * S + 500 * MS, {}),
    _count(4, "maecho.place_bytes", 10 * S + 2, parent=3, n=3 * GIB,
           device=0),
    _count(5, "maecho.place_bytes", 10 * S + 3, parent=3, n=GIB // 2,
           device=1),
    _count(6, "maecho.place_bytes", 10 * S + 4, parent=3, n=GIB // 2,
           device=1),
    _count(7, "maecho.route", 10 * S + 500 * MS, n=6, route="oracle"),
    _count(8, "maecho.route", 10 * S + 500 * MS, n=1, route="sharded"),
    _count(9, "maecho.route", 10 * S + 500 * MS, n=7, route="sharded2d"),
    # the untraced call
    _count(10, "maecho.place_bytes", 21 * S, n=9 * GIB, device=0),
    _count(11, "maecho.route", 22 * S, n=14, route="sharded2d"),
]

WANT = {"mesh_leaves.agg": 8,              # sharded + sharded2d
        "place_gib_max.agg": 3.0}          # device 0's, not the sum


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


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_program_without_spans(name,
                                                         monkeypatch):
    import repro.utils

    monkeypatch.delattr(repro.utils, "spans")
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    assert _reader(name).read({"calls": CALLS}) is None


def test_mesh_leaves_read_zero_where_every_leaf_ran_on_one_chip(ring):
    ring([r for r in RECORDS
          if r.attrs.get("route") not in ("sharded", "sharded2d")])
    assert _reader("mesh_leaves.agg").read({"calls": CALLS}) == 0


def test_psum_reads_the_fullest_chips_all_reduces():
    """All-reduce device time on the fullest chip, in ms; a kernel or a
    gather is not an all-reduce."""
    gram = "%all-reduce.7 = (f32[12,2,2]{2,1,0}, f32[2,2]{1,0}) all-reduce("
    done = "%all-reduce-done.2 = f32[2,2]{1,0} all-reduce-done(%start)"
    kernel = ("%maecho_gram_stacked.4 = f32[12,2,2]{2,1,0} custom-call(), "
              "custom_call_target=\"tpu_custom_call\"")
    gather = "%all-gather.1 = f32[2,768]{1,0} all-gather(%p)"
    devs = [T.Device("/device:TPU:0", ops=[T.Op(gram, 0, 2 * MS),
                                           T.Op(kernel, 0, 50 * MS)]),
            T.Device("/device:TPU:1", ops=[T.Op(gram, 0, 3 * MS),
                                           T.Op(done, 0, MS // 2),
                                           T.Op(gather, 0, 9 * MS)])]
    tr = T.Trace((0, S), devs, [])
    assert _reader("psum_ms.agg").read({"trace": tr}) == pytest.approx(3.5)
    devs[0].ops = devs[0].ops[1:]
    devs[1].ops = devs[1].ops[2:]
    assert _reader("psum_ms.agg").read({"trace": tr}) is None


def test_psum_finds_nothing_on_one_chip():
    """The one-chip aggregate recorded on a v5e reduces no array across
    chips: the reader stays silent there."""
    path = ROOT / "chipbench" / "testdata" / "agg-qwen2-0.5b-silo2.xplane.pb.gz"
    tr = T.reduce_trace(gzip.decompress(path.read_bytes()),
                        "chipbench.aggregate_llm")
    assert tr is not None and tr.op_ns(T.is_maecho_kernel) > 0
    assert _reader("psum_ms.agg").read({"calls": CALLS, "trace": tr}) is None
