#!/usr/bin/env python3
"""Record the small chip traces the trace-reduction tests read.

    python3 chipbench/testdata/record.py <out_dir>

Runs both cells on the chip at a tiny size (the sizes of
``chipbench/tests/test_faults.py``, with one outer iteration of two QP
steps, or four generated tokens, so that the trace stays small) with the
first call of the window traced, and writes each trace (a serialized
XSpace, as in a ``.xplane.pb``) gzipped to ``<out_dir>/<cell>.xplane.pb.gz`` with the run's result line
beside it in ``<cell>.json``.
"""
from __future__ import annotations

import gzip
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench import run as R
    from chipbench.tests.test_faults import tiny_run

    R.use_compile_cache()
    spec = harness.benchmark_spec(ROOT)
    for cell in (c["name"] for c in spec["workloads"] if c["chips"] == 1):
        r = tiny_run(cell)
        if r.traffic["kind"] == "aggregate":
            r.traffic["maecho"] = dict(r.traffic["maecho"], tau=1, qp_iters=2)
        else:
            r.traffic = dict(r.traffic, gen=4, prompt_len=16)
        r.trace, r.seconds = True, 0.5
        result = R.execute(r, spec)
        with gzip.open(out / f"{cell}.xplane.pb.gz", "wb") as g:
            g.write(r.xspace)
        (out / f"{cell}.json").write_text(json.dumps(result, indent=1))
        print(cell, len(r.xspace), json.dumps(result["device"]))


if __name__ == "__main__":
    main()
