#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: the cell's set-up and a short window (at
least one whole call, at the cell's own sizes), then the cell's check
twice: once on what the program produced (the sound reading) and once
with the reference in a lower precision put in the program's place (the
control).  Prints one JSON line per seed.  The limit of each compared
number lies above the largest sound reading and below the smallest
control reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench import run as R

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    R.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = R.load_run(args.workload, seed, args.seconds, False)
        R.check_device(r.cell["chips"])
        generator = harness.load_module(
            R.BENCH / "generators" / f"{r.traffic['kind']}.py",
            f"chipbench_generator_{r.traffic['kind']}")
        t0 = time.perf_counter()
        st = generator.setup(r)
        R.run_window(r, generator, st)
        generator.release(r, st)
        t1 = time.perf_counter()
        sound = generator.check(r, st)
        t2 = time.perf_counter()
        control = {c: generator.check(r, st, control=c)
                   for c in generator.CONTROLS}
        print(json.dumps({"seed": seed, "sound": sound, "control": control,
                          "calls": len(r.calls), "run_s": t1 - t0,
                          "check_s": t2 - t1}), flush=True)
        del st
        gc.collect()


if __name__ == "__main__":
    main()
