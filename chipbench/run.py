#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names its configuration (``chipbench/configs/<config>.json``) and its
traffic mix (``chipbench/traffic/<traffic>.json``); the mix's ``kind``
names the generator that serves it (``chipbench/generators/<kind>.py``);
``chipbench/workloads/<cell>.json`` holds the limits of the cell's
correctness check; each per-layer metric is read by
``chipbench/metrics/<metric>.py``.

A run makes its inputs from ``--seed``, warms up every shape (set-up),
then calls the program back to back for ``--seconds`` and finishes the
call in flight.  With ``--trace 0`` it reports the cell's end-to-end
metrics; with ``--trace 1`` the first call of the window runs under the
profiler and the run reports the per-layer metrics instead.  Then it
reads the device's peak memory, frees the program's state and checks
what the window produced against the plain reference.  The last line of
standard output is one JSON object; the numbers compared, each beside
its limit, are also the last lines of standard error.

It exits non-zero, and prints no result, where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind missing from
``chipbench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GIB = 2 ** 30


@dataclass
class Run:
    cell: dict
    conf: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    xspace: bytes = b""                         # the traced call's profile
    calls: list = field(default_factory=list)    # (start, end, traced)
    cell_metrics: list = field(default_factory=list)
    stop_trace_s: float = 0.0


def _fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def load_run(name: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT) -> Run:
    from chipbench import harness

    spec = harness.benchmark_spec(root)
    cell = harness.find_cell(spec, name)
    entry = harness.config_entry(spec, cell["config"])
    conf = harness.load_json(root / entry["file"])
    bench = root / BENCH.name
    traffic = harness.load_json(bench / "traffic" / f"{cell['traffic']}.json")
    limits = harness.load_json(bench / "workloads" / f"{name}.json")["limits"]
    return Run(cell, conf, traffic, limits, seed, seconds, trace)


def check_device(chips: int) -> dict:
    """The peaks of the chip this runs on; exits where it is no TPU."""
    import jax

    from chipbench import work

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        _fail(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        return work.peaks(devs[0].device_kind)
    except KeyError as e:
        _fail(str(e))


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, where the program puts it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
    holding every program however fast it compiled."""
    import jax

    from repro.utils.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCount:
    """Counts, while open, the programs JAX hands to the backend and
    those of them it found in the persistent cache (JAX's own events: a
    cache hit also records a backend-compile duration)."""

    def __enter__(self):
        import jax.monitoring

        self.programs = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.programs += 1

    def _event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


def run_window(r: Run, generator, st) -> None:
    """Calls the program back to back until ``r.seconds`` have passed
    and the call in flight has finished.  With ``r.trace``, the first
    call runs under the profiler."""
    import jax
    from jax._src.lib import _profiler

    t_end = None
    i = 0
    while True:
        traced = r.trace and i == 0
        if traced:
            # a profiler session kept in memory: nothing is written to disk
            # and nothing converted for a viewer
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            session = _profiler.ProfilerSession(opts)
        t0 = time.perf_counter()
        if t_end is None:
            t_end = t0 + r.seconds
        with jax.profiler.TraceAnnotation(generator.SPAN):
            generator.call(r, st, i)
        t1 = time.perf_counter()
        if traced:
            r.xspace = session.stop()
            r.stop_trace_s = time.perf_counter() - t1
        r.calls.append((t0, t1, traced))
        i += 1
        if t1 >= t_end:
            return


def end_to_end(r: Run, generator, st, device: dict, setup_s: float) -> dict:
    """The end-to-end metrics that ``BENCHMARK.json`` lists for this cell."""
    elapsed = r.calls[-1][1] - r.calls[0][0]
    out = {"setup_s": {"value": setup_s, "unit": "s"},
           "hbm_peak_gib": {"value": device["memory_peak_bytes"] / GIB,
                            "unit": "GiB"}}
    out.update(generator.end_to_end(r, st, elapsed))
    return {name: out[name] for name in r.cell_metrics}


def per_layer(r: Run, generator, st, spec: dict, peaks: dict, tr) -> dict:
    """Every per-layer metric of this cell that its reader finds."""
    from chipbench import harness

    moves = set(r.cell_metrics)
    ctx = dict(generator.context(r, st), trace=tr, peaks=peaks,
               chips=r.cell["chips"], calls=r.calls, traffic=r.traffic)
    out = {}
    for m in spec["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and r.cell["name"] not in cells:
            continue
        if cells is None and m["moves"] not in moves:
            continue
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     f"chipbench_metric_{len(out)}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(r: Run, got: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct where every one
    is within it."""
    checks, ok = {}, True
    for name, limit in r.limits.items():
        value = got[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, checks


def execute(r: Run, spec: dict, require_tpu: bool = True) -> dict:
    """One run of the cell: set-up, window, device readings, the
    check, and the metrics.  Returns the result line's object."""
    from chipbench import harness

    peaks = (check_device(r.cell["chips"]) if require_tpu
             else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    generator = harness.load_module(
        BENCH / "generators" / f"{r.traffic['kind']}.py",
        f"chipbench_generator_{r.traffic['kind']}")
    r.cell_metrics = [m["name"] for m in spec["end_to_end"]
                      if r.cell["name"] in m.get("workloads",
                                                 [r.cell["name"]])]
    st = generator.setup(r)
    with CompileCount() as compiles:
        run_window(r, generator, st)
    setup_s = r.calls[0][0] - T_START
    loaded = compiles.cache_hits
    compiled = compiles.programs - loaded
    device = harness.device_info(r.cell["chips"])
    generator.release(r, st)
    t_trace = time.perf_counter()
    tr = None
    if r.trace:
        from chipbench import trace as trace_mod

        tr = trace_mod.reduce_trace(r.xspace, generator.SPAN)
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_ns / 1e9
    t_check = time.perf_counter()
    got = generator.check(r, st)
    ok, checks = judge(r, got)
    if r.trace:
        metrics = per_layer(r, generator, st, spec, peaks, tr)
    else:
        metrics = end_to_end(r, generator, st, device, setup_s)
    window = [round(c[1] - c[0], 3) for c in r.calls]
    print(f"chipbench: set-up {setup_s:.1f} s; {len(r.calls)} calls in the "
          f"window {window} s, in which {compiled} programs compiled and "
          f"{loaded} were loaded from the cache; trace stop "
          f"{r.stop_trace_s:.1f} s, reduction {t_check - t_trace:.1f} s; check "
          f"{time.perf_counter() - t_check:.1f} s; {json.dumps(got)}",
          file=sys.stderr, flush=True)
    attempted, failed = generator.attempts(r, st)
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = trace_mod.breakdown(tr)
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the program (src/repro) is not in {ROOT}")
    from chipbench import harness

    spec = harness.benchmark_spec(ROOT)
    r = load_run(args.workload, args.seed, args.seconds, bool(args.trace))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    use_compile_cache()
    result = execute(r, spec)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
