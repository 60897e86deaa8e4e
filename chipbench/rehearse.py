#!/usr/bin/env python3
"""Compile an aggregation cell's MA-Echo executor for a described TPU v5e
(not attached) and print its ``memory_analysis``.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell> \\
        [--layers L] [--qp-batched 0|1] [--chips 1|4]
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config <file.json> \\
        --traffic <file.json> --chips 1|4 [--layers L] [--qp-batched 0|1]

The second form sizes a cell before it exists.

Nothing runs: this sizes the program before any chip time.  The
executor is ``repro.core.maecho._maecho_jit`` over the plan
``compile_plan`` makes for the cell's configuration, traffic and
backend, with every argument described on one chip, or on the (2, 2)
("data", "model") mesh with the client anchors placed by the program's
sharding rules and the projectors replicated (as the mesh cells place
them).  Prints one JSON line with the bytes per device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def rehearse(conf: dict, traffic: dict, chips: int, qp_batched: bool) -> dict:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    import repro.kernels.env as kenv
    from chipbench import harness
    from repro.core import maecho
    from repro.core.plan import compile_plan
    from repro.fl.llm_adapter import stack_levels_fn
    from repro.sharding.rules import make_rules
    from repro.utils import trees

    kenv.interpret_default = lambda: False      # compile for Mosaic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = harness.program_config(conf)
    n = traffic["n_clients"]
    macfg = maecho.MAEchoConfig(**dict(traffic["maecho"],
                                       qp_batched=qp_batched))
    shapes = jax.eval_shape(lambda: harness.make_client(
        conf, 0, 0, traffic["client_delta_std"]))
    pshapes = jax.eval_shape(lambda: harness.make_projectors(
        conf, 0, 0, traffic["probe_rows"], traffic["probe_tokens"],
        traffic["projector_ridge"]))
    if chips == 1:
        mesh, backend = None, traffic["backend"]
        if backend.startswith("sharded"):
            backend = "auto"
        one = SingleDeviceSharding(topo.devices[0])
        w_sh = jax.tree.map(lambda _: one, shapes)
        v_sh = w_sh
        p_sh = jax.tree.map(lambda _: one, pshapes)
    else:
        mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                    ("data", "model"))
        backend = traffic["backend"]
        rules = make_rules(mesh, cfg)
        w_sh = rules.params_shardings(shapes)
        rep = NamedSharding(mesh, PartitionSpec())
        v_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, PartitionSpec(None, *s.spec)), w_sh)
        p_sh = jax.tree.map(lambda _: rep, pshapes)

    def sds(x, sh, lead=()):
        return jax.ShapeDtypeStruct(lead + x.shape, x.dtype, sharding=sh)

    W0 = jax.tree.map(sds, shapes, w_sh)
    V0 = jax.tree.map(lambda x, s: sds(x, s, (n,)), shapes, v_sh)
    P = jax.tree.map(lambda x, s: sds(x, s, (n,)), pshapes, p_sh)
    lv = stack_levels_fn(cfg)
    levels = trees.map_with_path(lambda path, _: lv(path), shapes)
    plan = compile_plan(W0, P, levels, macfg, "io", backend, mesh)
    compiled = maecho._maecho_jit.lower(W0, V0, P, macfg, "io", plan,
                                        mesh, None).compile()
    mem = compiled.memory_analysis()
    per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {"layers": conf["num_hidden_layers"], "chips": chips,
            "backend": backend, "qp_batched": qp_batched,
            "routes": plan.route_counts(),
            "argument_gib": mem.argument_size_in_bytes / 2**30,
            "output_gib": mem.output_size_in_bytes / 2**30,
            "temp_gib": mem.temp_size_in_bytes / 2**30,
            "alias_gib": mem.alias_size_in_bytes / 2**30,
            "per_device_gib": per_dev / 2**30}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--qp-batched", type=int, choices=(0, 1), default=None)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    from chipbench import run as R

    if args.workload:
        r = R.load_run(args.workload, 0, 1.0, False)
        conf, traffic, chips = dict(r.conf), r.traffic, r.cell["chips"]
    else:
        conf = harness.load_json(args.config)
        traffic, chips = harness.load_json(args.traffic), 1
    if args.layers:
        conf["num_hidden_layers"] = args.layers
    qp = (traffic["maecho"]["qp_batched"] if args.qp_batched is None
          else bool(args.qp_batched))
    chips = args.chips or chips
    print(json.dumps(rehearse(conf, traffic, chips, qp)), flush=True)


if __name__ == "__main__":
    main()
