#!/usr/bin/env python3
"""The control's readings for a cell's limits, several seeds in one process.

    python3 benchmark/limits.py --workload <name> --seeds <n> [<n> ...]

For each seed, the numbers `correct` compares, with the plain reference —
computed in the workload's `control` precision, one step below what its
configuration states — put in the program's place. A limit is set between the
largest reading sound runs of the program give (run.py's `compared`, over a
dozen seeds) and the smallest the control gives here; PERF.md records both.
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmark import run

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--fault", default=None, help="a fault the driver can plant in the reference")
    args = parser.parse_args(argv)

    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run._entry(bench["workloads"], args.workload, "workload")
    config = run.load_json(os.path.join(ROOT, run._entry(bench["configs"], cell["config"], "config")["file"]))
    spec = run.load_json(os.path.join(HERE, "workloads", args.workload + ".json"))
    import jax

    run.gate(cell["chips"])
    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    driver = importlib.import_module("benchmark.drivers." + spec["driver"])
    for seed in args.seeds:
        one = driver.Run(spec, config, seed, jax.devices()[: cell["chips"]], run.Tracer(False))
        numbers = one.control(args.fault) if args.fault else one.control()
        print(json.dumps({"workload": args.workload, "seed": seed, "control": spec["control"],
                          "fault": args.fault, "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
