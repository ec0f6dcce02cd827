#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python benchmarks/chip/control.py --workload <cell> \\
        --seeds 1,2,...,12 --seconds 10 --control fp8

For each seed, one process runs the cell as a benchmark run does (the
same weights from the seed, the same warmed server, the cell's own
load for a short window) and reads the compared numbers; besides, it
reads the control: the reference computed with every matmul weight at
a lower precision, on the same prompts and served tokens, and puts that
reading through the run's own verdict under the cell's limit
(``control_correct``, which has to read false). One JSON line per seed.
The limit goes between the largest reading of the program and the
smallest of the control (``cells/<cell>.json``, ``limits``). Not part
of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    manifest, cell, cfg, mix, load = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.place_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("control.py: needs a TPU")
        return 3
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run.run_cell(cell, cfg, mix, load, manifest, seed=seed,
                           seconds=args.seconds, trace=False,
                           control=args.control)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_correct": {
                              k: v["correct"]
                              for k, v in out["control"].items()},
                          "compared": out["compared"],
                          "control": out["control"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
