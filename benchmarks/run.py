"""Benchmark entrypoint: one module per paper table/figure + the roofline
table + a CPU serving microbench. ``python -m benchmarks.run [--only X]``.

CSV schema: bench,name,value,unit,paper_anchor,rel_err
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (dual_input, fig4_vehicle_n2, fig5_vehicle_n270,
                        fig6_ssd_n2, latency_breakdown, roofline,
                        serving_bench)
from benchmarks.common import HEADER, emit
from repro.launch.compile_cache import place_compile_cache

BENCHES = {
    "fig4": fig4_vehicle_n2,
    "fig5": fig5_vehicle_n270,
    "fig6": fig6_ssd_n2,
    "dual_input": dual_input,
    "latency": latency_breakdown,
    "roofline": roofline,
    "serving": serving_bench,
}


def main() -> None:
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    args = ap.parse_args()
    names = [args.only] if args.only else list(BENCHES)
    print(HEADER)
    bad = 0
    for name in names:
        t0 = time.time()
        rows = BENCHES[name].run()
        emit(rows, save_as=f"{name}.json")
        for r in rows:
            if r.rel_err is not None and r.rel_err > 0.25:
                bad += 1
                print(f"WARN,{name},{r.name},rel_err={r.rel_err:.3f}",
                      file=sys.stderr)
        print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s",
              file=sys.stderr)
    if bad:
        print(f"# {bad} rows deviate >25% from paper anchors",
              file=sys.stderr)


if __name__ == "__main__":
    main()
