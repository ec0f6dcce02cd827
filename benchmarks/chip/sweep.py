#!/usr/bin/env python3
"""Rate sweep of a configuration under an open-loop traffic mix, to find
the knee once, on the chip.

    python benchmarks/chip/sweep.py --config <config> --traffic <mix> \\
        --seed <n> --rates 3,5,7,9 --seconds 15

One process builds and warms the configuration's server once, then
drives it at each Poisson rate in turn with the mix for ``--seconds``
and prints one JSON line per rate: TTFT and inter-token percentiles,
tokens per second, the queue left at the window's close, and how TTFT
moved from the window's first quarter to its last (a backlog that grows
shows as a rising TTFT). The knee is the highest rate whose backlog
does not grow; a cell's ``rate_per_s`` is set below it, once, by hand,
so a new cell is swept before its file exists.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cfg = run.traffic.load_json("configs", args.config)
    mix = run.traffic.load_json("traffic", args.traffic)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.place_compile_cache()
    import jax
    import numpy as np

    import stats
    import weights
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.server import EngineServer, ServerConfig
    if jax.devices()[0].platform != "tpu":
        run.log("sweep.py: needs a TPU")
        return 3
    compiles = run.CompileLog()
    spec = weights.Spec.from_config(cfg)
    engine = Engine(run.model_config(args.config, cfg),
                    weights.make_params(spec, args.seed),
                    EngineConfig(**cfg["engine"]))
    with EngineServer(engine, ServerConfig(port=0, **cfg["server"])) as srv:
        run.warm(engine, mix, cfg["vocab_size"])
        for rate in [float(r) for r in args.rates.split(",")]:
            ld = {"rate_per_s": rate, "ramp_s": 2.0}
            m = run.measure(srv, cfg, mix, ld, args.seed, args.seconds,
                            False, compiles, grace_s=20.0)
            t = stats.ttfts_s(m)
            q = max(1, len(t) // 4)
            row = {"rate_per_s": rate, "requests": len(t),
                   "ttft_p50_ms": 1e3 * np.percentile(t, 50),
                   "ttft_p90_ms": 1e3 * np.percentile(t, 90),
                   "ttft_first_quarter_ms": 1e3 * float(np.median(t[:q])),
                   "ttft_last_quarter_ms": 1e3 * float(np.median(t[-q:])),
                   "itl_p99_ms": 1e3 * np.percentile(stats.token_gaps_s(m),
                                                     99),
                   "tokens_per_s": stats.tokens_in_window(m) / args.seconds,
                   "queue_at_close": m["status1"]["queue_depth"],
                   "lag_p99_s": m["lag_p99_s"],
                   "compiles": m["compiles"]}
            print(json.dumps(row), flush=True)
            # cut streams leave queued requests that end only when their
            # first token finds the connection gone: let them drain
            deadline = time.monotonic() + 180
            while (run.get_status(srv.port)["inflight"]
                   and time.monotonic() < deadline):
                time.sleep(0.5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
