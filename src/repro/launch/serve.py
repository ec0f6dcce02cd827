"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Spins up the policy-based ``Engine`` over a model — the arch's
smoke-sized variant by default, its full published config with
``--no-smoke`` — and runs a synthetic request workload; ``--partition
pp`` additionally serves through the Edge-PRUNE partitioned actor graph
at the given partition point, reporting the boundary traffic — the
paper's collaborative-inference scenario with an LLM as the workload.

``--policy`` picks the admission policy: ``batch`` is the seed
static-bucket executor (closed batches, no arrivals); ``fifo`` /
``priority`` / ``edf`` stream through the continuous scheduler against
the real clock — each request is admitted at its arrival instant and
its completion is printed the moment it finishes. The legacy ``--mode
static-bucket|continuous`` spelling still works and maps onto
``--policy batch|fifo``.

``--trace <jsonl>`` replays a recorded request trace instead of the
synthetic workload; one JSON object per line::

    {"arrival_s": 0.00, "prompt": [17, 3, 99], "max_new": 8}
    {"arrival_s": 0.02, "prompt_len": 32, "max_new": 16, "priority": 2,
     "deadline_s": 0.5}

``prompt`` gives explicit token ids; ``prompt_len`` asks for that many
random tokens (deterministic under the driver's seed). ``priority`` and
``deadline_s`` feed the priority/EDF admission policies. Arrivals are
seconds from serve start; out-of-order lines are allowed.
"""
from __future__ import annotations

import argparse
import json
from typing import List, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core import Mapping
from repro.launch.compile_cache import place_compile_cache
from repro.models import transformer as T
from repro.runtime.engine import Engine, EngineConfig
from repro.runtime.serving import PartitionedServeEngine, Request


def load_trace(path: str, cfg,
               rng: np.random.RandomState) -> Tuple[List[Request], List[float]]:
    """Parse a JSONL request trace into (requests, arrival offsets).
    Frontend architectures (vlm/audio) get deterministic synthetic
    ``embeds`` per request, like the synthetic workload path — traces
    record arrival/prompt/max-new (+ scheduling fields), not frontend
    tensors."""
    reqs: List[Request] = []
    arrivals: List[float] = []
    with open(path) as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if "prompt" in d:
                prompt = np.asarray(d["prompt"], np.int32)
            else:
                prompt = rng.randint(0, cfg.vocab_size,
                                     int(d.get("prompt_len", 32))
                                     ).astype(np.int32)
            r = Request(i, prompt, max_new_tokens=int(d.get("max_new", 16)),
                        eos=d.get("eos"),
                        priority=int(d.get("priority", 0)),
                        deadline_s=d.get("deadline_s"))
            if cfg.arch_type == "vlm":
                r.embeds = rng.randn(cfg.frontend_tokens,
                                     cfg.frontend_dim).astype(np.float32)
            elif cfg.arch_type == "audio":
                r.embeds = rng.randn(len(prompt),
                                     cfg.frontend_dim).astype(np.float32)
            reqs.append(r)
            arrivals.append(float(d.get("arrival_s", 0.0)))
    if not reqs:
        raise ValueError(f"trace {path} contains no requests")
    return reqs, arrivals


def main() -> None:
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's reduced same-family variant; "
                         "--no-smoke serves its full published width and "
                         "depth (random weights from PRNGKey(0))")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--partition", type=int, default=None,
                    help="also run Edge-PRUNE partitioned inference with "
                         "this many actors on the 'endpoint' unit")
    # shared engine-policy flags (one registration with serving_bench.py,
    # load_bench.py, runtime/server.py — the surface can't drift)
    EngineConfig.add_cli_args(ap)
    ap.add_argument("--mode", default=None,
                    choices=("static-bucket", "continuous"),
                    help="legacy spelling of --policy: static-bucket=batch, "
                         "continuous=fifo")
    ap.add_argument("--serve", action="store_true",
                    help="instead of running the synthetic workload, start "
                         "the HTTP front end (repro.runtime.server) over "
                         "this engine and block")
    ap.add_argument("--port", type=int, default=8800,
                    help="--serve listen port")
    ap.add_argument("--trace", default=None,
                    help="JSONL request trace to replay against the real "
                         "clock (continuous policies; see module docstring)")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload RNG seed (synthetic prompts and "
                         "prompt_len trace lines)")
    args = ap.parse_args()

    policy = args.policy
    if policy is None and args.mode is not None:
        policy = "batch" if args.mode == "static-bucket" else "fifo"
    if policy is None:
        policy = "batch"
    paged = args.paged or args.prefix_cache
    if policy == "batch" and (paged or args.prefill_chunk or args.trace
                              or args.serve or args.enforce_deadlines):
        policy = "fifo"
        print("# --paged/--prefix-cache/--prefill-chunk/--trace/--serve/"
              "--enforce-deadlines imply a continuous admission policy "
              "(fifo)")

    cfg = get_config(args.arch).smoke() if args.smoke else get_config(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(args.seed)
    arrivals = None
    if args.trace is not None:
        reqs, arrivals = load_trace(args.trace, cfg, rng)
        max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 8
    else:
        reqs = []
        # with --prefix-cache the synthetic workload models the shared-
        # preamble traffic the cache exists for: every prompt opens with
        # the same first half
        shared = rng.randint(0, cfg.vocab_size,
                             args.prompt_len // 2).astype(np.int32) \
            if args.prefix_cache else None
        for i in range(args.requests):
            prompt = rng.randint(0, cfg.vocab_size,
                                 args.prompt_len).astype(np.int32)
            if shared is not None:
                prompt[:len(shared)] = shared
            r = Request(i, prompt, max_new_tokens=args.max_new)
            if cfg.arch_type == "vlm":
                r.embeds = rng.randn(cfg.frontend_tokens,
                                     cfg.frontend_dim).astype(np.float32)
            elif cfg.arch_type == "audio":
                r.embeds = rng.randn(args.prompt_len,
                                     cfg.frontend_dim).astype(np.float32)
            reqs.append(r)
        max_len = args.prompt_len + args.max_new + 8
    eng = Engine(cfg, params,
                 EngineConfig.from_args(args, max_len=max_len,
                                        admission=policy))

    if args.serve:
        # HTTP front end over this engine/model; blocks until Ctrl-C.
        import time as _time

        from repro.runtime.server import EngineServer, ServerConfig
        with EngineServer(eng, ServerConfig(port=args.port)) as srv:
            print(f"# serving {cfg.name} on {srv.url} (policy={policy}, "
                  f"layout={eng.config.kv_layout}); POST /generate, "
                  f"GET /health/ready, GET /status", flush=True)
            try:
                while True:
                    _time.sleep(3600)
            except KeyboardInterrupt:
                return

    if policy != "batch":
        # Streaming serve: completions print as they finish, admission
        # follows arrival instants on the real clock.
        def stream(c) -> None:
            print(f"t={c.finish_s:8.3f}s req {c.id}: ttft "
                  f"{c.ttft_s * 1e3:7.1f} ms, latency "
                  f"{c.latency_s * 1e3:7.1f} ms, {len(c.tokens)} tokens, "
                  f"{c.finish_reason}, first: {c.tokens[:8]}")
        outs = eng.generate(reqs, arrivals=arrivals, on_completion=stream)
        span = max(o.finish_s for o in outs) - min(o.arrival_s for o in outs)
        toks = sum(len(o.tokens) for o in outs)
        lat = [o.latency_s for o in outs]
        st = eng.stats()
        print(f"# served {len(outs)} requests / {toks} tokens in "
              f"{span:.3f} s wall ({toks / max(span, 1e-9):.1f} tok/s); "
              f"mean latency {np.mean(lat) * 1e3:.1f} ms, p95 "
              f"{np.percentile(lat, 95) * 1e3:.1f} ms; "
              f"{st['preemptions']} preemptions, "
              f"{st['slot_failures']} slot failures")
        if paged:
            ks = eng.kv_stats()
            print(f"# paged KV: pool {ks['paged_kv_pool_bytes'] / 1e6:.2f} "
                  f"MB, high-water {ks['paged_kv_hwm_bytes'] / 1e6:.2f} MB "
                  f"({ks['paged_kv_hwm_blocks']:.0f} blocks, watermark "
                  f"{args.watermark}) vs slotted reservation "
                  f"{ks['slotted_kv_reserved_bytes'] / 1e6:.2f} MB")
        if args.prefix_cache:
            print(f"# prefix cache: {st['prefix_hits']} admissions matched "
                  f"a resident chain; {st['prefill_tokens_saved']} of "
                  f"{st['prefill_tokens_total']} prompt tokens skipped "
                  f"prefill")
    else:
        outs = eng.generate(reqs)
        tput = sum(len(o.tokens) for o in outs) / sum(o.decode_s for o in outs)
        for o in outs[:4]:
            print(f"req {o.id}: prefill {o.prefill_s*1e3:.1f} ms, "
                  f"{len(o.tokens)} tokens, {o.finish_reason}, "
                  f"first: {o.tokens[:8]}")
        print(f"# aggregate decode throughput ~{tput:.1f} tok/s")

    if args.partition is not None and cfg.arch_type not in ("vlm", "audio"):
        g = T.to_actor_graph(cfg, params, batch=1, seq=args.prompt_len)
        names = list(g.actors)
        pp = max(1, min(args.partition, len(names)))
        mapping = Mapping("cli", {n: ("endpoint" if i < pp else "server")
                                  for i, n in enumerate(names)})
        pse = PartitionedServeEngine(cfg, params, mapping, batch=1,
                                     seq=args.prompt_len)
        logits = pse.infer(reqs[0].prompt[None])
        print(f"# partitioned inference @pp={pp}: boundary "
              f"{pse.comm_bytes()} B, argmax {int(np.argmax(logits[0,-1]))}")


if __name__ == "__main__":
    main()
