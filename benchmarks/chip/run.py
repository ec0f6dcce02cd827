#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process finds.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
else is found by name: the configuration in ``configs/<config>.json``,
the traffic mix in ``traffic/<traffic>.json``, the cell's offered load
and its correctness limits in ``cells/<cell>.json``, and one reader per
metric in ``metrics/<metric>.py``.

This process holds the chip. It draws the weights from ``--seed`` on
the device, builds the served model (``Engine`` behind ``EngineServer``,
the users' HTTP entry point), warms every program the traffic uses,
then starts the load generator (``loadgen.py``, a child process that
never imports JAX) and measures for ``--seconds``. ``--trace 1``
records the scheduler's histograms and a profiler trace of part of the
window, and reports the per-layer metrics instead of the end-to-end
ones.

After the window the served model is freed and a sample of the finished
requests, the longest among them, is compared with the float32
reference (``reference.py``), which draws its own copy of the weights
from the seed. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``compared`` (each compared
number beside its limit, also printed as the last lines of standard
error). Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import traffic  # noqa: E402

# correctness sample: requests compared with the reference per run
SAMPLE = 16
# open loop: how long past the window's close the generator waits for
# the requests due in it before it gives up on them
GRACE_S = 60.0
# closed loop: the longest wait for every slot to fill
FILL_TIMEOUT_S = 180.0
# after the window: the longest wait for cut requests to leave the server
DRAIN_TIMEOUT_S = 240.0
# --trace 1: seconds of the window the profiler records
TRACE_S = 5.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- set-up --------------------------------------------------------------


def place_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory inside
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping
    every program, so that only a checkout's first run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Counts the compilations and traces JAX reports, with their time."""

    def __init__(self):
        import jax.monitoring
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.events.append((time.monotonic(), name))

    def between(self, a: float, b: float) -> dict:
        out = {n.rsplit("/", 1)[-1]: 0 for n in COMPILE_EVENTS}
        for t, n in self.events:
            if a <= t < b:
                out[n.rsplit("/", 1)[-1]] += 1
        return out


def model_config(name: str, cfg: dict):
    from repro.models.config import ModelConfig
    keys = ModelConfig.__dataclass_fields__
    kw = {k: v for k, v in cfg.items() if k in keys and k != "name"}
    return ModelConfig(name=name, arch_type="dense", layer_pattern=("attn",),
                       **kw)


def warm(engine, mix: dict, vocab: int) -> None:
    """Serve, in this process, requests that take every path the
    traffic takes, so that no program compiles in the window (the
    server's own warm-up request has compiled the decode step): chunked
    prefill of the longest prompt, block growth, and with the prefix
    cache a resume from resident blocks (both from a live request's
    blocks and from the victim pool) at every chunk boundary a prompt of
    the mix can reach, a whole-prompt match, and the copy-on-write of a
    shared page."""
    import numpy as np
    from repro.runtime.scheduler import Request
    ec = engine.config
    chunk = ec.prefill_chunk
    longest = min(traffic.longest_context(mix), ec.max_len) \
        - int(mix["output"]["max"])
    rng = np.random.RandomState(12345)
    ids = iter(range(10 ** 6, 2 * 10 ** 6))

    def req(prompt, new=2):
        return Request(id=next(ids), prompt=np.asarray(prompt, np.int32),
                       max_new_tokens=new)

    base = rng.randint(0, vocab, longest)
    engine.submit(req(base, 20)).result()
    if ec.prefix_cache:
        for k in range(1, (longest - 16) // chunk + 1):
            head = base[:k * chunk + 16]
            engine.submit(req(np.concatenate(
                [head, rng.randint(0, vocab, 8)]))).result()
        twin = rng.randint(0, vocab, chunk + 40)
        hs = [engine.submit(req(twin, 24)) for _ in range(2)]
        for h in hs:
            h.result()
        engine.submit(req(twin, 24)).result()
    # the chunked admission reads its first token at the prompt's last
    # real row: that row's static slice is a program of its own. A
    # request per row would cost a chunk step each (about 10 s of every
    # run's set-up); the slices alone cost well under a second, and the
    # compiles counted in the window (logged by every run) show when
    # they stop covering what the engine does
    import jax.numpy as jnp
    dummy = jnp.zeros((1, chunk, engine.cfg.padded_vocab_size),
                      jnp.dtype(engine.cfg.dtype))
    for r in range(chunk):
        dummy[:, r].block_until_ready()


def get_status(port: int) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/status",
                                timeout=60) as r:
        return json.loads(r.read())


# -- the measured window ---------------------------------------------------


def measure(srv, cfg: dict, mix: dict, load: dict, seed: int,
            seconds: float, trace: bool, compiles: CompileLog,
            grace_s: float = GRACE_S) -> dict:
    """Drive the live server with the load generator for one window."""
    import jax
    engine = srv.engine
    start_at = time.monotonic() + 1.0
    child = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py")], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
    try:
        child.stdin.write(json.dumps({
            "port": srv.port, "mix": mix, "load": load, "seed": seed,
            "seconds": seconds, "vocab": cfg["vocab_size"],
            "start_at": start_at, "grace_s": grace_s}) + "\n")
        child.stdin.flush()
        if mix["loop"] == "open":
            w0 = start_at + float(load.get("ramp_s", 0.0))
            time.sleep(max(w0 - time.monotonic(), 0.0))
        else:
            full = engine.config.max_slots
            deadline = time.monotonic() + FILL_TIMEOUT_S
            while engine.snapshot()["active_slots"] < full:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"the {full} slots never filled")
                time.sleep(0.05)
            w0 = time.monotonic()
            child.stdin.write(json.dumps(
                {"open": w0, "close": w0 + seconds}) + "\n")
            child.stdin.flush()
        w1 = w0 + seconds
        status0 = get_status(srv.port)
        red = None
        if trace:
            red = traced_part(w0, seconds)
        time.sleep(max(w1 - time.monotonic(), 0.0))
        status1 = get_status(srv.port)
        out, _ = child.communicate(timeout=seconds + grace_s + 120)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    # the generator cut its open streams; a request learns of that when
    # it next writes a token, so wait until none is left in flight
    # before the caller closes the server
    deadline = time.monotonic() + DRAIN_TIMEOUT_S
    while get_status(srv.port)["inflight"]:
        if time.monotonic() > deadline:
            raise RuntimeError("requests still in flight after the run")
        time.sleep(0.2)
    gen = json.loads(out.strip().splitlines()[-1])
    gen["window"] = [w0, w1]
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return dict(gen, status0=status0, status1=status1, trace=red,
                setup_s=w0 - T_START, memory_peak_bytes=int(peak),
                compiles=compiles.between(w0, w1))


def traced_part(w0: float, seconds: float):
    """Profile TRACE_S seconds in the middle of the window and reduce
    the trace."""
    import jax
    import tracereduce
    span = min(TRACE_S, seconds / 2)
    time.sleep(max(w0 + (seconds - span) / 2 - time.monotonic(), 0.0))
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            time.sleep(span)
        jax.profiler.stop_trace()
        files = list(Path(tmp).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        return tracereduce.reduce_file(str(files[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# -- correctness -----------------------------------------------------------


def compare(spec, cfg: dict, mix: dict, load: dict, seed: int,
            seconds: float, records: list, limits: dict,
            control: str = "") -> dict:
    """Compare a sample of the finished requests with the reference.
    Returns the compared numbers, each with its limit, and the readings
    behind them. With ``control`` ("int8" or "fp8") also the reading of
    the reference at that precision on the same tokens (a comma list
    reads several)."""
    import numpy as np
    import reference
    import weights
    reqs = {r.id: r for r in traffic.build(mix, load, seed, seconds,
                                           cfg["vocab_size"])}
    done = {}
    short = 0
    for r in records:
        if r["finish"] is None or r["error"] is not None:
            continue
        if len(r["tokens"]) != reqs[r["id"]].max_new:
            short += 1
        done.setdefault(r["id"], r)
    ids = sorted(done)
    rng = np.random.RandomState(seed % (2 ** 32))
    pick = []
    if ids:
        longest = max(ids, key=lambda i: len(reqs[i].prompt)
                      + len(done[i]["tokens"]))
        rest = [i for i in ids if i != longest]
        pick = [longest] + [rest[j] for j in rng.permutation(len(rest))
                            [:SAMPLE - 1]]
    length = traffic.longest_context(mix)
    seqs = [reference.padded(reqs[i].prompt, done[i]["tokens"], length)
            for i in pick]
    kinds = [k for k in control.split(",") if k]
    alt = {}
    for kind in kinds:
        q = reference.quantize(weights.make_params(spec, seed), kind)
        alt[kind] = [reference.picks(spec, q, toks)[where]
                     for toks, where in seqs]
        del q
    params = weights.make_params(spec, seed)
    served, margins, ctrl = [], [], {k: [] for k in kinds}
    for k, (i, (toks, where)) in enumerate(zip(pick, seqs)):
        got = reference.gaps(spec, params, toks, where, done[i]["tokens"],
                             *[alt[kind][k] for kind in kinds])
        served.append(got[0])
        margins.append(got[-1])
        for kind, g in zip(kinds, got[1:-1]):
            ctrl[kind].append(g)
    del params
    m = np.concatenate(margins) if margins else np.zeros(1)
    log(f"reference: top-2 margin at the served positions "
        f"p5={np.percentile(m, 5):.4f} p50={np.percentile(m, 50):.4f} "
        f"min={m.min():.4f}; distinct served tokens per request "
        f"{[len(set(done[i]['tokens'])) for i in pick]}")
    compared = {
        "logit_gap_mean": {"value": gap_stats(served)["logit_gap_mean"],
                           "limit": limits.get("logit_gap_mean")},
        "short_streams": {"value": short, "limit": 0},
        "compared_requests": {"value": len(pick), "limit": 1},
    }
    control_out = {}
    for kind, g in ctrl.items():
        # the control in the program's place, through the same verdict
        stats = gap_stats(g)
        as_served = dict(compared, logit_gap_mean={
            "value": stats["logit_gap_mean"],
            "limit": compared["logit_gap_mean"]["limit"]})
        control_out[kind] = dict(stats, correct=is_correct(as_served))
    return {"compared": compared, "served": gap_stats(served),
            "control": control_out}


def gap_stats(gaps: list) -> dict:
    """The mean gap over every compared position (the number compared:
    it grows with the square of a logit error, since both how often a
    near-tie flips and how far it flips grow with it), and beside it the
    widest gap and the share of positions that flipped."""
    import numpy as np
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not len(g):
        return {"logit_gap_mean": None, "logit_gap_max": None,
                "flipped": None, "positions": 0}
    return {"logit_gap_mean": float(g.mean()), "logit_gap_max": float(g.max()),
            "flipped": float(np.mean(g > 0)), "positions": int(len(g))}


def is_correct(compared: dict) -> bool:
    gap = compared["logit_gap_mean"]
    return (gap["value"] is not None and gap["limit"] is not None
            and gap["value"] <= gap["limit"]
            and compared["short_streams"]["value"] == 0
            and compared["compared_requests"]["value"]
            >= compared["compared_requests"]["limit"])


# -- metrics ---------------------------------------------------------------


def cell_metrics(manifest: dict, cell: str, trace: bool) -> list:
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metric(name: str, run: dict):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


# -- one run ---------------------------------------------------------------


def run_cell(cell: dict, cfg: dict, mix: dict, load: dict, manifest: dict,
             *, seed: int, seconds: float, trace: bool,
             control: str = "") -> dict:
    """Build, warm, measure and check one cell; returns the result
    object (without ``device``'s platform fields)."""
    import jax

    import weights
    import workcount
    from repro.runtime.engine import Engine, EngineConfig
    from repro.runtime.server import EngineServer, ServerConfig

    compiles = CompileLog()
    spec = weights.Spec.from_config(cfg)
    dev = jax.devices()[0]
    # no device numbers off the chip: the readers that need a peak read
    # nothing on another platform
    peak = workcount.peaks(dev.device_kind) if dev.platform == "tpu" \
        else None
    params = jax.block_until_ready(weights.make_params(spec, seed))
    engine = Engine(model_config(cell["config"], cfg), params,
                    EngineConfig(**cfg["engine"], observability=trace))
    del params
    srv = EngineServer(engine, ServerConfig(port=0, **cfg["server"]))
    srv.start()
    try:
        warm(engine, mix, cfg["vocab_size"])
        m = measure(srv, cfg, mix, load, seed, seconds, trace, compiles)
    finally:
        srv.close()
    del srv, engine
    gc.collect()
    log(f"window: lag_p99_s={m['lag_p99_s']} "
        f"compiles_in_window={m['compiles']} "
        f"memory_peak_bytes={m['memory_peak_bytes']} "
        f"setup_s={m['setup_s']:.3f}")
    if m["trace"] is not None:
        log(f"trace: modules={json.dumps(m['trace'].get('modules'))}")
    run = dict(m, spec=spec, engine=cfg["engine"], peak=peak)
    names = cell_metrics(manifest, cell["name"], trace)
    metrics = {}
    for mm in names:
        v = read_metric(mm["name"], run)
        if v is not None:
            metrics[mm["name"]] = {"value": float(v), "unit": mm["unit"]}
    window_ids = set(m["in_window"])
    if window_ids:
        recs = [r for r in m["records"] if r["id"] in window_ids]
        attempted = len(window_ids)
    else:
        w0, w1 = m["window"]
        recs = [r for r in m["records"]
                if r["sent"] is not None and w0 <= r["sent"] < w1]
        attempted = len(recs)
    if window_ids:
        # an open-loop request fails when it was refused, broke off, or
        # had not finished when the generator gave up on it
        failed = sum(1 for r in recs if r["status"] != 200
                     or r["finish"] is None)
    else:
        failed = sum(1 for r in recs if r["status"] not in (0, 200))
    chk = compare(spec, cfg, mix, load, seed, seconds, m["records"],
                  load.get("limits", {}), control)
    log(f"compared: requests={chk['compared']['compared_requests']['value']}"
        f" served={json.dumps(chk['served'])}")
    if control:
        log(f"control: {json.dumps(chk['control'])}")
    device = {"memory_peak_bytes": m["memory_peak_bytes"]}
    out = {"correct": is_correct(chk["compared"]), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    tr = m["trace"]
    if trace and tr is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr.get("device_ops", []),
                            "idle_gaps": tr.get("idle_gaps", [])}
    if control:
        out["control"] = chk["control"]
    out["compared"] = chk["compared"]
    return out


def load_cell(name: str):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"one of {sorted(cells)}")
    cell = cells[name]
    return (manifest, cell, traffic.load_json("configs", cell["config"]),
            traffic.load_json("traffic", cell["traffic"]),
            traffic.load_json("cells", name))


def report(out: dict) -> None:
    for k, v in out["compared"].items():
        log(f"{k} {v['value']} limit {v['limit']}")
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, cell, cfg, mix, load = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    place_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"run.py: needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 3
    out = run_cell(cell, cfg, mix, load, manifest, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace))
    out["device"] = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                         count=len(devs), **out["device"])
    compared = out.pop("compared")
    out["compared"] = compared
    report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
