"""The benchmark's traffic generator and load generator (CPU, no JAX in
the generator): seeded schedules repeat, every seed gets the same work,
and a slow server does not delay later arrivals of an open loop."""
from __future__ import annotations

import json
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import traffic  # noqa: E402

MIX = {"loop": "open", "base_seed": 3,
       "prefix": {"pool": 4, "zipf": 1.1,
                  "len": {"dist": "uniform", "min": 20, "max": 30}},
       "user": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                "min": 2, "max": 40},
       "output": {"dist": "lognormal", "median": 5, "sigma": 0.5,
                  "min": 2, "max": 12}}
LOAD = {"rate_per_s": 20.0, "ramp_s": 0.5}


def test_seeded_schedule_repeats():
    a = traffic.build(MIX, LOAD, 2 ** 31 + 11, 2.0, vocab=100)
    b = traffic.build(MIX, LOAD, 2 ** 31 + 11, 2.0, vocab=100)
    assert [(r.prompt, r.max_new, r.offset_s) for r in a] == \
        [(r.prompt, r.max_new, r.offset_s) for r in b]


def test_every_seed_gets_the_same_work():
    """Seeds differ in token ids only: sizes, shared prefixes and due
    times are the mix's own."""
    a = traffic.build(MIX, LOAD, 1, 2.0, vocab=100)
    b = traffic.build(MIX, LOAD, 2, 2.0, vocab=100)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.prefix_id, lambda r: r.prefix_len,
                lambda r: r.offset_s):
        assert list(map(key, a)) == list(map(key, b))
    # the window holds exactly round(rate * seconds) arrivals, in order
    for reqs in (a, b):
        assert sum(r.in_window for r in reqs) == 40
        offs = [r.offset_s for r in reqs]
        assert offs == sorted(offs)


@pytest.mark.parametrize("pool,shared", [(4, True), (0, False)])
def test_system_prompts_shared_or_fresh(pool, shared):
    mix = dict(MIX, prefix=dict(MIX["prefix"], pool=pool))
    reqs = traffic.build(mix, LOAD, 5, 2.0, vocab=1000)
    heads = {tuple(r.prompt[:r.prefix_len]) for r in reqs}
    assert (len(heads) <= pool) == shared
    assert all(r.prompt[:r.prefix_len] and len(r.prompt) > r.prefix_len
               for r in reqs)


class _SlowHandler(BaseHTTPRequestHandler):
    """Streams two tokens, the first after DELAY_S: a server slower than
    the offered load."""
    protocol_version = "HTTP/1.1"
    DELAY_S = 0.8

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers["Content-Length"])
        body = json.loads(self.rfile.read(n))
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        time.sleep(self.DELAY_S)
        lines = [{"token": 1}, {"token": 2},
                 {"done": True, "finish_reason": "length",
                  "tokens": [1, 2]}]
        for obj in lines:
            data = json.dumps(obj).encode() + b"\n"
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()
        self.wfile.write(b"0\r\n\r\n")
        assert body["stream"]


def test_slow_server_does_not_delay_later_arrivals():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _SlowHandler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        settings = {"port": srv.server_address[1], "mix": MIX,
                    "load": LOAD, "seed": 9, "seconds": 1.0, "vocab": 100,
                    "start_at": time.monotonic() + 0.5, "grace_s": 5.0}
        p = subprocess.run([sys.executable, str(CHIP / "loadgen.py")],
                           input=json.dumps(settings) + "\n",
                           capture_output=True, text=True, timeout=60)
    finally:
        srv.shutdown()
        srv.server_close()
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    recs = {r["id"]: r for r in out["records"]}
    window = [recs[i] for i in out["in_window"]]
    assert len(window) == 20
    # each request went out on time although every earlier one was
    # still waiting for its first token
    lags = [r["sent"] - r["due"] for r in window]
    assert max(lags) < 0.2, lags
    assert all(r["finish"] == "length" and r["tokens"] == [1, 2]
               for r in window)
    ttft = [r["times"][0] - r["due"] for r in window]
    assert min(ttft) >= _SlowHandler.DELAY_S
    assert out["lag_p99_s"] < 0.2
    assert "jax" not in p.stderr


def test_loadgen_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import loadgen, traffic; "
            "print('jax' in sys.modules)" % str(CHIP))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert p.stdout.strip() == "False", p.stderr


def test_lengths_stay_in_bounds():
    rng = np.random.RandomState(0)
    x = traffic._lengths(rng, MIX["user"], 1000)
    assert x.min() >= 2 and x.max() <= 40
