"""HTTP front end (`runtime.server`): routes, streaming, backpressure.

Runs a real ``EngineServer`` on an ephemeral port (tiny model, warmup
on) and exercises it over actual sockets with ``http.client``:

* ``/health/live`` / ``/health/ready`` / ``/status`` probe contracts;
* ``/generate`` non-streaming vs streaming return identical tokens, and
  both match an in-process caller-pumped engine run of the same prompt
  (the HTTP layer is transport, not policy);
* chunked NDJSON framing: one token per line, terminal ``done`` line
  carries the completion;
* 400 on malformed bodies, 404 on unknown routes;
* 429 + Retry-After once ``max_inflight`` requests are open
  (bounded-admission backpressure);
* wall-clock deadline shed surfaces as ``finish_reason="timeout"``
  through the HTTP response;
* ``/metrics`` Prometheus exposition agrees with ``/status`` whether
  observability is on or off, and ``/trace`` always serves a valid
  (possibly empty) Chrome trace.
"""
from __future__ import annotations

import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import (Engine, EngineConfig, EngineServer, Request,
                           ServerConfig, parse_prometheus,
                           validate_chrome_trace)

KEY = jax.random.PRNGKey(0)


def _tiny_cfg() -> ModelConfig:
    return ModelConfig(
        name="tiny", arch_type="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
        param_dtype="float32", attn_chunk=16, remat=False)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny_cfg()
    return cfg, T.init_params(cfg, KEY)


@pytest.fixture(scope="module")
def server(setup):
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        max_len=512, max_slots=2, admission="edf", enforce_deadlines=True))
    with EngineServer(eng, ServerConfig(port=0, max_inflight=3)) as srv:
        yield srv


def _request(srv, method, path, body=None):
    conn = http.client.HTTPConnection(srv.config.host, srv.port, timeout=120)
    try:
        conn.request(method, path,
                     json.dumps(body) if body is not None else None,
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _generate(srv, body):
    status, _, raw = _request(srv, "POST", "/generate", body)
    return status, (json.loads(raw) if raw else None)


def test_health_and_status(server):
    status, _, raw = _request(server, "GET", "/health/live")
    assert status == 200 and json.loads(raw)["status"] == "live"
    status, _, raw = _request(server, "GET", "/health/ready")
    assert status == 200 and json.loads(raw)["status"] == "ready"
    status, _, raw = _request(server, "GET", "/status")
    st = json.loads(raw)
    assert status == 200
    assert st["ready"] and st["max_inflight"] == 3
    assert {"inflight", "queue_depth", "active_slots",
            "kv", "counters"} <= set(st)
    assert st["counters"]["admissions"] >= 1        # the warmup request


def test_unknown_routes(server):
    assert _request(server, "GET", "/nope")[0] == 404
    assert _request(server, "POST", "/nope")[0] == 404


@pytest.mark.parametrize("body,frag", [
    ({}, "prompt"),
    ({"prompt": "hi"}, "prompt"),
    ({"prompt": []}, "prompt"),
    ({"prompt": [1, 2], "max_new_tokens": 0}, "max_new_tokens"),
    ({"prompt": [1, 2], "deadline_s": "soon"}, "deadline_s"),
    ({"prompt": [1, 2], "eos": "x"}, "eos"),
])
def test_bad_requests(server, body, frag):
    status, out = _generate(server, body)
    assert status == 400 and frag in out["error"]


def test_generate_matches_inprocess(server, setup):
    cfg, params = setup
    prompt = [int(t) for t in
              np.random.RandomState(5).randint(1, 64, 10)]
    status, out = _generate(server, {"prompt": prompt, "max_new_tokens": 7})
    assert status == 200
    assert out["finish_reason"] == "length" and len(out["tokens"]) == 7
    assert out["ttft_s"] >= 0 and out["latency_s"] >= out["ttft_s"]
    # oracle: same prompt through a fresh caller-pumped engine
    ref = Engine(cfg, params, EngineConfig(max_len=512, max_slots=2))
    (c,) = ref.generate([Request(0, np.asarray(prompt, np.int32),
                                 max_new_tokens=7)])
    assert out["tokens"] == [int(t) for t in c.tokens]


def test_streaming_ndjson(server):
    prompt = [int(t) for t in np.random.RandomState(6).randint(1, 64, 8)]
    conn = http.client.HTTPConnection(server.config.host, server.port,
                                      timeout=120)
    try:
        conn.request("POST", "/generate",
                     json.dumps({"prompt": prompt, "max_new_tokens": 5,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 200
        assert r.getheader("Content-Type") == "application/x-ndjson"
        lines = [json.loads(ln) for ln in r.read().splitlines()
                 if ln.strip()]
    finally:
        conn.close()
    toks = [ln["token"] for ln in lines if "token" in ln]
    final = lines[-1]
    assert final["done"] and final["finish_reason"] == "length"
    assert final["tokens"] == toks and len(toks) == 5
    # non-streamed run of the identical prompt matches token for token
    _, out = _generate(server, {"prompt": prompt, "max_new_tokens": 5})
    assert out["tokens"] == toks


def test_deadline_shed_over_http(server):
    status, out = _generate(server, {"prompt": [1, 2, 3], "deadline_s": 0.0,
                                     "max_new_tokens": 8})
    assert status == 200
    assert out["finish_reason"] == "timeout" and out["tokens"] == []


def test_backpressure_429(server):
    """Fill the admission bound (3) with slow streaming requests, then
    verify the next one bounces with 429 + Retry-After and that capacity
    comes back once the stream completes."""
    prompt = [int(t) for t in np.random.RandomState(7).randint(1, 64, 8)]
    conns = []
    try:
        for _ in range(3):
            c = http.client.HTTPConnection(server.config.host, server.port,
                                           timeout=120)
            c.request("POST", "/generate",
                      json.dumps({"prompt": prompt, "max_new_tokens": 300,
                                  "stream": True}),
                      {"Content-Type": "application/json"})
            conns.append(c)
        # wait until all three are actually admitted server-side
        deadline = time.time() + 30
        while time.time() < deadline:
            st = json.loads(_request(server, "GET", "/status")[2])
            if st["inflight"] >= 3:
                break
            time.sleep(0.01)
        status, headers, raw = _request(
            server, "POST", "/generate",
            {"prompt": prompt, "max_new_tokens": 2})
        assert status == 429
        assert "admission queue full" in json.loads(raw)["error"]
        assert headers.get("Retry-After") == "1"
    finally:
        for c in conns:
            c.getresponse().read()      # drain to completion
            c.close()
    # capacity released: the same request is admitted now
    status, out = _generate(server, {"prompt": prompt, "max_new_tokens": 2})
    assert status == 200 and out["finish_reason"] == "length"


def test_concurrent_http_clients(server):
    results = []
    errs = []

    def client(i):
        try:
            prompt = [int(t)
                      for t in np.random.RandomState(i).randint(1, 64, 8)]
            status, out = _generate(
                server, {"prompt": prompt, "max_new_tokens": 4})
            results.append((status, out))
        except Exception as e:          # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert [s for s, _ in results] == [200, 200, 200]
    assert all(len(o["tokens"]) == 4 for _, o in results)


def test_metrics_without_observability(server):
    """Counters are mirrored from the scheduler's event log, so
    /metrics works even with observability off — it just carries no
    histogram samples."""
    status, headers, raw = _request(server, "GET", "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    parsed = parse_prometheus(raw.decode())
    st = json.loads(_request(server, "GET", "/status")[2])
    assert not st["observability"]
    assert parsed["counters"]["repro_admissions_total"] \
        == st["counters"]["admissions"]
    assert parsed["counters"]["repro_steps_total"] == st["counters"]["steps"]
    assert parsed["gauges"]["repro_http_max_inflight"] == 3
    ttft = parsed["histograms"].get("repro_ttft_seconds")
    assert ttft is None or ttft["count"] == 0


def test_trace_empty_without_observability(server):
    status, _, raw = _request(server, "GET", "/trace")
    assert status == 200
    trace = json.loads(raw)
    assert validate_chrome_trace(trace) == 0
    assert trace["traceEvents"] == []


@pytest.fixture(scope="module")
def obs_server(setup):
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        max_len=512, max_slots=2, observability=True))
    with EngineServer(eng, ServerConfig(port=0, max_inflight=3)) as srv:
        yield srv


def test_metrics_with_observability(obs_server):
    prompt = [int(t) for t in np.random.RandomState(9).randint(1, 64, 8)]
    status, out = _generate(obs_server,
                            {"prompt": prompt, "max_new_tokens": 6})
    assert status == 200 and len(out["tokens"]) == 6

    status, _, raw = _request(obs_server, "GET", "/metrics")
    assert status == 200
    parsed = parse_prometheus(raw.decode())
    st = json.loads(_request(obs_server, "GET", "/status")[2])
    assert st["observability"]
    # exposition and snapshot describe the same state
    assert parsed["counters"]["repro_admissions_total"] \
        == st["counters"]["admissions"]
    snap_hists = st["metrics"]["histograms"]
    for name in ("repro_ttft_seconds", "repro_inter_token_seconds",
                 "repro_step_duration_seconds", "repro_queue_wait_seconds"):
        assert parsed["histograms"][name]["count"] \
            == snap_hists[name]["count"], name
    assert snap_hists["repro_ttft_seconds"]["count"] >= 1
    # cumulative buckets are monotone and end at the total count
    buckets = parsed["histograms"]["repro_ttft_seconds"]["buckets"]
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)
    assert cums[-1] == parsed["histograms"]["repro_ttft_seconds"]["count"]


def test_trace_with_observability(obs_server):
    prompt = [int(t) for t in np.random.RandomState(10).randint(1, 64, 8)]
    status, _ = _generate(obs_server, {"prompt": prompt,
                                       "max_new_tokens": 4})
    assert status == 200
    status, _, raw = _request(obs_server, "GET", "/trace")
    assert status == 200
    trace = json.loads(raw)
    assert validate_chrome_trace(trace) > 0
    names = {e.get("name") for e in trace["traceEvents"]}
    # request lifecycle spans and step slices are present
    assert any(isinstance(n, str) and n.startswith("req ") for n in names)
    assert any(isinstance(n, str) and n.startswith("step ") for n in names)
    # the engine drain records on the wall clock only
    cats = {e.get("cat") for e in trace["traceEvents"]
            if e.get("ph") != "M"}
    assert cats <= {"wall"}


def test_server_rejects_batch_engine(setup):
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(max_len=64, admission="batch"))
    with pytest.raises(ValueError, match="batch"):
        EngineServer(eng)


def test_multi_tenant_victim_cache_over_http(setup):
    """The prefix-cache service over the wire: two tenants post the same
    prompt twice each; /status exposes per-tenant pool occupancy, the
    second round registers cross-request victim hits, and a bad tenant
    field is a 400."""
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        max_len=64, max_slots=2, kv_layout="paged", block_size=8,
        num_blocks=24, prefix_cache=True, victim_cache=True,
        prefix_cache_tenants={"acme": 1 << 20, "globex": 1 << 20},
        debug=True))
    prompt = [int(t) for t in np.random.RandomState(11).randint(1, 64, 20)]
    with EngineServer(eng, ServerConfig(port=0, max_inflight=3)) as srv:
        status, out = _generate(srv, {"prompt": prompt, "tenant": 7})
        assert status == 400 and "tenant" in out["error"]
        first = {}
        for tenant in ("acme", "globex"):
            status, out = _generate(srv, {"prompt": prompt,
                                          "max_new_tokens": 6,
                                          "tenant": tenant})
            assert status == 200
            first[tenant] = out["tokens"]
        # identical prompts under different tenants: same greedy tokens,
        # but the pool holds a separate copy per namespace
        assert first["acme"] == first["globex"]
        status, _, raw = _request(srv, "GET", "/status")
        pc = json.loads(raw)["prefix_cache"]
        assert status == 200 and pc["enabled"] and pc["victim_cache"]
        per = pc["per_tenant_bytes"]
        assert per.get("acme", 0) > 0 and per.get("globex", 0) > 0
        assert pc["tenant_quotas"] == {"acme": 1 << 20, "globex": 1 << 20}
        before = pc["victim_hits"]
        for tenant in ("acme", "globex"):
            status, out = _generate(srv, {"prompt": prompt,
                                          "max_new_tokens": 6,
                                          "tenant": tenant})
            assert status == 200
            assert out["tokens"] == first[tenant], \
                "cache hit changed the tokens"
        status, _, raw = _request(srv, "GET", "/status")
        pc = json.loads(raw)["prefix_cache"]
        assert pc["victim_hits"] > before, \
            "second round never hit the parked chains"
        assert pc["prefill_tokens_saved"] > 0 and pc["bytes_saved"] > 0


@pytest.mark.parametrize("argv", [
    [],                                         # neither --tiny nor --arch
    ["--tiny", "--arch", "qwen2_1_5b"],         # both
    ["--arch", "llava_next_mistral_7b"],        # needs frontend embeds
    ["--arch", "no_such_model"],
    ["--arch", "qwen2_1_5b", "--policy", "batch"],
])
def test_cli_rejects_unservable_arguments(argv):
    """The CLI refuses what it cannot serve before building a model."""
    from repro.runtime.server import main
    with pytest.raises(SystemExit) as exc:
        main(argv)
    assert exc.value.code == 2
