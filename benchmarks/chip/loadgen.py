#!/usr/bin/env python3
"""Load generator: a child process that never imports JAX.

The benchmark's own process holds the chip and serves HTTP; this process
sends the traffic. It reads one JSON line of settings from standard
input, builds the run's requests with ``traffic.build``, sends them to
``POST /generate`` (streamed), and prints one JSON object of per-request
timings on standard output. Every timestamp is ``time.monotonic()``,
which is one system-wide clock, so the server's process can compare
them with its own.

* Open loop: each request is sent on a connection of its own at its
  due time, however many are still open, and its TTFT is measured
  from that due time; how late the sender ran is reported (``lag``).
  After the window closes, arrivals continue at the same rate until
  every request due in the window has finished, or ``grace_s`` has
  passed; then the rest are cut off.
* Closed loop: ``clients`` threads each send their next request when
  the previous one ends. A second input line, ``{"close": t}``, says
  when the window (opened by the server's side) ends; the clients then
  stop and open streams are cut.

Settings: host, port, mix, load, seed, seconds, vocab, start_at,
grace_s.
"""
from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import traffic  # noqa: E402


class Record:
    __slots__ = ("id", "due", "prompt_len", "sent", "status", "finish",
                 "error", "times", "tokens")

    def __init__(self, req, due: float):
        self.id = req.id
        self.prompt_len = len(req.prompt)
        self.due = due
        self.sent = None
        self.status = 0
        self.finish = None
        self.error = None
        self.times = []
        self.tokens = []

    def as_json(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class Sender:
    """Sends requests and keeps their connections, so that they can be
    cut when the run stops."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.live = {}
        self.lock = threading.Lock()
        self.stopped = threading.Event()

    def run_one(self, req, rec: Record) -> None:
        conn = http.client.HTTPConnection(self.host, self.port, timeout=900)
        with self.lock:
            if self.stopped.is_set():
                rec.error = "not sent: run stopped"
                return
            self.live[rec.id] = conn
        body = json.dumps({"prompt": req.prompt, "max_new_tokens": req.max_new,
                           "stream": True})
        try:
            rec.sent = time.monotonic()
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            rec.status = r.status
            if r.status != 200:
                r.read()
                return
            while True:
                line = r.readline()
                if not line:
                    break
                now = time.monotonic()
                line = line.strip()
                if not line:
                    continue
                obj = json.loads(line)
                if "token" in obj:
                    rec.times.append(now)
                    rec.tokens.append(int(obj["token"]))
                if obj.get("done"):
                    rec.finish = obj["finish_reason"]
        except Exception as e:          # noqa: BLE001 — recorded per request
            rec.error = f"{type(e).__name__}: {e}"
        finally:
            with self.lock:
                self.live.pop(rec.id, None)
            conn.close()

    def stop(self) -> None:
        """Refuse further sends and cut every open stream."""
        with self.lock:
            self.stopped.set()
            conns = list(self.live.values())
        for c in conns:
            try:
                if c.sock is not None:
                    c.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def open_loop(cfg: dict, reqs, sender: Sender) -> dict:
    start = cfg["start_at"]
    load = cfg["load"]
    w0 = start + float(load.get("ramp_s", 0.0))
    w1 = w0 + cfg["seconds"]
    recs = [Record(r, start + r.offset_s) for r in reqs]
    window = [rec for r, rec in zip(reqs, recs) if r.in_window]
    threads = []
    give_up = w1 + cfg["grace_s"]

    def window_done() -> bool:
        return all(rec.finish is not None or rec.error is not None
                   or (rec.status and rec.status != 200) for rec in window)

    for r, rec in zip(reqs, recs):
        while True:
            now = time.monotonic()
            if now >= rec.due:
                break
            time.sleep(min(rec.due - now, 0.05))
            if now > w1 and window_done():
                break
        now = time.monotonic()
        if (now > w1 and window_done()) or now > give_up:
            break
        t = threading.Thread(target=sender.run_one, args=(r, rec),
                             daemon=True)
        t.start()
        threads.append(t)
    while not window_done() and time.monotonic() < give_up:
        time.sleep(0.05)
    stopped_at = time.monotonic()
    sender.stop()
    for t in threads:
        t.join(timeout=30)
    return {"records": [rec.as_json() for rec in recs if rec.sent is not None
                        or rec.error is not None],
            "window": [w0, w1], "stopped_at": stopped_at,
            "in_window": [r.id for r in reqs if r.in_window]}


def closed_loop(cfg: dict, reqs, sender: Sender) -> dict:
    clients = int(cfg["load"]["clients"])
    lock = threading.Lock()
    cursor = [0]
    recs = []
    close = {}
    got_close = threading.Event()

    def read_close() -> None:
        line = sys.stdin.readline()
        if line.strip():
            close.update(json.loads(line))
        got_close.set()

    def client() -> None:
        while not sender.stopped.is_set():
            with lock:
                r = reqs[cursor[0] % len(reqs)]
                cursor[0] += 1
                rec = Record(r, time.monotonic())
                recs.append(rec)
            sender.run_one(r, rec)

    threading.Thread(target=read_close, daemon=True).start()
    delay = cfg["start_at"] - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    got_close.wait()
    w1 = close.get("close", time.monotonic())
    while time.monotonic() < w1:
        time.sleep(min(0.05, max(w1 - time.monotonic(), 0.0)))
    stopped_at = time.monotonic()
    sender.stop()
    for t in threads:
        t.join(timeout=30)
    with lock:
        out = [rec.as_json() for rec in recs]
    return {"records": out, "window": [close.get("open"), w1],
            "stopped_at": stopped_at, "in_window": []}


def main() -> int:
    cfg = json.loads(sys.stdin.readline())
    mix, load = cfg["mix"], cfg["load"]
    reqs = traffic.build(mix, load, cfg["seed"], cfg["seconds"], cfg["vocab"])
    sender = Sender(cfg.get("host", "127.0.0.1"), cfg["port"])
    ready_at = time.monotonic()
    if mix["loop"] == "open":
        out = open_loop(cfg, reqs, sender)
    else:
        out = closed_loop(cfg, reqs, sender)
    lags = sorted(r["sent"] - r["due"] for r in out["records"]
                  if r["sent"] is not None)
    out["lag_p99_s"] = lags[int(0.99 * (len(lags) - 1))] if lags else None
    # started and ready to send; after ``start_at``, start-up made the
    # first requests late
    out["ready_at"] = ready_at
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
