#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX.

It makes the cell's requests from the mix and the seed (harness/traffic),
waits for the window's start on the machine's monotonic clock, sends each
request to `POST /generate` over localhost HTTP as a user would, stamps
every streamed token line as it arrives, and writes what it saw as one
JSON file. Open loop: each request is sent when it is due, whether or not
earlier ones have finished, one thread per request in flight. Closed
loop: one thread per client. After the window closes it sends nothing
new and waits for what is in flight (`--grace` seconds at the most).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic  # noqa: E402


def post_generate(host, port, req, rec, deadline):
    """Send one request and read its stream; fills `rec` in place."""
    body = json.dumps({"tokens": req["prompt"], "max_new_tokens": req["max_new"],
                       "id": req["id"]})
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        rec["t_sent"] = time.monotonic()
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read().decode("utf-8", "replace")[:300]
            return
        while True:
            line = resp.readline()
            now = time.monotonic()
            if not line:
                break
            msg = json.loads(line)
            if "token" in msg:
                rec["t_tokens"].append(now)
            elif msg.get("done"):
                rec["tokens"] = msg.get("tokens")
                rec["timing"] = msg.get("timing")
                rec["t_done"] = now
                if msg.get("error"):
                    rec["error"] = msg["error"]
            elif "error" in msg:
                rec["error"] = msg["error"]
            if now > deadline:
                rec["error"] = rec.get("error") or "gave up after the grace"
                break
    except Exception as exc:  # a refused or broken connection is a failure
        rec["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        conn.close()


def new_record(req):
    return {"id": req["id"], "due_s": req["due_s"], "client": req["client"],
            "prompt_len": len(req["prompt"]), "max_new": req["max_new"],
            "t_sent": None, "t_tokens": [], "t_done": None, "tokens": None,
            "timing": None, "status": None, "error": None}


def run_open(host, port, reqs, t0, seconds, grace):
    recs, threads = [], []
    give_up = t0 + seconds + grace
    for req in reqs:
        if req["due_s"] >= seconds:
            break
        wait = t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        rec = new_record(req)
        recs.append(rec)
        th = threading.Thread(target=post_generate,
                              args=(host, port, req, rec, give_up), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(max(0.0, give_up - time.monotonic()) + 1.0)
    return recs


def run_closed(host, port, reqs, t0, seconds, grace):
    recs, lock = [], threading.Lock()
    give_up = t0 + seconds + grace
    by_client = {}
    for req in reqs:
        by_client.setdefault(req["client"], []).append(req)

    def client(mine):
        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        k = 0
        while time.monotonic() < t0 + seconds:
            req = dict(mine[k % len(mine)])
            req["id"] = f"{req['id']}.{k // len(mine)}"
            rec = new_record(req)
            rec["round"] = k
            with lock:
                recs.append(rec)
            post_generate(host, port, req, rec, give_up)
            if rec["error"]:
                time.sleep(0.05)    # a refusing server is not hammered
            k += 1

    threads = [threading.Thread(target=client, args=(mine,), daemon=True)
               for mine in by_client.values()]
    for th in threads:
        th.start()
    for th in threads:
        th.join(max(0.0, give_up - time.monotonic()) + 1.0)
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mix", required=True, help="traffic mix, a JSON file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--start-at", type=float, required=True,
                    help="time.monotonic() at which the window opens")
    ap.add_argument("--grace", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    with open(a.mix) as fh:
        mix = json.load(fh)
    reqs = traffic.make_requests(mix, a.seed, a.vocab, a.seconds)
    ready = time.monotonic()
    runner = run_open if mix["kind"] == "serve_open" else run_closed
    recs = runner(a.host, a.port, reqs, a.start_at, a.seconds, a.grace)
    with open(a.out, "w") as fh:
        json.dump({"t0": a.start_at, "seconds": a.seconds,
                   "ready_before_start_s": a.start_at - ready,
                   "records": recs}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
