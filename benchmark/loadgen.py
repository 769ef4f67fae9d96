#!/usr/bin/env python3
"""The load generator: a child process that never imports jax.

It shares no interpreter lock with the engine's driver thread and holds no
chip. The harness starts it with the mix, the seed and the window; it builds
the same plan the harness can rebuild (``traffic.plan``), prints ``READY``,
reads from its stdin the absolute instant ``t0`` at which the window opens,
waits for it, sends over
loopback HTTP (never through a proxy), drains what is in flight when the
window closes (bounded), and prints ONE JSON object on its stdout:

  {"t0", "seconds", "late_start_s", "requests": [
      {"i", "due", "sent", "done", "status", "n_out", "ttft_s",
       "latency_s", "preemptions", "tokens"}]}

``due``/``sent``/``done`` are seconds after t0 on this process's clock.
``status``: "ok", "shed" (503), "timeout" (504 or no reply inside the
request's bound), "error" (anything else), "unsent" (the window closed
first; closed loop only).
"""
import argparse
import http.client
import json
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import traffic  # noqa: E402


class Sender:
    def __init__(self, host, port, t0, request_timeout_s):
        self.host, self.port, self.t0 = host, port, t0
        self.timeout = request_timeout_s
        self.local = threading.local()

    def _conn(self):
        c = getattr(self.local, "conn", None)
        if c is None:
            c = http.client.HTTPConnection(self.host, self.port,
                                           timeout=self.timeout)
            self.local.conn = c
        return c

    def send(self, req, due):
        """One request; returns its record."""
        body = json.dumps({"tokens": req["tokens"],
                           "max_new_tokens": req["max_new_tokens"]})
        rec = {"i": req["i"], "due": due, "asked": req["max_new_tokens"]}
        rec["sent"] = time.time() - self.t0
        try:
            c = self._conn()
            c.request("POST", "/generate", body,
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            data = r.read()
            rec["done"] = time.time() - self.t0
            if r.status == 200:
                reply = json.loads(data)
                rec.update(status="ok", tokens=reply["tokens"],
                           n_out=len(reply["tokens"]),
                           ttft_s=reply["ttft_s"],
                           latency_s=reply["latency_s"],
                           preemptions=reply["preemptions"])
            else:
                rec["status"] = {503: "shed", 504: "timeout"}.get(
                    r.status, "error")
                rec["http"] = r.status
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["done"] = time.time() - self.t0
            rec["status"] = ("timeout" if isinstance(e, TimeoutError)
                             else "error")
            rec["error"] = repr(e)
            self.local.conn = None
        return rec


def run_closed(mix, requests, sender, seconds):
    """``clients`` callers over one shared list: each takes the next request
    the moment its reply arrives, until the window closes."""
    lock = threading.Lock()
    nxt = [0]
    records = []

    def client():
        due = 0.0
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            now = time.time() - sender.t0
            if now >= seconds or i >= len(requests):
                return
            rec = sender.send(requests[i], max(due, 0.0))
            with lock:
                records.append(rec)
            due = rec["done"]

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(int(mix["clients"]))]
    for t in threads:
        t.start()
    return threads, records


def run_open(mix, requests, sender, seconds):
    """Requests go out when they are due, whether or not earlier ones have
    been answered: a pool of workers takes them from a queue the scheduler
    fills on time. A worker stamps ``sent`` as it sends, so a starved pool
    or a late scheduler shows as lateness."""
    todo = queue.Queue()
    lock = threading.Lock()
    records = []

    def worker():
        while True:
            req = todo.get()
            if req is None:
                return
            rec = sender.send(req, req["due"])
            with lock:
                records.append(rec)

    workers = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(mix.get("max_inflight", 256)))]
    for w in workers:
        w.start()
    for req in requests:
        wait = sender.t0 + req["due"] - time.time()
        if wait > 0:
            time.sleep(wait)
        todo.put(req)
    for _ in workers:
        todo.put(None)
    return workers, records


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    args = ap.parse_args(argv)

    with open(args.mix) as f:
        mix = json.load(f)
    requests = traffic.plan(mix, args.seed, args.seconds, args.vocab)
    drain_s = float(mix.get("drain_s", 60.0))
    # the plan is made; the harness now fixes the instant the window opens
    sys.stdout.write("READY\n")
    sys.stdout.flush()
    t0 = float(sys.stdin.readline())
    sender = Sender(args.host, args.port, t0,
                    request_timeout_s=args.seconds + drain_s)
    late_start = max(0.0, time.time() - t0)
    if mix["loop"] == "closed":
        time.sleep(max(0.0, t0 - time.time()))
        threads, records = run_closed(mix, requests, sender, args.seconds)
    else:   # the scheduler itself waits for each due instant
        threads, records = run_open(mix, requests, sender, args.seconds)
    deadline = t0 + args.seconds + drain_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.time()))
    hung = sum(t.is_alive() for t in threads)
    records.sort(key=lambda r: r["i"])
    json.dump({"t0": t0, "seconds": args.seconds,
               "late_start_s": late_start, "hung_threads": hung,
               "planned": len(requests), "requests": records}, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    # daemon threads still blocked on a reply die with the process
    return 0


if __name__ == "__main__":
    sys.exit(main())
