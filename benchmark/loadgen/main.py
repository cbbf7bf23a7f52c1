"""The load generator: a process of its own that never imports JAX.

    python benchmark/loadgen/main.py --url URL --workload-file F --vocab V
                                     --seed N --seconds S

Builds the whole schedule from the seed, prints ``{"t0": <monotonic>}``
when its clock starts, sends every request over HTTP with ``stream:
true``, stamps every token as it arrives, and prints one JSON object with
everything it saw as its last line.  An open loop times each request from
the instant it was due; how late the generator itself ran is reported.
``time.monotonic()`` is the machine's CLOCK_MONOTONIC, so the run's
process reads the same clock.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import schedule


class Client:
    def __init__(self, url: str, t0: float, stop_at: float, timeout: float):
        u = urllib.parse.urlparse(url)
        self.host, self.port, self.path = u.hostname, u.port, u.path
        self.t0, self.stop_at, self.timeout = t0, stop_at, timeout

    def send(self, req: dict, cut_at_stop: bool) -> dict:
        """One streamed request.  Returns when the stream is done, fails,
        or (closed loop) the window has ended, in which case the
        connection is closed and the server cancels the sequence."""
        out = {"id": req["id"], "sent": time.monotonic() - self.t0,
               "status": None, "tokens": [], "t_tokens": [], "done": False,
               "cut": False, "error": None}
        body = json.dumps({"tokens": req["tokens"],
                           "maxNewTokens": req["max_new"], "stream": True})
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            conn.request("POST", self.path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out["status"] = resp.status
            if resp.status != 200:
                out["error"] = resp.read().decode("utf-8", "replace")[:200]
                return out
            while True:
                line = resp.readline()
                now = time.monotonic()
                if not line:
                    break
                if line.startswith(b"{"):
                    obj = json.loads(line)
                    if "token" in obj:
                        out["tokens"].append(obj["token"])
                        out["t_tokens"].append(now - self.t0)
                    elif obj.get("done"):
                        out["done"] = True
                        resp.read()     # the closing chunk, so that the
                        break           # socket closes without a reset
                    elif "error" in obj:
                        out["error"] = str(obj["error"])[:200]
                        break
                if cut_at_stop and now >= self.stop_at:
                    out["cut"] = True
                    break
        except (OSError, http.client.HTTPException, ValueError) as e:
            out["error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            conn.close()
        return out


def run_open(sched: dict, client: Client, drain_s: float) -> list:
    results, threads = [], []
    lock = threading.Lock()

    def one(req):
        r = client.send(req, cut_at_stop=False)
        r["due"] = req["due"]
        with lock:
            results.append(r)

    for req in sched["requests"]:
        wait = client.t0 + req["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    deadline = client.stop_at + drain_s
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    with lock:
        seen = {r["id"] for r in results}
        for req in sched["requests"]:
            if req["id"] not in seen:       # still running at the deadline
                results.append({"id": req["id"], "due": req["due"],
                                "sent": None, "status": None, "tokens": [],
                                "t_tokens": [], "done": False, "cut": False,
                                "error": "not finished when the drain ended"})
        return sorted(results, key=lambda r: r["id"])


def run_closed(sched: dict, client: Client, clients: int) -> list:
    results = []
    lock = threading.Lock()

    def loop(c):
        for req in (r for r in sched["requests"] if r["client"] == c):
            if time.monotonic() >= client.stop_at:
                return
            r = client.send(req, cut_at_stop=True)
            r["client"] = c
            with lock:
                results.append(r)

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(max(0.0, client.stop_at + 30.0 - time.monotonic()))
    with lock:
        return sorted(results, key=lambda r: r["id"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--workload-file", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="the knee sweep's override of the open loop's "
                    "rate; a cell's run never passes it")
    args = ap.parse_args(argv)
    with open(args.workload_file, encoding="utf-8") as f:
        traffic = json.load(f)["traffic"]
    if args.rate is not None:
        traffic["arrivals"] = dict(traffic["arrivals"], rate=args.rate)
    sched = schedule.build(traffic, args.vocab, args.seed, args.seconds)
    t0 = time.monotonic()
    print(json.dumps({"t0": t0, "requests": len(sched["requests"])}),
          flush=True)
    stop_at = t0 + sched["ramp_s"] + sched["window_s"]
    client = Client(args.url, t0, stop_at, timeout=traffic["timeout_s"])
    if sched["kind"] == "poisson":
        results = run_open(sched, client, traffic["drain_s"])
    else:
        results = run_closed(sched, client, traffic["arrivals"]["clients"])
    print(json.dumps({"t0": t0, "results": results}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
