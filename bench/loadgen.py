"""Open-loop load generator for the suggestion service's v1 HTTP API.

    python bench/loadgen.py < job.json

Runs in its own process and never imports JAX, so it neither holds the
chip nor shares the server's interpreter lock.  It speaks the wire
format of API.md with ``http.client`` (suggest, then observe after the
trial time), one keep-alive connection per worker thread.

The job arrives as one JSON line on standard input, after the server is
up (the process is started early so that its imports are done by then):

    {"url", "exp_ids", "space", "centers", "warm", "window", "t0",
     "seconds", "timeout_s", "wait_s", "seed", "workers"}

``warm`` and ``window`` are schedules from ``bench/traffic.py``; the
warm-up runs in [t0 - warm_s, t0) and the window in [t0, t0 + seconds),
on the shared monotonic clock.  Every suggest is timed from when it was
due.  The generator waits up to ``wait_s`` for an answer (a late answer
is late, not lost) and counts one that takes longer than ``timeout_s``
as failed at ``timeout_s``.  Observes due after the window are not sent
(those trials are still running).  The last line of standard output is
one JSON object with every request's record.
"""
from __future__ import annotations

import heapq
import http.client
import json
import math
import queue
import sys
import threading
import time
import urllib.parse

import numpy as np


def to_unit(space, assignment):
    """The unit-cube coordinates of an assignment (the space's codec for
    double and int parameters, linear or log)."""
    out = []
    for p in space:
        lo, hi = p["bounds"]
        v = float(assignment[p["name"]])
        if p.get("log"):
            out.append((math.log(v) - math.log(lo))
                       / (math.log(hi) - math.log(lo)))
        else:
            out.append((v - lo) / (hi - lo))
    return np.asarray(out)


def objective(u, center, noise):
    """Seeded synthetic objective per experiment (maximised): a bowl
    around the experiment's optimum, a ripple in every dimension, and
    trial-to-trial noise of a twentieth of the bowl's depth, as repeated
    training runs of one configuration scatter.  (Near noise-free smooth
    objectives drive the GP's fitted noise to its floor; see PERF.md.)"""
    ripple = np.sin(5.0 * u + 6.2832 * center).sum() / math.sqrt(len(u))
    return float(-((u - center) ** 2).sum() + 0.2 * ripple + 0.05 * noise)


class Conn:
    def __init__(self, url: str, wait_s: float):
        p = urllib.parse.urlparse(url)
        self.host, self.port, self.wait_s = p.hostname, p.port, wait_s
        self.c = None

    def call(self, path: str, payload: dict):
        body = json.dumps(payload).encode()
        for attempt in range(2):
            if self.c is None:
                self.c = http.client.HTTPConnection(self.host, self.port,
                                                    timeout=self.wait_s)
            try:
                self.c.request("POST", path, body,
                               {"Content-Type": "application/json"})
                r = self.c.getresponse()
                data = r.read()
                return r.status, json.loads(data or b"{}")
            except (ConnectionError, http.client.BadStatusLine,
                    http.client.CannotSendRequest):
                # a keep-alive socket the server closed: reconnect once
                self.c.close()
                self.c = None
                if attempt:
                    raise
            except Exception:
                self.c.close()
                self.c = None
                raise
        raise RuntimeError("unreachable")


def run(job: dict) -> dict:
    space = job["space"]
    centers = np.asarray(job["centers"])
    ids = job["exp_ids"]
    t0, seconds = job["t0"], job["seconds"]
    timeout_s, wait_s = job["timeout_s"], job["wait_s"]
    seed = job["seed"] & (2**63 - 1)
    events = []                  # heap of (due, seq, kind, payload)
    cv = threading.Condition()
    seq = 0
    for phase, sched, start in (("warm", job["warm"], t0 - job["warm_s"]),
                                ("window", job["window"], t0)):
        for i, s in enumerate(sched):
            seq += 1
            events.append((start + s["t"], seq, "suggest",
                           dict(s, phase=phase, i=i)))
    heapq.heapify(events)
    n_suggest = len(events)
    work: "queue.Queue" = queue.Queue()
    suggests, observes = [], []
    lock = threading.Lock()
    state = {"done": 0, "seq": seq}
    end = t0 + seconds

    def schedule_observe(rec, s, due):
        if due >= end:
            return
        with cv:
            state["seq"] += 1
            heapq.heappush(events, (due, state["seq"], "observe",
                                    {"rec": rec, "s": s}))
            cv.notify()

    def do_suggest(conn, due, ev):
        exp = ids[ev["exp"]]
        rec = {"phase": ev["phase"], "exp": ev["exp"], "due": due,
               "sent": time.monotonic()}
        try:
            status, body = conn.call(f"/v1/experiments/{exp}/suggestions",
                                     {"count": 1})
            rec["status"] = status
            rec["suggestions"] = body.get("suggestions", []) \
                if status == 200 else []
            if status != 200:
                rec["error"] = body
        except Exception as e:  # noqa: every failure is a counted record
            rec["status"] = None
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["suggestions"] = []
        rec["done"] = time.monotonic()
        with lock:
            suggests.append(rec)
        noise = np.random.default_rng(
            [seed, int(ev["phase"] == "window"), ev["i"]]).standard_normal()
        for s in rec["suggestions"]:
            schedule_observe(rec, dict(s, noise=float(noise)),
                             rec["done"] + ev["trial_s"])

    def do_observe(conn, due, ev):
        rec, s = ev["rec"], ev["s"]
        exp = ids[rec["exp"]]
        u = to_unit(space, s["assignment"])
        value = objective(u, centers[rec["exp"]], s["noise"])
        orec = {"exp": rec["exp"], "due": due, "sent": time.monotonic(),
                "suggestion_id": s["suggestion_id"], "value": value}
        try:
            status, body = conn.call(
                f"/v1/experiments/{exp}/observations",
                {"suggestion_id": s["suggestion_id"],
                 "assignment": s["assignment"], "value": value,
                 "trial_id": s["suggestion_id"]})
            orec["status"] = status
            orec["accepted"] = bool(body.get("accepted")) \
                if status == 200 else False
        except Exception as e:  # noqa: every failure is a counted record
            orec["status"] = None
            orec["error"] = f"{type(e).__name__}: {e}"
            orec["accepted"] = False
        orec["done"] = time.monotonic()
        with lock:
            observes.append(orec)

    def worker():
        conn = Conn(job["url"], wait_s)
        while True:
            item = work.get()
            if item is None:
                return
            due, kind, ev = item
            if kind == "suggest":
                do_suggest(conn, due, ev)
                with cv:
                    state["done"] += 1
                    cv.notify_all()
            else:
                do_observe(conn, due, ev)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(job["workers"])]
    for t in threads:
        t.start()
    # dispatcher: hand each event to the pool at its due time
    while True:
        with cv:
            if not events:
                if state["done"] >= n_suggest:
                    break
                cv.wait(0.05)
                continue
            due = events[0][0]
            now = time.monotonic()
            if due > now:
                cv.wait(min(due - now, 0.05))
                continue
            due, _, kind, ev = heapq.heappop(events)
        work.put((due, kind, ev))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(wait_s + 5.0)
    return {"suggests": suggests, "observes": observes,
            "timeout_s": timeout_s}


def main() -> int:
    job = json.loads(sys.stdin.readline())
    out = run(job)
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
