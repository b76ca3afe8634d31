"""Closed-loop load for the recommend service, run as a child process that
never imports JAX.

    python3 -m bench.loadgen <plan.json>

The plan gives the URL, the number of clients, the window's length and
the query mix.  Each client sends its next request when the previous one
has been answered, from a stream of requests of its own drawn from the
seed, until the window has passed.  Prints one JSON line: the window's
start and end on the host clock, every request's latency and outcome,
and the answers of a sample of requests drawn from the seed.
"""
from __future__ import annotations

import json
import random
import sys
import threading
import time
import urllib.error
import urllib.request


def zipf_weights(n: int, s: float):
    return [1.0 / (k + 1) ** s for k in range(n)]


def make_request(rng: random.Random, plan: dict) -> list:
    """1 to ``max_queries`` queries, each in the grid (with a power budget
    inside the cell's frontier range) or a surrogate fallback (an arch
    drawn by Zipf rank from the fallback list)."""
    mix = plan["mix"]
    out = []
    for _ in range(rng.randint(1, mix["max_queries"])):
        node = rng.choice(mix["nodes"])
        if rng.random() < mix["in_grid_share"]:
            lo, hi = plan["power_range"][str(node)]
            out.append(dict(arch=mix["grid_arch"], node_nm=node,
                            mode=mix["mode"],
                            power_budget_mw=lo + (hi - lo) * rng.random()))
        else:
            arch = rng.choices(mix["fallback_archs"],
                               weights=zipf_weights(
                                   len(mix["fallback_archs"]),
                                   mix["zipf_s"]))[0]
            out.append(dict(arch=arch, node_nm=node, mode=mix["mode"]))
    return out


def client(i: int, plan: dict, t_end: float, log: list, kept: list,
           lock: threading.Lock) -> None:
    rng = random.Random(plan["seed"] * 1009 + i)
    keep = random.Random(plan["seed"] * 7919 + i)
    url = plan["url"] + "/recommend"
    while time.time() < t_end:
        queries = make_request(rng, plan)
        body = json.dumps({"queries": queries}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        t = time.time()
        try:
            with urllib.request.urlopen(req, timeout=plan["timeout_s"]) as r:
                status, payload = r.status, r.read()
        except urllib.error.HTTPError as e:
            status, payload = e.code, b""
        except OSError:
            status, payload = 0, b""
        done = time.time()
        with lock:
            log.append([t, done, status, len(queries)])
            if status == 200 and keep.random() < plan["sample_share"]:
                kept.append(dict(queries=queries,
                                 answers=json.loads(payload)["answers"]))


def main(path: str) -> None:
    with open(path) as f:
        plan = json.load(f)
    log, kept, lock = [], [], threading.Lock()
    t0 = time.time()
    t_end = t0 + plan["seconds"]
    threads = [threading.Thread(target=client,
                                args=(i, plan, t_end, log, kept, lock))
               for i in range(plan["clients"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t1 = max([t0] + [rec[1] for rec in log])
    print(json.dumps(dict(t0=t0, t1=t1, requests=log, sample=kept)),
          flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
