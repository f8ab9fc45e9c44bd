"""Closed loop: callers that wait for a reply (batch and agent pipelines).
`clients` clients (or `clients_per_lane` x lanes) each send their next
request when the last one completes; no request starts after the window's
end. Sizes come from a pool of `pool` (about what a window uses), in turn. The clients
start `ramp_s` seconds before the window, which counts as set-up: filling
every lane takes one admission each, and a window that began on empty lanes
would measure the filling."""

from __future__ import annotations

import threading
import time

from benchmark.harness import traffic as tr

START_GAP_S = 0.01  # clients start apart: the server listens with a queue of 5


def drive(traffic: dict, cell: dict, rng, seconds: float, send, lanes: int,
          clock: dict) -> dict:
    size = tr.sizes(traffic, traffic["pool"], rng)
    n_clients = traffic.get("clients") or int(traffic["clients_per_lane"] * lanes)
    records: list = []
    lock = threading.Lock()
    nxt = [0]
    clock["t0"] = t0 = time.monotonic() + traffic["ramp_s"]
    t1 = t0 + seconds

    def client() -> None:
        while True:
            now = time.monotonic()
            with lock:
                i = nxt[0]
                if now >= t1:
                    return
                nxt[0] += 1
            n_prompt, n_out = size[i % len(size)]
            rec = send(i, n_prompt, n_out, now)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(n_clients)]
    for th in threads:
        th.start()
        time.sleep(START_GAP_S)
    for th in threads:
        th.join()
    records.sort(key=lambda r: r["due"])
    return {"t0": t0, "t1": t1, "records": records}
