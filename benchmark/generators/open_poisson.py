"""Open loop: independent users. Requests are sent on a schedule whether or
not earlier ones have finished, and each is timed from when it was due."""

from __future__ import annotations

import threading
import time

from benchmark.harness import traffic as tr


def drive(traffic: dict, cell: dict, rng, seconds: float, send, lanes: int,
          clock: dict) -> dict:
    """Send the window's requests at their due times; returns when all have
    ended. `send(i, prompt_tokens, output_tokens, due)` blocks for one
    request and returns its record."""
    due = tr.poisson_arrivals(cell["rate"], seconds, rng)
    size = tr.sizes(traffic, len(due), rng)
    records: list = [None] * len(due)
    threads = []
    clock["t0"] = t0 = time.monotonic()

    def one(i: int) -> None:
        records[i] = send(i, size[i][0], size[i][1], t0 + due[i])

    for i, d in enumerate(due):
        wait = t0 + d - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(i,), name=f"bench-req-{i}")
        th.start()
        threads.append(th)
    t1 = t0 + seconds
    for th in threads:
        th.join()
    return {"t0": t0, "t1": t1, "records": records}
