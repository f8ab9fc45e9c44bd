#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: one process, weights loaded once,
the cell's traffic at each of a few rates.

    python3 benchmark/sweep.py --workload mistral7b-chat --rates 0.6,0.9,1.2,1.5,1.8 --seconds 25

Prints one line per rate: requests due and finished by the window's end, the
median queue wait of the requests due in its middle and in its last third,
the client's latencies and how long the drain took. The knee is read from
the table: the highest rate at which the queue wait stays flat from the
middle to the last third and well under a request's service time. (ISSUE
23's "95% of the requests due had finished by the window's end" cannot hold
in a 30 s window when a request takes 3 to 5 s.) The cell file then holds a
fraction of the knee as a number, and the table goes to PERF.md. Nothing
here is a result.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as bench  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=101)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell, cfg, traffic = bench.load_cell(args.workload, args.rehearse)
    session = bench.Session(cell, cfg, traffic, args.seed, args.rehearse, False)
    try:
        from dllama_tpu.obs.trace import read_jsonl

        for rate in (float(r) for r in args.rates.split(",")):
            cell["rate"] = rate
            session.seed += 1  # other request ids and another order per rate
            win = session.window(args.seconds, False)
            t0, t1, recs = win["t0"], win["t1"], win["records"]
            server = {s["request_id"]: s for s in read_jsonl(session.served.trace_path)}
            due = [r for r in recs if r["in_window"]]
            done_in = [r for r in due if not r["failed"] and r["done"] <= t1]
            third = (t1 - t0) / 3

            def waits(lo, hi):
                return [server[r["id"]]["queue_wait_s"] * 1e3 for r in due
                        if lo <= r["due"] - t0 < hi and r["id"] in server]

            mid, last = waits(third, 2 * third), waits(2 * third, 3 * third)
            e2e = bench.end_to_end(recs, t0, t1)
            row = {
                "rate": rate, "due": len(due), "finished_by_end": len(done_in),
                "failed": sum(r["failed"] for r in due),
                "queue_wait_ms_mid": statistics.median(mid) if mid else None,
                "queue_wait_ms_last": statistics.median(last) if last else None,
                **{k: round(v, 2) for k, v in e2e.items()},
                "drain_s": round(max(r["done"] for r in recs) - t1, 2),
            }
            print("sweep " + json.dumps(row), flush=True)
    finally:
        session.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
