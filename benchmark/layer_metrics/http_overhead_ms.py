"""Client time to first token (from send, not from due) minus the server's
own, median over the window's requests: what the HTTP handler, SSE framing
and the client's socket add."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "HTTP front", "ms", "lower", "host_clock", "ttft_mean_ms"


def read(run_dir):
    server = {r["request_id"]: r for r in rundir.server_records(run_dir)}
    return rundir.median(
        (r["first"] - r["sent"]) * 1e3 - server[r["id"]]["ttft_s"] * 1e3
        for r in rundir.requests(run_dir)
        if r["first"] and r["id"] in server and server[r["id"]]["ttft_s"]
    )
