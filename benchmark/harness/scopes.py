"""Device seconds of a traced run by the program's nested `jax.named_scope`s.

`hostspans.by_scope` keeps a layer's first scope (`layers/attn`). The
readers of one kernel's share need the scopes below it: which attention
(`attn/full_decode`, `attn/window_decode`), which part of the expert layer
(`moe/decode/routed`, `moe/decode/shared`). `table` reduces the trace once
to seconds by the whole path of scopes under `layers` and keeps it beside
the run's other tables; a trace without the layers' scopes gives none.
"""

from __future__ import annotations

import json
import os

from benchmark.harness import hostspans, rundir, xplane

TABLE = "device_by_scope_path.json"
_NOT_SCOPES = ("while", "body", "cond", "closed_call", "checkpoint")


def path_of(op_name: str) -> str | None:
    """`attn/window_decode` for `jit(block)/.../layers/while/body/cond/
    branch_1_fun/attn/window_decode/dot_general`: the parts under `layers`
    that a `jax.named_scope` put there, without the traced function's own
    (`jit(..)`, loops, branches, the primitive's name last)."""
    parts = op_name.split(";")[0].split("/")
    if hostspans.LAYERS not in parts:
        return None
    inner = [p for p in parts[parts.index(hostspans.LAYERS) + 1:-1]
             if not (p.startswith(("jit(", "branch_", "pjit")) or p in _NOT_SCOPES)]
    return "/".join(inner)


def table(run_dir: str) -> dict | None:
    """{scope path: device seconds, averaged over planes} of the traced slice."""
    path = os.path.join(run_dir, TABLE)
    if not os.path.exists(path):
        profile = os.path.join(run_dir, "profile")
        if rundir.window(run_dir).get("trace_t0") is None or not os.path.isdir(profile):
            return None
        _, lines, ops = hostspans.load(profile)
        n_planes = len({p for p, _, _ in lines}) or 1
        seconds: dict[str, float] = {}
        for _, event, dur, op_name in ops:
            key = None if xplane.is_container(event) else path_of(op_name)
            if key is not None:
                seconds[key] = seconds.get(key, 0.0) + dur / 1e9 / n_planes
        with open(path, "w") as f:
            json.dump(seconds, f, indent=1)
    with open(path) as f:
        return json.load(f) or None


def seconds_under(run_dir: str, *scopes: str) -> float | None:
    """Device seconds of the operations under any of `scopes` (each a path
    such as `attn/full_decode`, matched as a run of whole parts)."""
    t = table(run_dir)
    if not t:
        return None
    wanted = [s.split("/") for s in scopes]

    def under(path: str) -> bool:
        parts = path.split("/")
        return any(parts[i:i + len(w)] == w for w in wanted
                   for i in range(len(parts) - len(w) + 1))

    found = [s for path, s in t.items() if under(path)]
    return sum(found) if found else None
