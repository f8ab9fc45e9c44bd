"""trace-purity: no host side effects inside JAX-traced functions.

Anything executed under ``jax.jit`` / ``shard_map`` / Pallas tracing
runs ONCE, at trace time — a ``time.monotonic()`` read, a metrics
``.inc()``, a lock acquisition or a ``self.X = ...`` mutation inside a
traced function is silently burned into the compiled program: it fires
at compile, never per step, and usually "works" until someone trusts
the number. The engine's decode block compiles on a background prefetch
thread, so a lock taken at trace time can even deadlock against the
dispatch path.

Traced functions are found three ways, then closed transitively over
same-module calls:

* decorated with ``@jax.jit`` / ``@partial(jax.jit, ...)`` /
  ``@functools.partial(jax.jit, ...)``;
* passed to ``jax.jit(...)``, ``pl.pallas_call(...)``,
  ``shard_map(...)`` (bare name or wrapped in ``partial``);
* called by name from an already-traced function in the same module.

Flagged inside a traced body: ``time.*`` clock calls, metric/recorder
side effects (``.inc``/``.observe``/``.labels``/``.record``,
``get_registry``/``get_recorder``/``get_span_tracker``), lock
acquisition (``with self._lock`` or any ``threading.*`` use),
``print``, ``logging``/``logger`` calls, ``os.environ`` reads, and
``self.X = ...`` host-state mutation. ``jax.debug.print`` /
``pl.debug_print`` are the sanctioned in-trace debug tools and are not
flagged.
"""

from __future__ import annotations

import ast
from typing import Iterable

from .core import Finding, Rule, SourceModule, dotted

TRACER_TAILS = {"jit", "pallas_call", "shard_map"}
METRIC_METHODS = {"inc", "observe", "labels", "record"}
OBS_GETTERS = {"get_registry", "get_recorder", "get_span_tracker"}


def _mentions_jit(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == "jit":
            if isinstance(n.value, ast.Name) and n.value.id == "jax":
                return True
        if isinstance(n, ast.Name) and n.id == "pallas_call":
            return True
        if isinstance(n, ast.Attribute) and n.attr == "pallas_call":
            return True
    return False


def _fn_names_from_arg(arg: ast.AST) -> list[str]:
    """Function names a tracer call-site argument refers to: a bare name
    or one wrapped in functools.partial(name, ...)."""
    if isinstance(arg, ast.Name):
        return [arg.id]
    if isinstance(arg, ast.Call):
        fn = arg.func
        tail = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else ""
        )
        if tail == "partial" and arg.args:
            return _fn_names_from_arg(arg.args[0])
    return []


class TracePurityRule(Rule):
    name = "trace-purity"
    description = (
        "functions reaching jax.jit/shard_map/pallas must not touch "
        "locks, metrics, time.*, or host-side state"
    )

    def check_module(self, mod: SourceModule) -> Iterable[Finding]:
        index: dict[str, list] = {}
        for n in ast.walk(mod.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                index.setdefault(n.name, []).append(n)

        traced: dict[int, ast.AST] = {}

        def mark(fn):
            traced.setdefault(id(fn), fn)

        for fns in index.values():
            for fn in fns:
                if any(_mentions_jit(d) for d in fn.decorator_list):
                    mark(fn)
        for n in ast.walk(mod.tree):
            if isinstance(n, ast.Call):
                tail = dotted(n.func).split(".")[-1]
                if tail in TRACER_TAILS and n.args:
                    for name in _fn_names_from_arg(n.args[0]):
                        for fn in index.get(name, ()):
                            mark(fn)

        # transitive: traced code calling a same-module function by name
        work = list(traced.values())
        while work:
            fn = work.pop()
            for n in ast.walk(fn):
                if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                    for callee in index.get(n.func.id, ()):
                        if id(callee) not in traced:
                            traced[id(callee)] = callee
                            work.append(callee)

        for fn in traced.values():
            yield from self._check_traced(mod, fn)

    # -- impurity scan ------------------------------------------------------

    def _check_traced(
        self, mod: SourceModule, fn: ast.FunctionDef | ast.AsyncFunctionDef
    ) -> Iterable[Finding]:
        where = f"JAX-traced function {fn.name}()"
        for n in ast.walk(fn):
            if isinstance(n, ast.Call):
                name = dotted(n.func)
                parts = name.split(".")
                if parts[0] == "time" and len(parts) == 2:
                    yield mod.finding(
                        self.name, n,
                        f"{name}() inside {where}: the clock is read once "
                        f"at trace time, not per step",
                    )
                elif parts[-1] in METRIC_METHODS and parts[0] in (
                    "self", "obs",
                ) or name.startswith(("logger.", "logging.")):
                    yield mod.finding(
                        self.name, n,
                        f"{name}() inside {where}: host side effect fires "
                        f"at trace time, not per step",
                    )
                elif parts[-1] in OBS_GETTERS:
                    yield mod.finding(
                        self.name, n,
                        f"{name}() inside {where}: observability handles "
                        f"must stay outside traced code",
                    )
                elif name == "print":
                    yield mod.finding(
                        self.name, n,
                        f"print() inside {where}: prints once at trace "
                        f"time — use jax.debug.print for in-trace output",
                    )
                elif parts[0] == "threading" or parts[0] == "_threading":
                    yield mod.finding(
                        self.name, n,
                        f"{name}() inside {where}: threading primitives "
                        f"must not be created or used under tracing",
                    )
            elif isinstance(n, (ast.With, ast.AsyncWith)):
                for item in n.items:
                    ce_name = dotted(item.context_expr)
                    if "lock" in ce_name.lower():
                        yield mod.finding(
                            self.name, item.context_expr,
                            f"lock acquisition ({ce_name}) inside {where}: "
                            f"acquired once at trace time and can deadlock "
                            f"the prefetch compile thread",
                        )
            elif isinstance(n, ast.Attribute):
                if (
                    isinstance(n.ctx, (ast.Store, ast.Del))
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self"
                ):
                    yield mod.finding(
                        self.name, n,
                        f"self.{n.attr} mutated inside {where}: host-side "
                        f"state written at trace time, not per step",
                    )
                elif (
                    n.attr == "environ"
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "os"
                ):
                    yield mod.finding(
                        self.name, n,
                        f"os.environ read inside {where}: environment is "
                        f"captured once at trace time",
                    )
