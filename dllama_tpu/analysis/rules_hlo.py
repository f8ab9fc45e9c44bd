"""The compiled-program (HLO) rules behind xlalint.

Each rule sees one :class:`~.xlalint.HloProgram` — optimized HLO text +
XLA cost analysis + the engine-derived :class:`~.xlalint.FamilyPolicy`
— and yields :class:`~.xlalint.HloFinding`s whose messages are
deliberately line-free and value-free (raw numbers ride in the
``detail`` field) so baseline fingerprints survive backend and version
churn. The text parsers at the top are shared with
``tests/test_parallel.py``'s sharding census tests, which used to carry
their own one-off regexes.

What the parsers rely on (validated against the optimized HLO jax
emits on CPU and TPU):

* ops appear as ``%name = TYPE[dims]{layout} op-name(...)`` one per
  line; async collectives split into ``op-start``/``op-done`` pairs
  (normalized to the base op here, and ``-done`` lines skipped so one
  async collective is counted once);
* donation shows up on the ``HloModule`` header line as
  ``input_output_alias={ {0}: (13, {}, may-alias), ... }`` with one
  ``{output_index}: (...)`` entry per donated leaf;
* host callbacks (``jax.pure_callback`` & co.) lower to custom-calls
  whose target names a callback/host transfer — while Pallas kernels
  are custom-calls too (``tpu_custom_call``), so host detection matches
  a denylist of target substrings, never "any custom-call".
"""

from __future__ import annotations

import re
from typing import Iterable, Iterator

from .xlalint import FamilyPolicy, HloFinding, HloProgram, HloRule

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "collective-broadcast",
    "all-to-all",
)

# %name = <result types...> op-name(   — with optional async suffix.
# The result segment (group 1) is everything between "=" and the op
# token; it may be a bare shape or a tuple of shapes for -start forms.
_COLLECTIVE_RE = re.compile(
    r"=\s*([^=]*?)\s*\b("
    + "|".join(re.escape(op) for op in COLLECTIVE_OPS)
    + r")(-start|-done)?\("
)

_HOST_OP_RE = re.compile(
    r"=\s*[^=]*?\s*\b(infeed|outfeed|send|recv)(-done)?\("
)

_SHAPE_RE = re.compile(r"\b(pred|[a-z]+[0-9]+(?:e[0-9]+m[0-9]+\w*)?)\[([0-9,]*)\]")

_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')

_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}\s*:\s*\(")

#: custom-call target substrings that mean "leaves the device for the
#: host". Pallas ("tpu_custom_call") and cuDNN/oneDNN math targets
#: deliberately do NOT match.
HOST_TARGET_MARKERS = ("callback", "infeed", "outfeed", "host")

_DTYPE_BITS = {
    "pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8, "s16": 16, "u16": 16,
    "s32": 32, "u32": 32, "s64": 64, "u64": 64,
    "f16": 16, "bf16": 16, "f32": 32, "f64": 64,
}


def dtype_bits(dtype: str) -> int:
    """Storage bits of an HLO element type name (f8E4M3 variants parse
    as 8; unknown names report 0 = never over any limit)."""
    if dtype in _DTYPE_BITS:
        return _DTYPE_BITS[dtype]
    m = re.match(r"[a-z]+(\d+)", dtype)
    return int(m.group(1)) if m else 0


def strip_strings(txt: str) -> str:
    """HLO text with every quoted string blanked, so op scans never
    match inside metadata/backend_config payloads."""
    return re.sub(r'"[^"]*"', '""', txt)


def parse_shapes(segment: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every ``dtype[d0,d1,...]`` in a result segment as
    (dtype, dims) — scalars parse as empty dims."""
    out: list[tuple[str, tuple[int, ...]]] = []
    for m in _SHAPE_RE.finditer(segment):
        dims = tuple(int(d) for d in m.group(2).split(",") if d.strip())
        out.append((m.group(1), dims))
    return out


def iter_collectives(
    hlo_text: str,
) -> Iterator[tuple[str, list[tuple[str, tuple[int, ...]]]]]:
    """(base op name, result shapes) for every collective in a program.
    Async pairs count once: ``-done`` lines are skipped and the
    ``-start`` line's operand-side shapes already include the result."""
    for line in strip_strings(hlo_text).splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or m.group(3) == "-done":
            continue
        yield m.group(2), parse_shapes(m.group(1))


def collective_census(hlo_text: str) -> dict:
    """op -> count over a whole program (the census the sharding tests
    assert on)."""
    census: dict = {}
    for op, _ in iter_collectives(hlo_text):
        census[op] = census.get(op, 0) + 1
    return census


def gather_result_shapes(hlo_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Result shapes of every all-gather (async ones via their -start
    line; the true gathered result is the largest shape on it)."""
    shapes: list[tuple[str, tuple[int, ...]]] = []
    for op, res in iter_collectives(hlo_text):
        if op == "all-gather" and res:
            shapes.append(max(res, key=lambda s: _elems(s[1])))
    return shapes


def _elems(dims: tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def scatter_result_dims(hlo_text: str) -> list[tuple[int, ...]]:
    """Result dims of every scatter op (the sharding tests pin the KV
    cyclic write to SHARD-LOCAL scatters: rows = S/sp, never full S)."""
    out: list[tuple[int, ...]] = []
    for line in strip_strings(hlo_text).splitlines():
        m = re.search(
            r"=\s*[a-z]+[0-9]+\[([0-9,]+)\][^=]*?\bscatter\(", line
        )
        if m:
            out.append(tuple(int(d) for d in m.group(1).split(",")))
    return out


def forbidden_gather_findings(
    hlo_text: str, table_dims: Iterable[tuple[int, ...]]
) -> list[tuple[str, tuple[int, ...]]]:
    """All-gather results whose trailing-two dims match a full-table
    shape — (dtype, dims) per offender. The callable core of the
    collective-census rule's regather check, shared with
    tests/test_parallel.py's embed/wcls census test."""
    tables = {tuple(t) for t in table_dims}
    hits: list[tuple[str, tuple[int, ...]]] = []
    for dtype, dims in gather_result_shapes(hlo_text):
        tail = dims[-2:] if len(dims) >= 2 else dims
        if tail in tables:
            hits.append((dtype, dims))
    return hits


def custom_call_targets(hlo_text: str) -> list[str]:
    """Every custom_call_target in a program (raw text: targets live
    inside the quoted strings strip_strings would blank)."""
    return _CUSTOM_CALL_TARGET_RE.findall(hlo_text)


def input_output_alias_count(hlo_text: str) -> int:
    """Number of donated-buffer aliases the executable honors, parsed
    from the module header's ``input_output_alias={...}`` map (balanced
    braces; 0 when the attribute is absent = every donation dropped)."""
    idx = hlo_text.find("input_output_alias={")
    if idx < 0:
        return 0
    start = idx + len("input_output_alias=")
    depth = 0
    end = start
    for i in range(start, len(hlo_text)):
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                end = i + 1
                break
    body = hlo_text[start:end]
    return len(_ALIAS_ENTRY_RE.findall(body))


def host_op_lines(hlo_text: str) -> list[str]:
    """infeed/outfeed/send/recv op names present in a program."""
    ops = []
    for line in strip_strings(hlo_text).splitlines():
        m = _HOST_OP_RE.search(line)
        if m and not m.group(2):  # count start of each pair once
            ops.append(m.group(1))
    return ops


def _name_dtypes(hlo_text: str) -> dict:
    """%name -> result element type for every instruction (the operand
    dtype table the upcast check walks)."""
    out: dict = {}
    for line in strip_strings(hlo_text).splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z]+[0-9]+|pred)\[", line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def f32_upcast_store_dots(hlo_text: str) -> list[str]:
    """Names of dots that STORE f32 while fed from a 16-bit float path
    — either ``dot(bf16, bf16) -> f32`` directly or through a
    ``convert`` — the silent accumulate-and-store upcast xlalint's
    dtype policy forbids on bf16 engines. (An f32-ACCUMULATING dot that
    stores bf16, or converts its result back down, is fine and does not
    match.)"""
    stripped = strip_strings(hlo_text)
    dtypes = _name_dtypes(hlo_text)
    # dot results consumed by a convert back down to 16-bit float are
    # accumulator-only: XLA itself lowers dot(bf16, bf16) -> bf16 as
    # convert-up / f32 dot / convert-down, and that round-trip is fine
    downcast = {
        m.group(2)
        for m in re.finditer(
            r"=\s*(bf16|f16)\[[^\]]*\][^=]*?\bconvert\(\s*"
            r"(?:[a-z0-9]+\[[^\]]*\]\S*\s+)?%?([\w.\-]+)",
            stripped,
        )
    }
    # ... and so is the same convert-down printed as a one-op fusion of a
    # dot's result (only dots are looked up: each lookup scans the text)
    dot_names = set(
        re.findall(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*f32\[[^\]]*\][^=]*?\bdot\(",
            stripped,
            re.M,
        )
    )
    for m in re.finditer(
        r"%?([\w.\-]+)\s*=\s*(?:bf16|f16)\[[^\]]*\][^=]*?\bfusion\(\s*"
        r"%?([\w.\-]+)\s*\)",
        stripped,
    ):
        if m.group(2) in dot_names and _fused_convert_source_dtype(
            hlo_text, m.group(1), dtypes
        ):
            downcast.add(m.group(2))
    hits: list[str] = []
    for line in stripped.splitlines():
        m = re.match(
            r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*f32\[[^\]]*\][^=]*?"
            r"\bdot\(([^)]*)\)",
            line,
        )
        if not m:
            continue
        if m.group(1) in downcast:
            continue
        operand_txt = m.group(2)
        # typed operand dumps show the 16-bit source inline
        if re.search(r"\b(?:bf16|f16)\[", operand_txt):
            hits.append(m.group(1))
            continue
        # otherwise resolve operand names through the instruction table
        names = re.findall(r"%([\w.\-]+)", operand_txt)
        if not names:
            names = [
                seg.strip().split()[-1]
                for seg in operand_txt.split(",")
                if seg.strip()
            ]
        for op_name in names:
            if dtypes.get(op_name) in ("bf16", "f16"):
                hits.append(m.group(1))
                break
            if op_name.startswith("convert"):
                src = _convert_source_dtype(hlo_text, op_name, dtypes)
            else:
                src = _fused_convert_source_dtype(hlo_text, op_name, dtypes)
            if src in ("bf16", "f16"):
                hits.append(m.group(1))
                break
    return hits


def _fused_convert_source_dtype(
    hlo_text: str, fusion_name: str, dtypes: dict[str, str]
) -> str | None:
    """Element type entering a fusion whose root is a convert. XLA prints a
    lone convert as ``%wrapped_convert = f32[..] fusion(%a), kind=kLoop,
    calls=%wrapped_convert_computation``: the dot's operand is then the
    fusion, and the 16-bit source sits inside the called computation."""
    stripped = strip_strings(hlo_text)
    fusion = re.search(
        r"%?" + re.escape(fusion_name)
        + r"\s*=\s*[a-z0-9]+\[[^\]]*\][^=]*?\bfusion\([^)]*\)[^\n]*?"
        r"\bcalls=%?([\w.\-]+)",
        stripped,
    )
    if not fusion:
        return None
    body = re.search(
        r"^%?" + re.escape(fusion.group(1)) + r"\s*\([^\n]*\{\n(.*?)^\}",
        stripped,
        re.S | re.M,
    )
    if not body:
        return None
    root = re.search(
        r"ROOT\s+%?([\w.\-]+)\s*=\s*[a-z0-9]+\[[^\]]*\][^=]*?\bconvert\(",
        body.group(1),
    )
    if not root:
        return None
    return _convert_source_dtype(hlo_text, root.group(1), dtypes)


def _convert_source_dtype(
    hlo_text: str, convert_name: str, dtypes: dict[str, str]
) -> str | None:
    """Element type feeding a convert — from the operand's inline typed
    dump (``convert(bf16[...] %p1)``) or, for a bare operand name
    (``convert(%p1)``), resolved through the instruction table."""
    m = re.search(
        r"%?" + re.escape(convert_name)
        + r"\s*=\s*[a-z0-9]+\[[^\]]*\][^=]*?\bconvert\(\s*([^)]*)\)",
        strip_strings(hlo_text),
    )
    if not m:
        return None
    operand = m.group(1).strip()
    typed = re.match(r"(pred|[a-z]+[0-9]+(?:e[0-9]+m[0-9]+\w*)?)\[", operand)
    if typed:
        return typed.group(1)
    name = re.match(r"%?([\w.\-]+)", operand)
    return dtypes.get(name.group(1)) if name else None


def dot_store_dtypes(hlo_text: str) -> list[str]:
    """Result element type of every dot in a program."""
    out: list[str] = []
    for line in strip_strings(hlo_text).splitlines():
        m = re.match(
            r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*([a-z]+[0-9]+)\[[^\]]*\][^=]*?\bdot\(",
            line,
        )
        if m:
            out.append(m.group(1))
    return out


# -- rules ------------------------------------------------------------------

class CollectiveCensusRule(HloRule):
    """Only the family's allowed collectives, no oversized all-gather,
    and no all-gather that reassembles a full weight/embed table."""

    name = "hlo-collective-census"
    description = (
        "compiled programs lower only their family's allowed collectives; "
        "all-gathers stay under the policy size cap and never rebuild a "
        "full sharded table"
    )

    def check(self, prog: HloProgram) -> Iterable[HloFinding]:
        pol = prog.policy
        seen_disallowed: set = set()
        for op, _ in iter_collectives(prog.hlo_text):
            if op not in pol.allowed_collectives and op not in seen_disallowed:
                seen_disallowed.add(op)
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message=f"collective '{op}' not allowed in "
                    f"{prog.family} programs",
                )
        table_hits = {
            dims
            for _, dims in forbidden_gather_findings(
                prog.hlo_text, pol.forbidden_gather_dims
            )
        }
        seen_shapes: set = set()
        for dtype, dims in gather_result_shapes(prog.hlo_text):
            if dims in seen_shapes:
                continue
            seen_shapes.add(dims)
            if dims in table_hits:
                tail = dims[-2:] if len(dims) >= 2 else dims
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message=f"all-gather reassembles a full sharded table "
                    f"{'x'.join(map(str, tail))}",
                    detail=f"result {dtype}[{','.join(map(str, dims))}]",
                )
            elif (
                pol.max_allgather_elements
                and _elems(dims) > pol.max_allgather_elements
            ):
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message=f"all-gather result "
                    f"{dtype}[{','.join(map(str, dims))}] exceeds the "
                    f"family size cap",
                    detail=f"{_elems(dims)} > {pol.max_allgather_elements} "
                    f"elements",
                )


class DonationRule(HloRule):
    """Every donated buffer must appear in the executable's
    input_output_alias map — a dropped donation is silent double-HBM
    for the KV cache / page pool."""

    name = "hlo-donation"
    description = (
        "donate_argnums buffers appear as input_output_alias entries in "
        "the compiled executable"
    )

    def check(self, prog: HloProgram) -> Iterable[HloFinding]:
        if prog.expected_aliases <= 0:
            return
        got = input_output_alias_count(prog.hlo_text)
        if got < prog.expected_aliases:
            yield HloFinding(
                rule=self.name,
                path=prog.path,
                line=1,
                message=f"donation dropped: "
                f"{prog.expected_aliases - got} of "
                f"{prog.expected_aliases} donated buffers have no "
                f"input-output alias",
                detail=f"alias map has {got} entries",
            )


class HostRoundTripRule(HloRule):
    """Hot-path programs never leave the device: no host-callback
    custom-calls, no infeed/outfeed/send/recv, no f64 (which usually
    means host-side Python float math leaked into a trace)."""

    name = "hlo-host"
    description = (
        "no host callbacks, infeed/outfeed, send/recv, or f64 in "
        "hot-path compiled programs"
    )

    def check(self, prog: HloProgram) -> Iterable[HloFinding]:
        if not prog.policy.forbid_host:
            return
        seen: set = set()
        for target in custom_call_targets(prog.hlo_text):
            low = target.lower()
            if target not in seen and any(
                marker in low for marker in HOST_TARGET_MARKERS
            ):
                seen.add(target)
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message=f"host-transfer custom-call '{target}'",
                )
        for op in sorted(set(host_op_lines(prog.hlo_text))):
            yield HloFinding(
                rule=self.name,
                path=prog.path,
                line=1,
                message=f"host-transfer op '{op}'",
            )
        if prog.policy.forbid_f64 and "f64[" in strip_strings(prog.hlo_text):
            yield HloFinding(
                rule=self.name,
                path=prog.path,
                line=1,
                message="f64 tensor in a hot-path program",
            )


class DtypePolicyRule(HloRule):
    """Weight-path dots store at most the policy width, and a bf16
    engine's dots never silently upcast to f32 accumulate-AND-store."""

    name = "hlo-dtype"
    description = (
        "dot-generals store within the family dtype width and never "
        "silently upcast a 16-bit float path to an f32 store"
    )

    def check(self, prog: HloProgram) -> Iterable[HloFinding]:
        pol = prog.policy
        if pol.max_dot_store_bits:
            over = sorted(
                {
                    d
                    for d in dot_store_dtypes(prog.hlo_text)
                    if dtype_bits(d) > pol.max_dot_store_bits
                }
            )
            for d in over:
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message=f"dot stores {d}, wider than the "
                    f"{pol.max_dot_store_bits}-bit family limit",
                )
        if pol.forbid_f32_upcast_store:
            hits = f32_upcast_store_dots(prog.hlo_text)
            if hits:
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message="16-bit float path upcast to an f32 "
                    "dot store (accumulate-and-store)",
                    detail=f"dots: {', '.join(sorted(set(hits))[:4])}",
                )


class CostBudgetRule(HloRule):
    """XLA's own cost analysis stays under the roofline-derived ceiling
    for the program family — the regather/replication cliff guard."""

    name = "hlo-cost-budget"
    description = (
        "per-program bytes_accessed/flops stay under the roofline-"
        "derived family budget (obs.cost.program_cost_ceilings)"
    )

    def check(self, prog: HloProgram) -> Iterable[HloFinding]:
        if prog.cost is None:
            return
        checks = (
            ("bytes_accessed", prog.bytes_budget),
            ("flops", prog.flops_budget),
        )
        for metric, budget in checks:
            value = prog.cost.get(metric, 0.0)
            if budget > 0 and value > budget:
                yield HloFinding(
                    rule=self.name,
                    path=prog.path,
                    line=1,
                    message=f"{metric} exceeds the {prog.family} "
                    f"roofline budget",
                    detail=f"{value:.3e} > {budget:.3e}",
                )


__all__ = [
    "COLLECTIVE_OPS",
    "HOST_TARGET_MARKERS",
    "CollectiveCensusRule",
    "CostBudgetRule",
    "DonationRule",
    "DtypePolicyRule",
    "FamilyPolicy",
    "HostRoundTripRule",
    "collective_census",
    "custom_call_targets",
    "dot_store_dtypes",
    "dtype_bits",
    "f32_upcast_store_dots",
    "forbidden_gather_findings",
    "gather_result_shapes",
    "scatter_result_dims",
    "host_op_lines",
    "input_output_alias_count",
    "iter_collectives",
    "parse_shapes",
    "strip_strings",
]
