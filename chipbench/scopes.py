"""The named scopes of the C2DFB round, and the device time of a traced
window split by them.

The program enters ``jax.named_scope(<name>)`` at each layer boundary of
its round (`repro.obs.scopes`); the names land in the compiled program's
``metadata={op_name="jit(f)/while/body/ll_oracle/vmap(jvp())/dot"}``,
which the trace's own event stats lack.  `op_scopes` maps each op of the
compiled text (``as_text()`` of the compiled round) to its scope, and
`scope_times` partitions the window's device busy time over those scopes
and the compression kernel, exactly: the parts sum to
`chipbench.trace_reduce.reduce`'s ``busy_s``.  `read` does both for one
traced run.  The list is kept here, apart from the program's own, so that
a scope renamed in the program stops the reading instead of moving its
time out of sight.  Nothing here imports the program.
"""

from __future__ import annotations

import heapq
import re
from bisect import bisect_right
from collections import defaultdict

from chipbench.trace_reduce import DEVICE_PLANE, OPS_LINE, WINDOW, is_custom_call, op_name

SCOPES = ("ll_oracle", "ul_oracle", "exchange", "compress", "meter", "update")

UNSCOPED = "unscoped"
UNMAPPED = "unmapped"
MODULES_LINE = "XLA Modules"
#: the largest share of busy time that ops absent from the map may hold
STALE = 0.01

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_APPLIED = re.compile(r"\b(calls|to_apply|comparator)=%([^\s,)}]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)


def _scope_of(op_name_meta: str, scopes) -> str:
    """The innermost path segment of ``op_name_meta`` that names one of
    ``scopes``, looking through transform wrappers such as
    ``vmap(transpose(jvp(ll_oracle)))``; else `UNSCOPED`."""
    for seg in reversed(re.split(r"[/;]", op_name_meta)):
        while True:
            if seg in scopes:
                return seg
            m = _WRAPPER.match(seg)
            if m is None:
                break
            seg = m.group(1)
    return UNSCOPED


def module_name(hlo_text: str) -> str:
    """``HloModule jit_step, ...`` -> ``jit_step``."""
    m = _MODULE.search(hlo_text)
    if m is None:
        raise ValueError("no HloModule line in the compiled text")
    return m.group(1)


def scopes_in(hlo_text: str, scopes) -> set:
    """The names of ``scopes`` that some op's ``op_name`` metadata holds,
    in any computation (fused ones included)."""
    found = set()
    for meta in _OP_NAME.findall(hlo_text):
        for seg in re.split(r"[/;]", meta):
            s = _scope_of(seg, scopes)
            if s != UNSCOPED:
                found.add(s)
    return found


#: ops the compiler adds around a fusion's root (type and layout changes,
#: tuples): where they name no scope, the root's scope is read through them
_SEE_THROUGH = ("convert", "bitcast", "copy", "tuple", "get-tuple-element")
_OPERAND = re.compile(r"%([^\s,()]+)")


def _parse(hlo_text: str):
    """Computations of the compiled text: name -> (instructions as
    name -> (opcode, op_name or None, first operand, fused computation),
    root), and the names of the computations that only run inside
    another op (a fusion's ``calls=``, a reduction's ``to_apply=``, a
    sort's ``comparator=``)."""
    comps, applied, cur = {}, set(), None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps[m.group(1)] = [{}, None]
            continue
        m = _INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        opcode = op.group(1) if op else ""
        first = _OPERAND.search(rest, op.end()) if op else None
        meta = _OP_NAME.search(rest)
        calls = None
        for kind, comp in _APPLIED.findall(rest):
            if kind == "calls" and opcode == "fusion":
                calls = comp
            if not (kind == "to_apply" and opcode == "call"):
                applied.add(comp)
        cur[0][name] = (opcode, meta.group(1) if meta else None,
                        first.group(1) if first else None, calls)
        if line.lstrip().startswith("ROOT "):
            cur[1] = name
    return comps, applied


def _fusion_scope(comps, comp: str, scopes) -> str:
    """The scope of a fused computation's root, read through the ops in
    `_SEE_THROUGH` that name none."""
    instrs, name = comps[comp]
    while name in instrs:
        opcode, meta, first, _ = instrs[name]
        label = _scope_of(meta, scopes) if meta else UNSCOPED
        if label != UNSCOPED or opcode not in _SEE_THROUGH:
            return label
        name = first
    return UNSCOPED


def op_scopes(hlo_text: str, scopes) -> dict:
    """Map each instruction that runs as an op of its own to one of
    ``scopes`` or to `UNSCOPED`, from the compiled text (``as_text()`` of
    a compiled program).  An op counts under its own ``op_name``, which
    for a fusion is its root's; where a fusion's names no scope, under
    its fused computation's root read through the converts, bitcasts,
    copies and tuples that the compiler adds and that name none.
    Instructions of computations that only run inside another op are left
    out."""
    scopes = tuple(scopes)
    comps, applied = _parse(hlo_text)
    out = {}
    for comp, (instrs, _) in comps.items():
        if comp in applied:
            continue
        for name, (_, meta, _, calls) in instrs.items():
            out[name] = _scope_of(meta, scopes) if meta else UNSCOPED
            if out[name] == UNSCOPED and calls in comps:
                out[name] = _fusion_scope(comps, calls, scopes)
    return out


def mixed_fusions(hlo_text: str, scopes) -> set:
    """Fusions that run as ops and whose fused computation holds ops of
    more than one of ``scopes``: their time goes to one scope by the root
    rule (`op_scopes`), so their share of busy time bounds how much the
    rule blurs the partition."""
    scopes = tuple(scopes)
    comps, applied = _parse(hlo_text)
    out = set()
    for comp, (instrs, _) in comps.items():
        if comp in applied:
            continue
        for name, (_, _, _, calls) in instrs.items():
            if calls in comps:
                held = {_scope_of(meta, scopes) for _, meta, _, _ in
                        comps[calls][0].values() if meta} - {UNSCOPED}
                if len(held) > 1:
                    out.add(name)
    return out


def _device_lines(pd):
    """Per device plane: (ops, module spans) as (start, end, event) and
    (start, end, name); and the host spans."""
    planes, host_spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.end_ns, e) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            planes.append((ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host_spans.append((e.start_ns, e.end_ns, e.name))
    return planes, host_spans


def scope_times(pd, op_map: dict, kernel_patterns: dict, module: str,
                strict: bool = True) -> dict:
    """Partition the union of the window's device busy intervals exactly
    over labels, in seconds averaged over device planes (so they sum to
    `chipbench.trace_reduce.reduce`'s ``busy_s``).

    Each instant goes to the latest-started op running then (a ``while``
    gives way to its body, as in `reduce`; it keeps only the instants
    between its body's ops).  That op's label is its kernel pattern's name
    where it is a custom call that the pattern matches (as in `reduce`),
    else its scope in ``op_map`` (`op_scopes`), else `UNMAPPED`: an op
    absent from the map, or one outside the ``XLA Modules`` events of
    ``module`` (`module_name` of the mapped program), such as the
    harness's key derivation between rounds.

    Raises where a kernel name is also a scope's; and, when ``strict``,
    where a scope of the map or a kernel pattern gets no time or unmapped
    ops hold more than `STALE` of the busy time (the map is not the traced
    program's)."""
    listed = sorted(set(op_map.values()) - {UNSCOPED})
    clash = set(kernel_patterns) & (set(listed) | {UNSCOPED, UNMAPPED})
    if clash:
        raise ValueError(f"kernel names clash with scope labels: {sorted(clash)}")
    planes, host_spans = _device_lines(pd)
    if not planes or not any(ops for ops, _ in planes):
        raise ValueError("the trace holds no device operations")
    wins = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if wins:
        lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    else:
        lo = min(s for ops, _ in planes for s, _, _ in ops)
        hi = max(e for ops, _ in planes for _, e, _ in ops)
    regexes = {k: re.compile(p) for k, p in kernel_patterns.items()}
    out = defaultdict(float)
    for ops, mods in planes:
        spans = sorted((s, e) for s, e, n in mods if n.split("(", 1)[0] == module)
        starts = [s for s, _ in spans]

        def label(s, e, ev):
            name = op_name(ev)
            if is_custom_call(ev):
                for k, rx in regexes.items():
                    if rx.search(name):
                        return k
            j = bisect_right(starts, s) - 1
            if j < 0 or e > spans[j][1]:
                return UNMAPPED
            return op_map.get(name, UNMAPPED)

        inside = sorted((s, e, j, label(s, e, ev))
                        for j, (s, e, ev) in enumerate(ops) if e > lo and s < hi)
        edges = sorted({min(max(x, lo), hi) for s, e, _, _ in inside for x in (s, e)})
        active, i = [], 0
        for a, b in zip(edges, edges[1:]):
            while i < len(inside) and max(inside[i][0], lo) <= a:
                s, e, j, lab = inside[i]
                heapq.heappush(active, (-s, e, -j, lab))
                i += 1
            while active and active[0][1] <= a:
                heapq.heappop(active)
            if active:
                out[active[0][3]] += (b - a) * 1e-9
    n = len(planes)
    busy = sum(out.values())
    missing = [k for k in list(kernel_patterns) + listed if not out[k]]
    if strict and missing:
        raise ValueError("no time in the traced window for " + ", ".join(missing))
    if strict and out[UNMAPPED] > STALE * busy:
        raise ValueError(f"ops absent from the map hold {out[UNMAPPED] / busy:.2%} "
                         f"of the busy time (over {STALE:.0%}): a stale map")
    labels = list(kernel_patterns) + listed + [UNSCOPED, UNMAPPED]
    return {k: out[k] / n for k in labels}


def read(pd, hlo_text: str, kernel_patterns: dict):
    """One traced run's partition: seconds per label (`scope_times`, over
    the module that ``hlo_text`` compiles), and ``mixed_s``, the busy
    seconds in fusions whose fused computation holds ops of more than one
    scope (how much the root rule blurs the partition).  None where the
    traced program carries none of `SCOPES` (a program from before
    them); raises where it carries only some (a scope was renamed)."""
    seen = scopes_in(hlo_text, SCOPES)
    if not seen:
        return None
    if set(SCOPES) - seen:
        raise ValueError(f"scopes missing from the traced program: "
                         f"{sorted(set(SCOPES) - seen)}")
    module = module_name(hlo_text)
    op_map = op_scopes(hlo_text, SCOPES)
    out = scope_times(pd, op_map, kernel_patterns, module)
    mixed = mixed_fusions(hlo_text, SCOPES)
    blur = scope_times(pd, {k: "mixed" if k in mixed else UNSCOPED for k in op_map},
                       {}, module, strict=False)
    return {"scope_s": out, "mixed_s": blur.get("mixed", 0.0)}
