"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle
time, per-kernel device time and a breakdown.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed operation, named by its HLO text
(``%name = shape op(operands), ...``); a ``while`` op's event spans the
events of its body.  An op's own name is the text before `` = ``; a
kernel is a ``custom-call`` whose own name matches its pattern (its
output also appears as an operand in later ops' text, which must not
count).  The measured window is the host span named ``window`` (a
`jax.profiler.TraceAnnotation` of the harness); idle gaps inside it are
labelled by the harness's span that covers the gap's middle and the
innermost other host span there.  Reading the file needs `jax.profiler.ProfileData`
only: nothing here touches a device.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "window"


def find_trace(directory) -> Path:
    files = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


HARNESS_SPANS = ("enqueue", "wait")


def op_name(event) -> str:
    """``%block_topk_pallas.6 = bf16[...] custom-call(...)`` ->
    ``block_topk_pallas.6``."""
    return event.name.split(" = ", 1)[0].lstrip("%")


def is_custom_call(event) -> bool:
    return " custom-call(" in event.name


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def reduce(pd, kernel_patterns: dict, window: str = WINDOW, top: int = 10) -> dict:
    """``kernel_patterns``: name -> regular expression on a custom call's
    own name; each name sums the calls its pattern matches (two names may
    match the same calls); a pattern that matches no custom call in the
    window is an error (a renamed kernel would otherwise move its time
    out of sight).
    Returns seconds: ``window_s``, ``busy_s`` (union of op
    intervals in the window, averaged over device planes), ``kernel_s``
    per pattern name, and ``device_ops`` / ``idle_gaps``: the ``top`` ops
    without nested ops (a loop's body ops count, not the loop) by summed
    time, and the longest gaps, as [name, s]."""
    host_spans = []
    dev_ops = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops.append([(e.start_ns, e.end_ns, e) for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host_spans.append((e.start_ns, e.end_ns, e.name))
    if not dev_ops or not any(dev_ops):
        raise ValueError("the trace holds no device operations")
    wins = [(s, e) for s, e, n in host_spans if n == window]
    if wins:
        lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    else:
        lo = min(s for ops in dev_ops for s, _, _ in ops)
        hi = max(e for ops in dev_ops for _, e, _ in ops)
    window_s = (hi - lo) * 1e-9
    regexes = {k: re.compile(p) for k, p in kernel_patterns.items()}
    busy, kernel = 0.0, defaultdict(float)
    by_name = defaultdict(float)
    gaps = []
    for ops in dev_ops:
        inside = [(s, e, ev) for s, e, ev in ops if e > lo and s < hi]
        merged = _union(_clip([(s, e) for s, e, _ in inside], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        inside.sort(key=lambda x: x[0])
        for j, (s, e, ev) in enumerate(inside):
            if j + 1 < len(inside) and inside[j + 1][0] < e and inside[j + 1][1] <= e:
                continue  # holds nested ops (a loop): its body ops count
            dur = (min(e, hi) - max(s, lo)) * 1e-9
            name = op_name(ev)
            by_name[name] += dur
            if is_custom_call(ev):
                for k, rx in regexes.items():
                    if rx.search(name):
                        kernel[k] += dur
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    missing = [k for k in kernel_patterns if not kernel[k]]
    if missing:
        raise ValueError("no custom call in the traced window matches "
                         + ", ".join(f"{k} {kernel_patterns[k]!r}" for k in missing))
    n = len(dev_ops)
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = sorted((e - s, name) for s, e, name in host_spans
                       if s <= mid <= e and name != window)
        ours = [name for _, name in cover if name in HARNESS_SPANS]
        inner = [name for _, name in cover if name not in HARNESS_SPANS]
        label = " / ".join(([ours[0]] if ours else []) + inner[:1]) or "no host span"
        labelled.append([label, (b - a) * 1e-9 / n])
    return {
        "window_s": window_s,
        "busy_s": busy / n,
        "kernel_s": {k: kernel[k] / n for k in kernel_patterns},
        "device_ops": [[k, v / n] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": labelled,
        "device_planes": n,
    }
