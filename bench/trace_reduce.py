"""Reduction of a JAX profiler trace to device busy time, idle gaps and spans.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read with
``jax.profiler.ProfileData``. Device operations are the events of each device
plane's ``XLA Ops`` line, programs those of its ``XLA Modules`` line. Host
spans are the ``TraceAnnotation`` events the harness wraps around the
server's phases; program spans are the program's own ``fl.*`` events, kept
apart with their numeric arguments (the program's counters). All share the
device trace's clock.

Busy time is the union of one device's operation intervals inside the
window; the idle share is one minus busy over the window. Each idle gap is
labelled with the innermost harness span open on the host at its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

#: Host spans the harness records, outermost first. ``window`` encloses the
#: whole traced window and never labels a gap.
SPAN_NAMES = ("window", "round", "draw", "local_work", "observe", "eval")
#: The prefix of the program's own spans (``repro.fl``'s ``TraceAnnotation`` names).
PROGRAM_PREFIX = "fl."


def op_name(raw: str) -> str:
    """``'%fusion.84 = bf16[...] ...'`` -> ``'fusion.84'``; program names lose
    their ``(hash)`` suffix."""
    name = raw.split(" = ", 1)[0].lstrip("%").strip()
    return name.split("(", 1)[0] if name.endswith(")") else name


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds, on one clock."""

    ops: dict  # device plane name -> [(op name, start, end)]
    modules: dict  # device plane name -> [(program name, start, end)]
    spans: list  # [(span name, start, end)]
    program: list = dataclasses.field(default_factory=list)  # [(name, start, end, {arg: number})]

    def window(self) -> tuple[float, float]:
        """The ``window`` span, or the extent of all spans without one."""
        for name, start, end in self.spans:
            if name == "window":
                return start, end
        if not self.spans:
            raise ValueError("the trace holds no harness span")
        return min(s for _, s, _ in self.spans), max(e for _, _, e in self.spans)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def collect(profile, *, device_prefix: str = "/device:", op_line: str = "XLA Ops",
            module_line: str = "XLA Modules", span_names=SPAN_NAMES) -> Trace:
    """Pull device operations, programs, harness spans and the program's
    ``fl.*`` spans out of a ``ProfileData``.

    ``device_prefix`` and ``op_line`` select which planes and lines count as
    the device; ``op_line`` may also be a predicate on the line's name, which
    a test on the CPU points at the host's executor threads.
    """
    ops, modules, spans, program = {}, {}, [], []
    wanted = set(span_names)
    is_op = op_line if callable(op_line) else (lambda name: name == op_line)
    for plane in profile.planes:
        on_device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            if on_device and (is_op(line.name) or line.name == module_line):
                out = ops if is_op(line.name) else modules
                out.setdefault(plane.name, []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                )
            elif plane.name.startswith("/host"):
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        args = {k: v for k, v in e.stats if isinstance(v, (int, float))}
                        program.append((e.name, e.start_ns, e.start_ns + e.duration_ns, args))
    return Trace(ops=ops, modules=modules, spans=spans, program=program)


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, clipped to ``[lo, hi]``, sorted."""
    out: list[list[float]] = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` in which no interval is open."""
    gaps, cursor = [], lo
    for start, end in merge(intervals, lo, hi):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def innermost_span(spans, at: float) -> str:
    """Name of the shortest harness span (other than ``window``) open at ``at``."""
    best, best_len = "none", float("inf")
    for name, start, end in spans:
        if name != "window" and start <= at < end and end - start < best_len:
            best, best_len = name, end - start
    return best


def device_summary(trace: Trace, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over devices), the device
    operations that took most time, and the longest idle gaps with what the
    host was doing in each."""
    lo, hi = trace.window()
    devices = sorted(trace.ops)
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy = [busy_ns([(s, e) for _, s, e in trace.ops[d]], lo, hi) for d in devices]
    totals: dict = collections.Counter()
    for name, s, e in trace.ops[devices[0]]:
        if e > lo and s < hi:
            totals[name] += (min(e, hi) - max(s, lo)) * 1e-9
    gaps = idle_gaps([(s, e) for _, s, e in trace.ops[devices[0]]], lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "device_ops": [[name, sec] for name, sec in totals.most_common(top)],
        "idle_gaps": [
            [innermost_span(trace.spans, (s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:top]
        ],
    }


def span_seconds(trace: Trace) -> dict:
    """Span name -> durations in seconds, inside the window."""
    lo, hi = trace.window()
    out: dict = collections.defaultdict(list)
    for name, s, e in trace.spans:
        if name != "window" and s >= lo and e <= hi:
            out[name].append((e - s) * 1e-9)
    return dict(out)


def program_spans(trace: Trace) -> dict:
    """Program span name -> ``[(seconds, {arg: number})]``, inside the window."""
    lo, hi = trace.window()
    out: dict = collections.defaultdict(list)
    for name, s, e, args in trace.program:
        if s >= lo and e <= hi:
            out[name].append(((e - s) * 1e-9, args))
    return dict(out)


def program_events(trace: Trace, fragment: str, *, modules: bool) -> list[tuple[float, float]]:
    """Intervals of the first device's programs (``modules=True``) or operations
    whose name contains ``fragment``, inside the window."""
    lo, hi = trace.window()
    source = trace.modules if modules else trace.ops
    if not source:
        return []
    first = source[sorted(source)[0]]
    return [(s, e) for name, s, e in first if fragment in name and s >= lo and e <= hi]
