"""The serving engine's own spans in a profiler trace, and what the
engine-layer metrics read from them and from the engine's request
timestamps and counters.

The engine marks its phases with ``serving.*`` host annotations
(``repro.core.spans``): ``serving.step`` around each step, ``serving.retire``,
``serving.admit``, ``serving.prefill``, ``serving.chunk``,
``serving.decode`` and ``serving.spec_round`` inside it, ``serving.wait``
(stat ``what``) around every place the host blocks on the device, and
``serving.gc`` around each garbage collection.  They share the clock of the
device planes, so an idle gap on the device can be put down to what the
host was doing then.

:func:`reduce` extends :func:`chipbench.trace.reduce`: its summary keeps
every reading of the plain one, labels each idle gap with the innermost
``chipbench.*`` or ``serving.*`` span, and adds the ``serving.*`` spans
with their stats, the idle gaps between device operations, and the device
time of each named Pallas kernel.  On a trace of a program without these
spans the additions are empty and every reader here returns ``None``.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import os
import re

from chipbench import trace
from chipbench.readings import percentile

PREFIX = "serving."
STEP = "serving.step"
BLOCKS = ("serving.wait", "serving.gc")     # the host is not working
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_OP_NAME = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")


@dataclasses.dataclass
class EngineTrace(trace.TraceSummary):
    spans: list = dataclasses.field(default_factory=list)
    # (start_ns, end_ns, name, stats) of each serving.* span, by start
    gaps: list = dataclasses.field(default_factory=list)
    # per device: (start_ns, end_ns) of each idle gap between operations
    kernel_names: dict = dataclasses.field(default_factory=dict)
    # Pallas kernel name (its HLO instruction's) -> device seconds


def _load(path: str):
    import jax
    if os.path.isdir(path):
        path = trace.find(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return path, jax.profiler.ProfileData.from_serialized_xspace(
                f.read())
    return path, jax.profiler.ProfileData.from_file(path)


def reduce(path: str) -> EngineTrace:
    """``path``: a trace directory, an ``.xplane.pb`` file, or one
    compressed with gzip."""
    path, data = _load(path)
    base = trace.reduce(path)
    host, serving, gaps = [], [], []
    kernels = collections.defaultdict(float)
    for plane in data.planes:
        if trace._DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
                    if _KERNEL_TARGET in e.name:
                        m = _OP_NAME.match(e.name)
                        if m:
                            kernels[m.group(1)] += e.duration_ns / 1e9
            merged = trace._union(ops)
            gaps.append([(a, b) for (_, a), (b, _)
                         in zip(merged, merged[1:])])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.startswith(PREFIX):
                        host.append(iv)
                        serving.append(iv + (dict(e.stats),))
                    elif e.name.startswith(trace.HOST_PREFIX):
                        host.append(iv)
    serving.sort(key=lambda s: s[:2])
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(trace.TraceSummary)}
    fields["idle"] = label_gaps(host, gaps)
    return EngineTrace(**fields, spans=serving, gaps=gaps,
                       kernel_names=dict(kernels))


def label_gaps(host, gaps) -> dict:
    """Idle seconds between device operations (mean per chip) by the
    innermost host span (``(start, end, name)``) at each gap's middle."""
    host = sorted(host)
    starts = [a for a, _, _ in host]
    idle = collections.defaultdict(float)
    for dev in gaps:
        for a, b in dev:
            idle[trace._label(host, starts, (a + b) / 2)] += \
                (b - a) / 1e9 / len(gaps)
    return dict(idle)


def _measure(intervals) -> float:
    return sum(b - a for a, b in trace._union(intervals))


def _clip(intervals, a, b) -> list:
    return [(max(x, a), min(y, b)) for x, y in intervals if x < b and y > a]


def _intersect(xs, ys) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(intervals, holes) -> list:
    """``intervals`` (sorted, disjoint) less the union of ``holes``."""
    out, j = [], 0
    holes = trace._union(holes)
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        cur, k = a, j
        while k < len(holes) and holes[k][0] < b:
            x, y = holes[k]
            if x > cur:
                out.append((cur, x))
            cur = max(cur, y)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _spans(run) -> list | None:
    got = getattr(run.trace, "spans", None) if run.trace else None
    return got or None


def step_host_self_ms(run) -> float | None:
    """Mean over the traced ``serving.step`` spans of the step's time
    less the union of its ``serving.wait`` children, in ms."""
    spans = _spans(run)
    if spans is None:
        return None
    steps = [(a, b) for a, b, name, _ in spans if name == STEP]
    waits = [(a, b) for a, b, name, _ in spans if name == "serving.wait"]
    if not steps:
        return None
    selfs = [(b - a) - _measure(_clip(waits, a, b)) for a, b in steps]
    return sum(selfs) / len(selfs) / 1e6


def idle_host_work_share(run) -> float | None:
    """Share of the traced window, in %, in which the device is idle
    between two operations while the host is inside a ``serving.*`` span
    and not inside ``serving.wait`` or ``serving.gc`` (mean per chip)."""
    spans = _spans(run)
    if spans is None or run.trace.window_s <= 0:
        return None
    work = trace._union([(a, b) for a, b, name, _ in spans
                         if name not in BLOCKS])
    work = _subtract(work, [(a, b) for a, b, name, _ in spans
                            if name in BLOCKS])
    gaps = run.trace.gaps
    idle = sum(_measure(_intersect(dev, work))
               for dev in gaps) / max(len(gaps), 1)
    return 100.0 * idle / 1e9 / run.trace.window_s


def _states(run) -> list | None:
    """The engine's request states of the window's requests (``None`` for
    a refused one); ``None`` when the engine keeps no admission time."""
    states = [c.state for c in run.window.clients]
    if not any(hasattr(s, "admitted_at") for s in states if s is not None):
        return None
    return states


def admit_wait_p90_ms(run) -> float | None:
    """p90 of ``admitted_at - submitted_at`` (engine clock), in ms; a
    request never admitted counts as missing (+inf)."""
    states = _states(run)
    if states is None:
        return None
    return percentile(
        [(s.admitted_at - s.submitted_at) * 1e3
         if s is not None and s.admitted_at is not None else float("inf")
         for s in states], 90)


def first_token_wait_p90_ms(run) -> float | None:
    """p90 of ``first_token_at - admitted_at`` (engine clock), in ms; a
    request with no first token counts as missing (+inf)."""
    states = _states(run)
    if states is None:
        return None
    return percentile(
        [(s.first_token_at - s.admitted_at) * 1e3
         if s is not None and s.first_token_at is not None
         else float("inf") for s in states], 90)


def prefill_slots_mean(run) -> float | None:
    """Slots held PREFILLING per engine step over the window (engine
    counters: Δ``slot_steps_prefilling`` / Δ``steps``)."""
    c0, c1 = run.window.counters0, run.window.counters1
    if "steps" not in c1 or "slot_steps_prefilling" not in c1:
        return None
    steps = c1["steps"] - c0["steps"]
    if steps <= 0:
        return None
    return (c1["slot_steps_prefilling"] - c0["slot_steps_prefilling"]) / steps
