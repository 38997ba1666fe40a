"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, per-program and per-kernel device time,
and the host activity behind each idle gap.

Read with ``jax.profiler.ProfileData`` alone.  A TPU's plane is
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
run on the device, named by its HLO text (``%fusion.3 = bf16[...]
fusion(...)``; a Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` and no kernel name), and its
``XLA Modules`` line one event per program run (named after the jitted
function, e.g. ``jit_step(12)``).  An operation belongs to the program
whose run holds its start, so a kernel is found by its call target inside
a named program.  The traced window is the profile's own start and stop
(the ``Task Environment`` plane).  Host spans are the harness's
``chipbench.*`` annotations.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "chipbench."
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CONTAINERS = ("while", "conditional", "call")    # ops that hold other ops


@dataclasses.dataclass
class TraceSummary:
    window_s: float                      # length of the traced window
    busy_s: float                        # device busy union, mean per chip
    programs: dict                       # program name -> [seconds, ...]
    ops: dict                            # op name -> seconds (all chips)
    kernels: dict                        # (program, call target) -> seconds
    idle: dict                           # host activity -> idle seconds
    devices: int

    def program_times(self, name: str) -> list:
        return self.programs.get(name, [])

    def kernel_seconds(self, program: str, target: str) -> float:
        """Device seconds of the custom calls to ``target`` run inside
        the programs named ``program``."""
        return self.kernels.get((program, target), 0.0)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        top = [(k[:160], v) for k, v in top]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find(directory: str) -> str:
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(hits, key=os.path.getmtime)


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _container(op: str) -> bool:
    """A ``while``/``conditional``/``call`` op, whose time is its body's."""
    base = re.sub(r"\.\d+$", "", op.split(" ", 1)[0].lstrip("%"))
    return base in _CONTAINERS


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _label(spans, starts, t: float) -> str:
    """The innermost host span covering ``t`` (``spans`` sorted by start,
    ``starts`` their starts): spans nest, so it is the latest-starting
    span that still covers ``t``."""
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        a, b, name = spans[i]
        if b >= t:
            return name
    return "host:outside any span"


def reduce(path: str) -> TraceSummary:
    """``path``: a trace directory, an ``.xplane.pb`` file, or one
    compressed with gzip (``.xplane.pb.gz``)."""
    import jax
    if os.path.isdir(path):
        path = find(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    window = None
    busy_per_device = []
    programs = collections.defaultdict(list)
    ops = collections.defaultdict(float)
    kernels = collections.defaultdict(float)
    host = []
    merged_all = []
    for plane in data.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = (int(st["profile_stop_time"])
                          - int(st["profile_start_time"])) / 1e9
        elif _DEVICE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _program(e.name))
                          for e in lines.get(MODULES_LINE, []))
            for a, b, name in runs:
                programs[name].append((b - a) / 1e9)
            run_starts = [a for a, _, _ in runs]
            intervals = []
            for e in lines.get(OPS_LINE, []):
                intervals.append((e.start_ns, e.start_ns + e.duration_ns))
                if not _container(e.name):
                    ops[e.name] += e.duration_ns / 1e9
                target = _TARGET.search(e.name)
                i = bisect.bisect_right(run_starts, e.start_ns) - 1
                if target and i >= 0 and e.start_ns < runs[i][1]:
                    kernels[(runs[i][2], target.group(1))] += \
                        e.duration_ns / 1e9
            merged = _union(intervals)
            merged_all.append(merged)
            busy_per_device.append(sum(b - a for a, b in merged) / 1e9)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    n = len(busy_per_device)
    if not n:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    host.sort()
    starts = [a for a, _, _ in host]
    idle = collections.defaultdict(float)
    for merged in merged_all:
        for (_, a), (b, _) in zip(merged, merged[1:]):
            idle[_label(host, starts, (a + b) / 2)] += (b - a) / 1e9 / n
    if window is None:
        ends = [iv for m in merged_all for iv in m]
        window = (max(b for _, b in ends) - min(a for a, _ in ends)) / 1e9
    return TraceSummary(window_s=window, busy_s=sum(busy_per_device) / n,
                        programs=dict(programs), ops=dict(ops),
                        kernels=dict(kernels), idle=dict(idle), devices=n)
