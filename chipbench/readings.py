"""Shared arithmetic of the per-layer metric readers (``metrics/``).

A reader gets the run (:class:`chipbench.run.Run`): the window's clients
and per-step host records, the engine's counters, and with ``--trace 1``
the reduced device trace (:mod:`chipbench.trace`).  Each returns a number
or ``None`` when the run holds nothing for it to read.

Traced programs and host records are matched by count, not by clock: the
host records of the steps dispatched inside the traced span give the mean
work per step, and the trace gives how many such programs ran and for how
long.  A step dispatched at an edge of the span shifts a count by one.
"""
from __future__ import annotations

import numpy as np

from chipbench import flops, peaks, spec

DECODE_STEP = "jit_step"          # the engine's decode-step program
CHUNK_STEP = "jit_chunk_step"     # the engine's chunk-prefill program
KV_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def percentile(xs, q: float) -> float | None:
    """Linear-interpolated percentile; a missing sample (+inf) is larger
    than every other, and a percentile that reaches one is +inf."""
    xs = sorted(xs)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100
    lo = int(np.floor(pos))
    frac = pos - lo
    if frac == 0:
        return float(xs[lo])
    a, b = xs[lo], xs[lo + 1]
    return float("inf") if b == float("inf") else float(a + (b - a) * frac)


def decode_calls(run) -> list:
    """Live lengths of the active slots of each traced decode step."""
    return [d for _, d, _ in run.traced_steps() if d]


def chunk_calls(run) -> list:
    """``(start, size, valid)`` of each traced chunk call."""
    return [c for _, _, chunks in run.traced_steps() for c in chunks]


def step_ms(run, program: str) -> float | None:
    times = run.trace.program_times(program) if run.trace else []
    return float(np.mean(times)) * 1e3 if times else None


def _per_traced(run, program: str, values: list) -> tuple | None:
    """(work over the traced programs, their device seconds)."""
    times = run.trace.program_times(program) if run.trace else []
    if not times or not values:
        return None
    return float(np.mean(values)) * len(times), float(np.sum(times))


def step_mfu(run, program: str) -> float | None:
    """Model operations of the traced steps over their device time times
    the chip's bf16 peak, in %."""
    if program == DECODE_STEP:
        work = [flops.decode_step(run.arch, d) for d in decode_calls(run)]
    else:
        work = [flops.chunk_step(run.arch, s, v)
                for s, _, v in chunk_calls(run)]
    got = _per_traced(run, program, work)
    if got is None:
        return None
    total, secs = got
    return 100.0 * total / (secs * run.peaks["flops_bf16"])


def kernel_roofline(run, kernel: str, program: str) -> float | None:
    """Least time the chip could take for the kernel's work over the
    kernel's traced time, in %."""
    mod = spec.kernel_cost(kernel)
    kv_bytes = KV_BYTES[run.cfg["serve"]["kv_format"]]
    calls = decode_calls(run) if program == DECODE_STEP else chunk_calls(run)
    least = [peaks.least_time(*mod.cost(run.arch, c, kv_bytes),
                              run.device["kind"]) for c in calls]
    # per-step least times, scaled to the traced programs' count
    got = _per_traced(run, program, least)
    kernel_s = (run.trace.kernel_seconds(program, mod.TARGET)
                if run.trace else 0.0)
    if got is None or kernel_s <= 0:
        return None
    return 100.0 * got[0] / kernel_s


def idle_share(run) -> float | None:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
