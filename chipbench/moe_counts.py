"""The MoE engine's routing counters over a run's window, per program run,
and a Pallas kernel's traced time found by its name.

The engine sums, over layers and steps, the (token, choice) rows routed to
the experts this chip holds (``moe_rows_here``) and the held experts given
at least one row (``moe_experts_touched``); decode steps alone under the
``decode_`` prefix, so chunk steps hold the rest.  A program without them
(one that serves no MoE layer) reads ``None``.
"""
from __future__ import annotations

from chipbench import engine_spans

NAMES = ("moe_rows_here", "moe_experts_touched")


def _delta(run, name: str) -> float:
    w = run.window
    return w.counters1[name] - w.counters0.get(name, 0)


def per_decode_step(run) -> tuple[float, float] | None:
    """(rows routed here, experts touched) per decode step, all layers,
    as the window's mean."""
    steps = _delta(run, "decode_steps")
    if steps <= 0 or any("decode_" + n not in run.window.counters1
                         for n in NAMES):
        return None
    return tuple(_delta(run, "decode_" + n) / steps for n in NAMES)


def per_chunk_step(run) -> tuple[float, float] | None:
    """(rows routed here, experts touched) per chunk-prefill program run,
    all layers, as the window's mean."""
    steps = _delta(run, "prefill_chunks")
    if steps <= 0 or any(n not in run.window.counters1 for n in NAMES):
        return None
    return tuple((_delta(run, n) - _delta(run, "decode_" + n)) / steps
                 for n in NAMES)


def kernel_seconds(trace, name: str) -> float:
    """Device seconds of the Pallas calls named ``name`` (their HLO
    instruction ``%<name>.N = ... custom-call``), in every program."""
    total = 0.0
    for op, secs in trace.ops.items():
        m = engine_spans._OP_NAME.match(op)
        if m and m.group(1) == name and engine_spans._KERNEL_TARGET in op:
            total += secs
    return total
