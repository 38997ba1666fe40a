"""Dispatcher model (paper §VI.A, Fig. 2-3 — C6).

Ara's throughput on medium/short vectors is limited by how fast the *scalar*
core (CVA6) can issue vector instructions — the paper measures the real
system against an "ideal dispatcher" (a pre-filled instruction queue) and
shows a 1.54× swing from scalar-memory-path sizing alone.

The framework analogue: device work is issued by the host Python loop.  Three
dispatch modes reproduce the paper's experiment:

  * ``blocking``  — ``block_until_ready`` after every step: the host is in
    the critical path (the paper's worst case, small D-cache/AXI).
  * ``queued(d)`` — async dispatch keeping ≤ d steps in flight: the real
    system with a d-deep dispatcher queue (Ara's accelerator port).
  * ``ideal``     — the whole step-loop is one compiled ``lax.scan``: the
    pre-filled queue; the device never waits for the host.

``DispatchBench`` measures steps/s in each mode (benchmarks/bench_dispatch).
The serving path uses ``queued`` with donated buffers; training uses
``ideal`` inner loops of `scan_steps` steps between host-visible events
(checkpoint/logging), which is how a 1000-node deployment avoids host jitter
becoming a global straggler.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable

import jax
from jax import lax

from repro.core import spans


class DispatchQueue:
    """Bounded async dispatch of a compiled step function.

    Keeps at most ``depth`` dispatched-but-unfinished steps in flight.  With
    depth=0 it degrades to fully blocking dispatch.

    ``inflight_of``: optional projection of the step output to the value the
    queue blocks on for backpressure.  A step whose state buffers are
    *donated* into the next step must not leave those buffers in the queue —
    blocking on a donated buffer raises — so a donating caller passes e.g.
    ``lambda out: out[-1]`` to track a never-donated output (the serving
    engine's host-readback token copy).  Any output of the step becomes
    ready exactly when the step completes, so backpressure is unchanged.

    ``counts``: the dict whose ``"host_blocked_s"`` the backpressure
    blocks add to (``spans.wait``, ``what="backpressure"``); the serving
    engine passes its ``stats``.  Without one the queue keeps its own.
    """

    def __init__(self, step_fn: Callable, *, depth: int = 2,
                 inflight_of: Callable[[Any], Any] = lambda out: out,
                 counts: dict | None = None):
        self.step_fn = step_fn
        self.depth = depth
        self._inflight_of = inflight_of
        self._inflight: collections.deque = collections.deque()
        self.counts = counts if counts is not None else {spans.BLOCKED: 0.0}

    def _block(self, value: Any) -> None:
        with spans.wait("backpressure", self.counts):
            jax.block_until_ready(value)

    def submit(self, state: Any, *args) -> Any:
        out = self.step_fn(state, *args)
        if self.depth == 0:
            self._block(self._inflight_of(out))
            return out
        self._inflight.append(self._inflight_of(out))
        while len(self._inflight) > self.depth:
            self._block(self._inflight.popleft())
        return out

    def drain(self) -> None:
        while self._inflight:
            self._block(self._inflight.popleft())


def ideal_dispatcher(step_fn: Callable, num_steps: int, *, unroll: int = 1):
    """Compile ``num_steps`` applications of ``step_fn`` into one call.

    ``step_fn(state) -> state``.  This is the paper's pre-filled instruction
    queue: issue latency is paid once for the whole run.
    """
    def run(state):
        def body(s, _):
            return step_fn(s), None
        out, _ = lax.scan(body, state, None, length=num_steps, unroll=unroll)
        return out
    return jax.jit(run, donate_argnums=0)


def measure_steps_per_sec(run_once: Callable[[], Any], *, repeats: int = 3,
                          steps_per_call: int = 1) -> float:
    """Wall-clock steps/s of ``run_once`` (which must block on completion)."""
    run_once()  # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run_once())
        best = min(best, time.perf_counter() - t0)
    return steps_per_call / best
