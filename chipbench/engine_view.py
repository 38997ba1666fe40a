"""Run one benchmark cell once and read it through the engine's own spans,
request timestamps and counters.

    python -m chipbench.engine_view --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--trace-seconds <t>] \
        [--keep-trace <file.xplane.pb.gz>] [--out <file.json>]

The window is served exactly as ``chipbench.run`` serves it (the same
set-up, traffic and trace of the window's last seconds).  The last line
of standard output is one JSON object with:

* ``end_to_end``: the cell's end-to-end metrics of this window;
* ``per_layer``: every per-layer metric of the cell that finds something
  to read, and the engine-layer metrics of ``chipbench.engine_spans``;
* ``consistency``: the engine's clock against the client's: requests
  whose ``first_token_at - submitted_at`` exceeds the client's
  ``first - due``, the median interval between steps, and the slots
  prefilling plus decoding per step;
* ``occupancy``: RUNNING slots per decode step, and the fewest RUNNING
  slots seen by each request that waited long for admission;
* with ``--trace 1``: the idle gaps between device operations by the
  innermost host span, the longest gaps, host time per engine phase,
  waits by ``what``, garbage collections, and the device time of each
  named kernel;
* ``span_cost_us``: one span's cost with the profiler off and on.

Unlike ``chipbench.run`` it does not check the served tokens.
"""
from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import shutil
import statistics
import sys
import time

from chipbench import engine_spans, run, spec, trace

NEW_METRICS = ("admit_wait_p90_ms", "first_token_wait_p90_ms",
               "prefill_slots_mean", "step_host_self_ms",
               "idle_host_work_share")
CLIENT_LABELS = ("chipbench.submit", "chipbench.wait_for_arrival")


def consistency(r: run.Run, layer: dict) -> dict:
    clients = [c for c in r.window.clients
               if c.state is not None and c.first is not None]
    over = [(c.state.first_token_at - c.state.submitted_at)
            - (c.first - c.due) for c in clients
            if getattr(c.state, "first_token_at", None) is not None]
    steps = [t for t, _, _ in r.window.steps]
    gaps = [b - a for a, b in zip(steps, steps[1:])]
    return {"requests": len(clients),
            "engine_ttft_over_client": sum(1 for d in over if d > 0),
            "engine_ttft_minus_client_max_ms":
                max(over) * 1e3 if over else None,
            "step_interval_median_ms":
                statistics.median(gaps) * 1e3 if gaps else None,
            "slots_busy": (layer.get("prefill_slots_mean", 0.0)
                           + layer.get("decode_batch_mean", 0.0))}


def occupancy(r: run.Run, long_s: float = 0.5) -> dict:
    """RUNNING slots after each decode step (the harness's step records),
    and for the requests that waited over ``long_s`` for admission the
    fewest RUNNING slots seen while they waited."""
    steps = [(t, len(d)) for t, d, _ in r.window.steps if d is not None]
    if not steps:
        return {}
    most = max(n for _, n in steps)
    least = []
    for c in r.window.clients:
        st = c.state
        if getattr(st, "admitted_at", None) is None:
            continue
        if st.admitted_at - st.submitted_at > long_s:
            during = [n for t, n in steps
                      if st.submitted_at <= t <= st.admitted_at]
            least.append(min(during, default=None))
    return {"running_mean": statistics.mean(n for _, n in steps),
            "running_max": most,
            "steps_at_max_share":
                sum(n == most for _, n in steps) / len(steps),
            "long_waits": len(least),
            "long_waits_least_running": least}


def _interval_total(spans, name: str) -> float:
    return sum(b - a for a, b, n, _ in spans if n == name) / 1e9


def largest_gaps(summary: engine_spans.EngineTrace, n: int = 10) -> list:
    """The ``n`` longest idle gaps between device operations, in ms, each
    with the innermost ``serving.*`` span (a wait with its ``what``)."""
    host = [(a, b, f"{name}:{st['what']}" if name == "serving.wait"
             else name) for a, b, name, st in summary.spans]
    starts = [a for a, _, _ in host]
    gaps = sorted((b - a, a, b) for dev in summary.gaps for a, b in dev)
    return [[d / 1e6, trace._label(host, starts, (a + b) / 2)]
            for d, a, b in reversed(gaps[-n:])]


def trace_view(summary: engine_spans.EngineTrace) -> dict:
    spans = summary.spans
    between = sum(summary.idle.values())
    serving = sum(v for k, v in summary.idle.items()
                  if k.startswith(engine_spans.PREFIX) or k in CLIENT_LABELS)
    waits = collections.defaultdict(float)
    for a, b, name, st in spans:
        if name == "serving.wait":
            waits[st.get("what", "?")] += (b - a) / 1e9
    gcs = [(b - a) / 1e9 for a, b, name, _ in spans if name == "serving.gc"]
    names = sorted({n for _, _, n, _ in spans})
    return {
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "idle_between_ops_s": between,
        "idle_between_ops_labelled_share":
            serving / between if between else None,
        "idle_between_ops_bare_step_share":
            summary.idle.get("chipbench.step", 0.0) / between
            if between else None,
        "idle_labels": sorted(summary.idle.items(), key=lambda kv: -kv[1]),
        "phase_host_s": {n: _interval_total(spans, n) for n in names},
        "phase_count": {n: sum(1 for _, _, m, _ in spans if m == n)
                        for n in names},
        "waits_s": dict(waits),
        "gc": {"count": len(gcs), "total_s": sum(gcs),
               "max_s": max(gcs, default=0.0)},
        "largest_gaps": largest_gaps(summary),
        "kernel_names": summary.kernel_names,
        "custom_calls": sorted(k[:100] for k in summary.ops
                               if "tpu_custom_call" in k)[:8],
        "breakdown": summary.breakdown()}


def span_cost_us(n: int = 20000) -> dict:
    """Microseconds per span with one stat (a ``TraceAnnotation``, as
    ``repro.core.spans.span`` makes) with the profiler off, then on."""
    import jax

    def per_span():
        t0 = time.perf_counter()
        for i in range(n):
            with jax.profiler.TraceAnnotation("serving.cost", tick=i):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    off = per_span()
    d = os.path.join(run.OUT_DIR, "span-cost")
    jax.profiler.start_trace(d)
    try:
        on = per_span()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return {"off": off, "on": on}


def view(bench, name: str, seed: int, seconds: float, traced: bool, *,
         keep_trace: str | None = None, require_tpu: bool = True,
         log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> dict:
    cell = bench.cell(name)
    s = run.serve(bench, cell, seed, seconds, t_start=run.T_START,
                  trace=traced, require_tpu=require_tpu, log=log)
    summary = None
    if traced:
        summary = engine_spans.reduce(s.trace_dir)
        if keep_trace:
            with open(trace.find(s.trace_dir), "rb") as f, \
                    gzip.open(keep_trace, "wb") as g:
                shutil.copyfileobj(f, g)
        shutil.rmtree(s.trace_dir, ignore_errors=True)
    r = run.Run(s.arch, s.cfg, s.device, s.window, summary,
                s.setup_compile_s)
    layer = {}
    names = [m["name"] for m in bench.metrics(cell, "per_layer")]
    for m in names + [m for m in NEW_METRICS if m not in names]:
        value = spec.metric_reader(m).read(r)
        if value is not None:
            layer[m] = value
    out = {"workload": name, "seed": seed, "trace": traced,
           "end_to_end": run.end_to_end(s.window, s.setup_s, seconds),
           "per_layer": layer,
           "consistency": consistency(r, layer),
           "occupancy": occupancy(r),
           "device": dict(s.device, memory_peak_bytes=s.peak),
           "attempted": len(s.window.clients), "failed": s.failed}
    if summary is not None:
        out["trace_view"] = trace_view(summary)
    out["span_cost_us"] = span_cost_us()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--trace-seconds", type=float, default=run.TRACE_SECONDS,
                   help="length of the traced end of the window")
    p.add_argument("--keep-trace", help="write the trace here, gzipped")
    p.add_argument("--out", help="also write the JSON object here")
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    run.TRACE_SECONDS = args.trace_seconds
    bench = spec.Benchmark()
    run.configure_jax()
    try:
        out = view(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), keep_trace=args.keep_trace)
    except run.NoChip as e:
        print(f"[engine_view] {e}", file=sys.stderr, flush=True)
        return 2
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
