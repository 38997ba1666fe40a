"""Readings that set a cell's limits and rate, in one process per call.

    python -m chipbench.calibrate --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control-seeds 1,2,3]
    python -m chipbench.calibrate --workload <cell> --seconds <s> \
        --seeds 1 --rates 1.5,2,2.5,3

Readings (no ``--rates``): for each seed the cell's window is served as a
run serves it, and the largest gap of a served greedy token below the
reference's best is printed (the lower reading of ``max_logit_gap``).  For each control seed
the control is read too: the reference computed in float8 (the precision
step below the configuration's bfloat16), teacher-forced on the same
served tokens; at each position the token it would put first is scored
by the float32 reference, and the largest gap is the upper reading.

Sweep (``--rates``): the window at each offered rate, with the numbers
that show where the queue starts to grow (the cell's knee).  The
benchmark's own runs run neither.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

from chipbench import judge, readings, run, spec


def control_gaps(served: run.Served) -> list:
    """Per checked request, the gap of the token the control puts first
    at each served position, scored by the reference."""
    out = []
    for c in served.picked:
        toks = np.asarray(c.state.generated, np.int32)
        fed = np.concatenate([c.planned.prompt, toks[:-1]])
        rows = judge.rows(c.state.prompt_len, toks.size)
        ref = served.family.logits(served.cfg, served.params, fed, rows,
                                   "f32")
        ctl = served.family.logits(served.cfg, served.params, fed, rows,
                                   "fp8")
        out.append(judge.control_gaps(ref, ctl, toks.size))
    return out


def control_gap(served: run.Served) -> float:
    return max(float(g.max()) for g in control_gaps(served))


def spread(gaps: list) -> dict:
    """How a run's gaps are spread: the compared maximum and, beside it,
    the mean, the 99th percentile and the share of tokens with a gap."""
    x = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"max": float(x.max()), "mean": float(x.mean()),
            "p99": float(np.percentile(x, 99)),
            "nonzero_share": float(np.mean(x > 0)), "tokens": int(x.size)}


def sweep_row(s: run.Served, rate: float, seconds: float) -> dict:
    w = s.window
    ttft = [(c.first - c.due) * 1e3 if c.first is not None else np.inf
            for c in w.clients]
    late = sum(1 for c in w.clients if c.first is None or c.first > w.close)
    e2e = run.end_to_end(w, s.setup_s, seconds)
    return {"rate_rps": rate, "sent": len(w.clients),
            "first_after_close": late,
            "ttft_p50_ms": readings.percentile(ttft, 50),
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"], "tok_per_s": e2e["tok_per_s"],
            "drain_s": w.end - w.close}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--rates", default="")
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    run.configure_jax()
    bench = spec.Benchmark()
    cell = bench.cell(args.workload)
    limits = bench.limits(cell)
    seeds = [int(x) for x in args.seeds.split(",")]
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    rates = [float(x) for x in args.rates.split(",") if x]
    for seed in seeds:
        for rate in rates or [None]:
            t0 = time.perf_counter()
            s = run.serve(bench, cell, seed, args.seconds, t_start=t0,
                          rate_rps=rate)
            if rate is not None:
                out = sweep_row(s, rate, args.seconds)
            else:
                numbers = run.check(s.family, s.cfg, s.params, s.picked,
                                    limits)
                out = {"seed": seed, "correct": run.passes(numbers),
                       "check": {k: v["value"] for k, v in numbers.items()},
                       "failed": s.failed, "attempted": len(s.window.clients),
                       "compiles_in_window": s.window.compiles,
                       "peak_bytes": s.peak}
                out["program_gaps"] = spread(run.served_gaps(
                    s.family, s.cfg, s.params, s.picked))
                if seed in controls:
                    ctl = spread(control_gaps(s))
                    out["control_max_logit_gap"] = ctl["max"]
                    out["control_gaps"] = ctl
            out["wall_s"] = time.perf_counter() - t0
            print("[calibrate] " + json.dumps(out), flush=True)
            del s
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
