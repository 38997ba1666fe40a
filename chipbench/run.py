"""Run one benchmark cell once.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell's configuration through the program's own entry
(``repro.launch.serve.make_engine``), makes its weights on the device from
the seed, and warms every program the cell's traffic will run.  Then the
measured window opens: the seeded requests are submitted at their
scheduled times (open loop) or by a fixed set of clients (closed loop),
between ``step()`` calls, and the harness reads each request's generated
tokens after every step, as a client would see them.  After the window the
served tokens are checked against the plain reference (``correct``), and
the last line of standard output is one JSON object with the cell's
metrics: its end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1`` (a profiler trace of the window's last seconds).

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()        # process start, as near as Python gets

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

# libtpu logs to a fixed /tmp path unless told otherwise: nothing is
# written outside the checkout and the run's own directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from chipbench import judge, loadgen, spec  # noqa: E402
from chipbench.readings import percentile  # noqa: E402

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
OUT_DIR = os.path.join(spec.ROOT, "chipbench_out")
TRACE_SECONDS = 6.0        # a traced run traces the window's last seconds
DRAIN_LIMIT_S = 60.0       # wait this long past the close for first tokens


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell needs."""


class CompileClock:
    """Sums the XLA backend-compile seconds JAX reports through its
    monitoring events (persistent-cache hits compile nothing)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.secs += duration
            self.count += 1


def configure_jax() -> None:
    """Persistent compile cache inside the checkout, at a fixed path, with
    every program written to it (JAX skips programs that compile in under
    a second by default, which were most of the served steps)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(require_tpu: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# the client side of the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Client:
    """One request as its client sees it."""
    planned: loadgen.Planned
    due: float                        # scheduled send time (host clock)
    sent: float | None = None
    state: object = None              # the engine's RequestState
    admitted: float | None = None     # left WAITING
    times: list = dataclasses.field(default_factory=list)  # token arrivals
    refused: bool = False

    @property
    def first(self) -> float | None:
        return self.times[0] if self.times else None

    @property
    def done(self) -> bool:
        return self.refused or (self.state is not None and self.state.done)


@dataclasses.dataclass
class Window:
    """What the window recorded: clients, per-step host records and the
    engine's counters at its edges."""
    t0: float
    close: float
    end: float
    clients: list
    steps: list          # (time, decode lengths or None, [(start, size, valid)])
    counters0: dict
    counters1: dict
    compiles: int
    compile_s: float
    router: dict                     # the router's stats (4-chip cells)
    trace_span: tuple | None = None


class Driver:
    """Submits requests between engine steps and watches their tokens."""

    def __init__(self, engine, router=None):
        self.eng = engine
        self.router = router
        self.front = router if router is not None else engine

    def engines(self):
        if self.router is None:
            return [self.eng]
        return [r.engine for r in self.router.replicas.values()]

    def submit(self, request):
        from repro.runtime.serving import AdmissionRejected
        try:
            return self.front.submit(request)
        except AdmissionRejected:
            return None

    def busy(self) -> bool:
        return not (self.router.all_done if self.router is not None
                    else self.eng.scheduler.all_done)

    def step(self) -> None:
        self.front.step()

    def counters(self) -> dict:
        out: dict = {}
        for e in self.engines():
            for k, v in e.stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[k] = out.get(k, 0) + v
        return out


def _request(p: loadgen.Planned):
    from repro.runtime.serving import GREEDY, Request, SamplingParams
    sampling = GREEDY if p.greedy else SamplingParams(
        temperature=p.temperature, top_p=p.top_p, seed=p.seed % (1 << 31))
    return Request(uid=p.index, prompt=p.prompt,
                   max_new_tokens=p.max_new_tokens, sampling=sampling)


def drive(driver: Driver, plan, mix: dict, seconds: float, clock,
          compiles: CompileClock, tracer=None) -> Window:
    """The measured window: open loop by schedule, closed loop by clients.
    Runs past the close (without new requests) until every request sent
    in the window has its first token, at most ``DRAIN_LIMIT_S``."""
    from jax.profiler import TraceAnnotation as span
    from repro.runtime.serving import Status
    t0 = clock()
    close = t0 + seconds
    closed_loop = mix["loop"] == "closed"
    clients = [Client(p, due=t0 + p.at) for p in plan]
    queue = list(clients)
    if closed_loop:
        idle = int(mix["clients"])
        queue, spare = queue[:idle], queue[idle:]
    live: list[Client] = []
    steps = []
    c0 = driver.counters()
    k0 = (compiles.count, compiles.secs)
    nxt = 0
    while True:
        now = clock()
        # send what is due (closed loop: one per client that is free);
        # a request due before the close is sent even if a long step ran
        # past it, and its latency counts from when it was due
        while (nxt < len(queue) and queue[nxt].due <= now
               and queue[nxt].due < close):
            c = queue[nxt]
            nxt += 1
            c.sent = now
            with span("chipbench.submit"):
                c.state = driver.submit(_request(c.planned))
            c.refused = c.state is None
            if not c.refused:
                live.append(c)
        if tracer is not None:
            tracer.tick(now, close)
        if driver.busy():
            before = {id(c): (c.state.chunk_idx, c.state.prefill_pos)
                      for c in live}
            d0 = driver.counters()["decode_steps"]
            with span("chipbench.step"):
                driver.step()
            t = clock()
            chunks = []
            for c in live:
                idx0, pos0 = before[id(c)]
                st = c.state
                pos = pos0
                for size in (st.chunk_plan or [])[idx0:st.chunk_idx]:
                    chunks.append((pos, size,
                                   min(size, st.prompt_len - pos)))
                    pos += size
            decode = None
            if driver.counters()["decode_steps"] > d0:
                decode = [c.state.prompt_len + len(c.state.generated)
                          for c in live if c.state.status is Status.RUNNING]
            steps.append((t, decode, chunks))
        else:
            t = clock()
            wait = (queue[nxt].due - t if nxt < len(queue) and t < close
                    else 0.001)
            with span("chipbench.wait_for_arrival"):
                time.sleep(min(max(wait, 0.0), 0.01))
        # what each client sees after this step
        t = clock()
        still = []
        for c in live:
            st = c.state
            n = len(st.generated)
            if n > len(c.times):
                c.times.extend([t] * (n - len(c.times)))
            if c.admitted is None and st.status is not Status.WAITING:
                c.admitted = t
            if st.done:
                if closed_loop and spare and t < close:
                    nc = spare.pop(0)
                    nc.due = t
                    queue.append(nc)
            else:
                still.append(c)
        live = still
        if t >= close:
            if tracer is not None:
                tracer.stop()
            # every request due before the close is sent, and waited for
            unsent = nxt < len(queue) and queue[nxt].due < close
            waiting = [c for c in clients if c.sent is not None
                       and c.first is None and not c.done]
            if not unsent and (not waiting or t > close + DRAIN_LIMIT_S):
                break
    sent = [c for c in clients if c.sent is not None]
    return Window(t0=t0, close=close, end=clock(), clients=sent,
                  steps=steps, counters0=c0, counters1=driver.counters(),
                  compiles=compiles.count - k0[0],
                  compile_s=compiles.secs - k0[1],
                  router=dict(driver.router.stats) if driver.router else {},
                  trace_span=tracer.span if tracer is not None else None)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def engine_config(arch, model, cfg: dict, mix: dict, seed: int):
    """The cell's EngineConfig: rows per slot from the mix's longest request,
    slot count from the configuration's cache budget."""
    import jax
    from repro.launch.serve import arena_rows
    from repro.runtime.serving import EngineConfig
    serve = cfg["serve"]
    buckets = tuple(serve["chunk_buckets"])
    rows = arena_rows(*loadgen.max_lengths(mix), buckets)
    one = jax.eval_shape(lambda: model.init_cache(
        1, rows, kv_format=serve["kv_format"]))
    per_slot = sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(one))
    slots = int(serve["cache_budget_bytes"]) // per_slot
    return EngineConfig(max_slots=slots, max_seq=rows,
                        prefill_chunks=buckets,
                        kv_format=serve["kv_format"],
                        base_seed=seed % (1 << 31))


def warm_up(driver: Driver, plan, econf, vocab: int) -> None:
    """Run every program the window will: each chunk size its prompts use
    (one prompt of exactly that size), and the decode steps and
    first-token paths of the kinds of request it sends (sampled, greedy, or
    both, the greedy ones outliving the sampled so that both the sampled
    step and its greedy twin run)."""
    from repro.runtime.serving import chunking
    sizes = sorted({s for p in plan
                    for s in chunking.chunk_plan(p.prompt.size,
                                                 econf.prefill_chunks)})
    kinds = sorted({p.greedy for p in plan})      # greedy last: it outlives
    rng = np.random.default_rng(0)
    for i, size in enumerate(sizes):
        greedy = kinds[min(i, len(kinds) - 1)]
        driver.submit(_request(loadgen.Planned(
            index=-1 - i, at=0.0,
            prompt=rng.integers(0, vocab, size).astype(np.int32),
            max_new_tokens=6 if greedy else 3,
            temperature=0.0 if greedy else 0.7, top_p=0.95, seed=i)))
    while driver.busy():
        driver.step()


def build(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
          device: dict, rate_rps: float | None = None):
    """Model, weights, engine (or router) and the run's requests."""
    import jax
    from repro.launch.serve import make_engine, make_router
    from repro.models import registry
    family = spec.model_module(cfg["model"])
    arch = family.program_config(cfg)
    model = registry.build_model(arch)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = family.make_params(cfg, shapes, seed)
    jax.block_until_ready(params)
    plan = loadgen.generate(mix, seed, seconds, arch.vocab, rate_rps)
    econf = engine_config(arch, model, cfg, mix, seed)
    bundle = registry.Bundle(name=arch.name, cfg=arch, model=model)
    if cell["chips"] > 1:
        router = make_router(bundle, params, config=econf,
                             replicas=cell["chips"])
        driver = Driver(None, router)
    else:
        driver = Driver(make_engine(bundle, params, config=econf))
    return family, arch, params, plan, econf, driver


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_for_check(clients, seed: int, want_tokens: int) -> list:
    """Finished greedy requests drawn from the seed: the longest first,
    then at random until ``want_tokens`` served tokens."""
    from repro.runtime.serving import Status
    done = [c for c in clients if c.planned.greedy and c.state is not None
            and c.state.status is Status.FINISHED]
    if not done:
        return []
    done.sort(key=lambda c: c.planned.index)
    longest = max(done, key=lambda c: (c.state.prompt_len
                                       + len(c.state.generated)))
    rng = np.random.default_rng(seed + 1)
    picked = [longest]
    for i in rng.permutation(len(done)):
        if sum(len(p.state.generated) for p in picked) >= want_tokens:
            break
        if done[i] is not longest:
            picked.append(done[i])
    return picked


def served_gaps(family, cfg: dict, params, picked) -> list:
    """Each checked request's served-token gaps under the reference."""
    out = []
    for c in picked:
        st = c.state
        toks = np.asarray(st.generated, np.int32)
        fed = np.concatenate([c.planned.prompt, toks[:-1]])
        ref = family.logits(cfg, params, fed,
                            judge.rows(st.prompt_len, toks.size))
        out.append(judge.served_gaps(ref, toks))
    return out


def check(family, cfg: dict, params, picked, limits: dict) -> dict:
    """The compared numbers of a run, each beside its limit: the widest
    gap of a served greedy token below the reference's best, outputs cut
    short, and how many tokens were checked."""
    gaps = served_gaps(family, cfg, params, picked)
    every = np.concatenate(gaps) if gaps else np.zeros(0)
    short = sum(int(len(c.state.generated) != c.planned.max_new_tokens)
                for c in picked)
    return {"max_logit_gap": {"value": float(every.max()) if every.size
                              else 0.0, "limit": limits["max_logit_gap"]},
            "short_outputs": {"value": short, "limit": 0},
            "checked_tokens": {"value": int(every.size),
                               "limit": limits["checked_tokens"]}}


def passes(numbers: dict) -> bool:
    """Every number at or under its limit; checked tokens at or over."""
    return all(v["value"] >= v["limit"] if k == "checked_tokens"
               else v["value"] <= v["limit"] for k, v in numbers.items())


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(win: Window, setup_s: float, seconds: float) -> dict:
    ttft = [(c.first - c.due) * 1e3 if c.first is not None else np.inf
            for c in win.clients]
    gaps = []
    for c in win.clients:
        ts = [t for t in c.times if t <= win.close]
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
    valid_in = sum(v for t, _, chunks in win.steps if t <= win.close
                   for _, _, v in chunks)
    out_tokens = sum(1 for c in win.clients for t in c.times
                     if t <= win.close)
    return {"ttft_p90_ms": percentile(ttft, 90),
            "itl_p95_ms": percentile(gaps, 95),
            "tok_per_s": (valid_in + out_tokens) / seconds,
            "setup_s": setup_s}


class Run:
    """What a per-layer metric reader may read."""

    def __init__(self, arch, cfg, device, window, trace, compile_s):
        from chipbench.peaks import peaks
        self.arch = arch
        self.cfg = cfg
        self.device = device
        self.peaks = peaks(device["kind"])
        self.window = window
        self.trace = trace
        self.compile_s = compile_s

    def traced_steps(self):
        """Host step records inside the traced span."""
        if self.window.trace_span is None:
            return []
        a, b = self.window.trace_span
        return [s for s in self.window.steps if a <= s[0] <= b]


def per_layer(bench, cell, run: Run) -> dict:
    """Every per-layer metric the cell lists; one that finds nothing to
    read (a kernel or program name the trace no longer holds) fails the
    run, so that no metric falls silent unseen."""
    out = {}
    for m in bench.metrics(cell, "per_layer"):
        value = spec.metric_reader(m["name"]).read(run)
        if value is None:
            raise RuntimeError(f"per-layer metric {m['name']!r} of "
                               f"{cell['name']!r} found nothing to read")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Tracer:
    """Traces the window's last ``TRACE_SECONDS`` with the JAX profiler."""

    def __init__(self, directory: str, seconds: float):
        self.dir = directory
        self.lead = min(TRACE_SECONDS, seconds)
        self.span = None
        self._start = None

    def tick(self, now: float, close: float) -> None:
        import jax
        if self._start is None and now >= close - self.lead:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, no Python calls
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._start = time.perf_counter()

    def stop(self) -> None:
        import jax
        if self._start is not None and self.span is None:
            self.span = (self._start, time.perf_counter())
            jax.profiler.stop_trace()


@dataclasses.dataclass
class Served:
    """A cell's window, served and closed, with what the check needs."""
    family: object
    arch: object
    cfg: dict
    params: object
    window: Window
    picked: list
    device: dict
    peak: int
    setup_s: float
    setup_compile_s: float
    failed: int
    trace_dir: str | None


def serve(bench, cell: dict, seed: int, seconds: float, *, t_start: float,
          trace: bool = False, require_tpu: bool = True,
          rate_rps: float | None = None,
          log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> Served:
    """Set-up, the measured window, and the program's state freed."""
    import jax
    cfg = bench.config(cell)
    mix = bench.traffic(cell)
    limits = bench.limits(cell)
    device = device_info(require_tpu, cell["chips"])
    compiles = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        family, arch, params, plan, econf, driver = build(
            cell, cfg, mix, seed, seconds, device, rate_rps)
        warm_up(driver, plan, econf, arch.vocab)
        setup_compile_s = compiles.secs
        log(f"[chipbench] {cell['name']} seed={seed}: {arch.n_layers} "
            f"layers d={arch.d_model}, {econf.max_slots} slots x "
            f"{econf.max_seq} rows ({econf.kv_format}), {len(plan)} "
            f"requests planned; set-up compiled {compiles.count} programs "
            f"in {setup_compile_s:.3f} s")
        tracer = None
        if trace:
            tracer = Tracer(os.path.join(OUT_DIR, f"trace-{cell['name']}"),
                            seconds)
        t0 = time.perf_counter()
        win = drive(driver, plan, mix, seconds, time.perf_counter,
                    compiles, tracer)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    devices = [d for e in driver.engines()
               for d in ([e.device] if e.device is not None
                         else jax.devices()[:1])]
    peak = memory_peak(devices)
    log(f"[chipbench] window: {len(win.clients)} requests sent, "
        f"{win.counters1['decode_steps'] - win.counters0['decode_steps']} "
        f"decode steps, {win.compiles} compiles inside the window "
        f"({win.compile_s:.3f} s); {win.end - win.close:.3f} s past the "
        f"close for first tokens")
    failed = sum(1 for c in win.clients if c.refused or c.first is None
                 or (c.state is not None and c.state.status.name
                     in ("FAILED", "TIMED_OUT")))
    picked = sample_for_check(win.clients, seed, limits["checked_tokens"])
    del driver
    gc.collect()
    return Served(family, arch, cfg, params, win, picked, device,
                  peak, t0 - t_start, setup_compile_s, failed,
                  tracer.dir if tracer is not None else None)


def run_cell(bench, name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> dict:
    """One run of one cell; returns the result object."""
    cell = bench.cell(name)
    s = serve(bench, cell, seed, seconds, t_start=t_start, trace=trace,
              require_tpu=require_tpu, log=log)
    t_check = time.perf_counter()
    numbers = check(s.family, s.cfg, s.params, s.picked, bench.limits(cell))
    log(f"[chipbench] check: {len(s.picked)} requests, "
        f"{numbers['checked_tokens']['value']} tokens against the "
        f"reference in {time.perf_counter() - t_check:.3f} s")
    result = {"correct": passes(numbers), "attempted": len(s.window.clients),
              "failed": s.failed}
    summary = None
    if trace:
        from chipbench import trace as trace_mod
        summary = trace_mod.reduce(s.trace_dir)
        shutil.rmtree(s.trace_dir, ignore_errors=True)
        run = Run(s.arch, s.cfg, s.device, s.window, summary,
                  s.setup_compile_s)
        result["metrics"] = per_layer(bench, cell, run)
    else:
        e2e = end_to_end(s.window, s.setup_s, seconds)
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in bench.metrics(cell, "end_to_end")}
    result["device"] = dict(s.device, memory_peak_bytes=s.peak)
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["compiles_in_window"] = s.window.compiles
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    bench = spec.Benchmark()
    configure_jax()
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr, flush=True)
        return 2
    for k, v in result["check"].items():
        print(f"[chipbench] check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
