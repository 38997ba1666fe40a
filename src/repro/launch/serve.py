"""Serving launcher: continuous batching over the dispatcher model (C6).

``python -m repro.launch.serve --arch <id> --requests 8 --gen 32``

The arch is built reduced (the smoke-test widths) unless ``--full`` asks for
its published widths; the persistent compilation cache follows
``launch/compile_cache.py``.

Built on :mod:`repro.runtime.serving`: a request queue + scheduler admits
and retires decode sequences every step, a slot-based paged KV cache holds
the batch, and decode steps flow through a ``DispatchQueue`` so the host
(the CVA6-analogue) stays out of the device's critical path.  ``--depth 0``
reproduces the paper's starved-dispatcher worst case; ``--slots`` smaller
than ``--requests`` exercises slot reuse; ``--pages`` under-provisions the
cache pool to exercise preemption + recompute.

Prefill knobs (the stripmined prompt-ingestion path):

  * ``--prefill-mode chunked`` cuts prompts into bucket-sized chunks
    (``--chunk-buckets``, default 32,64,128,256,512) interleaved with
    decode under a per-step token budget (``--prefill-budget``) — bounded
    compile churn, bounded long-prompt stalls.  Every LM family: dense/
    MoE append K/V rows, SSM/hybrid thread the SSD chunk recurrence
    through the slot's arena state.
  * ``--prompt-mix 64,128,512,2048`` serves a mixed-length workload
    (lengths cycle over the requests) — the traffic shape where chunked
    prefill pays: run it in both modes and compare the printed TTFT
    percentiles and ``prefill_compiles``.
  * ``--prefix-sharing`` (chunked mode only) turns on the copy-on-write
    prefix cache: requests whose prompts open with an already-ingested
    page-aligned token prefix fork onto the donor's pages by refcount
    and ingest only the unshared tail.  ``--prompt-mix shared-prefix``
    generates the matching workload — one common system prefix plus
    distinct per-request tails.

Sampling knobs (per-slot stochastic decode inside the compiled step):

  * ``--temperature/--top-k/--top-p/--min-p`` set the sampled requests'
    :class:`~repro.runtime.serving.SamplingParams`; ``--temperature 0``
    (the default) keeps every request on the bit-exact greedy path.
  * ``--seed`` is the run-level base seed; request *i* samples with seed
    ``base + i``, so a rerun with the same seed replays identical streams.
  * ``--sampling-mix f`` samples only a fraction ``f`` of the requests
    (evenly spread), the rest stay greedy — the mixed traffic shape the
    bench sweep measures.

Robustness knobs (the failure model; see serving/README.md):

  * ``--deadline-ms`` gives every request a wall-clock deadline; expiry
    departs it ``TIMED_OUT`` with its partial output (a clean prefix of
    the fault-free stream).
  * ``--fault-plan site:rate[:seed],...`` turns on deterministic fault
    injection (sites: alloc/chunk/decode/logits/draft).  Same plan + same
    traffic ⟹ the identical failure interleaving, replayable bit-exactly.
  * ``--health`` enables the degradation ladder; rung transitions and the
    fault/quarantine counters are printed after the run.

Multi-replica knobs (the router; see serving/README.md):

  * ``--replicas N`` serves the workload over N engine replicas behind a
    :class:`~repro.runtime.serving.Router` — independent arenas /
    schedulers / dispatch queues sharing one model object, replica *r*
    committed to device *r* (``launch.mesh.replica_mesh``; replicas share
    devices when they outnumber them).  A per-replica stats line is
    printed after the run.  Streams are bit-identical to ``--replicas 1``
    under every placement policy: the PRNG folds only (seed, position).
  * ``--placement least-pressure|round-robin|affinity`` picks where each
    request lands; ``affinity`` pins a request's session to the replica
    that served it before (requests are given cycling session ids).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import replica_mesh
from repro.models import registry
from repro.runtime.serving import (DEFAULT_BUCKETS, PLACEMENT_POLICIES,
                                   EngineConfig, GREEDY, HealthConfig,
                                   Request, Router, RouterConfig,
                                   SamplingParams, ServingEngine,
                                   SpecConfig, parse_fault_plan)


def parse_speculative(text: str) -> SpecConfig:
    """Parse ``--speculative draft=<arch>:k=<n>[:k-max=<n>][:adaptive=0|1]``
    into a :class:`SpecConfig`.  ``draft`` is a registry arch name (built
    reduced, sharing the target's vocab family)."""
    fields: dict = {}
    for part in text.split(":"):
        key, sep, val = part.partition("=")
        if not sep:
            raise ValueError(f"--speculative: expected key=value, got {part!r}")
        key = key.replace("-", "_")
        if key == "draft":
            fields[key] = val
        elif key in ("k", "k_max", "window", "draft_seed"):
            fields[key] = int(val)
        elif key == "adaptive":
            fields[key] = bool(int(val))
        elif key in ("low", "high", "ema"):
            fields[key] = float(val)
        else:
            raise ValueError(f"--speculative: unknown key {key!r}")
    if "draft" not in fields:
        raise ValueError("--speculative requires draft=<arch>")
    return SpecConfig(**fields)


def make_engine(bundle, params, *, config: EngineConfig = None,
                **fields) -> ServingEngine:
    """Build the engine from an :class:`EngineConfig` (or config fields)."""
    if config is None:
        config = EngineConfig(**fields)
    elif fields:
        config = config.replace(**fields)
    return ServingEngine(bundle.model, bundle.cfg, params, config=config)


def make_router(bundle, params, *, config: EngineConfig, replicas: int,
                placement: str = "least-pressure") -> Router:
    """``replicas`` engines behind a :class:`Router`, replica *r* on the
    *r*-th device of :func:`~repro.launch.mesh.replica_mesh`."""
    return Router(bundle.model, bundle.cfg, params,
                  config=RouterConfig(replicas=replicas, placement=placement,
                                      engine=config),
                  mesh=replica_mesh(replicas))


def arena_rows(max_prompt: int, gen: int, chunks=None,
               prefix: int = 0) -> int:
    """Slot arena depth for a workload: the longest prompt (+ VLM prefix)
    and its generation, the chunk padding slack (always under the smallest
    bucket) and one row."""
    return max_prompt + prefix + gen + (min(chunks) if chunks else 0) + 1


def sampling_plan(n_requests: int, *, temperature: float, top_k: int,
                  top_p: float, min_p: float, seed: int,
                  mix: float) -> list[SamplingParams]:
    """Per-request SamplingParams for a run: a ``mix`` fraction of the
    requests sample (evenly spread over arrival order, Bresenham-style),
    the rest decode greedily.  Request i's seed is ``seed + i`` so streams
    are distinct but the whole run replays from one base seed."""
    if temperature <= 0 or mix <= 0:
        return [GREEDY] * n_requests
    mix = min(mix, 1.0)
    return [
        SamplingParams(temperature=temperature, top_k=top_k, top_p=top_p,
                       min_p=min_p, seed=seed + i)
        if int((i + 1) * mix) > int(i * mix) else GREEDY
        for i in range(n_requests)
    ]


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def report_stats(eng: ServingEngine) -> None:
    """Print the engine + scheduler counters and the TTFT distribution
    (the bench/serve reporting surface for ``engine.stats``)."""
    stats = dict(eng.stats)
    ttft = sorted(stats.pop("ttft_s", {}).values())
    print("engine:", stats)
    slots = eng.scheduler.max_slots
    print(f"arena: {eng.arena_bytes / 1e6:.2f} MB resident "
          f"(kv_format={eng.kv_format}, "
          f"{eng.arena_bytes // max(slots, 1)} bytes/slot, "
          f"{eng.kv_row_bytes} bytes/row), "
          f"donation {'on' if eng.donate else 'off'} "
          f"(in-place slot writes are unconditional)")
    total = max(stats["requests"], 1)
    sampled = stats["sampled_requests"]
    # guard the per-sampled-request average: a greedy-only run
    # (--sampling-mix 0 / --temperature 0) has sampled == 0, and dividing
    # by it printed nan — report "n/a" instead
    per_req = (f"{stats['sampled_steps'] / sampled:.1f} sampling "
               f"steps/request" if sampled else "n/a (greedy-only run)")
    print(f"sampler: base_seed={eng.base_seed} "
          f"sampled={sampled}/{total} requests "
          f"(greedy={total - sampled}; {per_req}; keys fold "
          f"(seed, position) — batch/preemption/donation invariant)")
    print("scheduler:", eng.scheduler.stats)
    if getattr(eng, "prefix_sharing", False):
        ps = eng.cache_mgr.stats
        print(f"prefix cache: forks={stats['forks']} "
              f"shared_prompt_tokens={stats['shared_prompt_tokens']} "
              f"prefill_rows={stats['prefill_rows']} "
              f"(pages: registered={ps['registered_pages']} "
              f"shared={ps['shared_pages']} max_ref={ps['max_page_ref']})")
    if getattr(eng, "spec", None) is not None:
        sp = eng.spec.stats
        # acceptance-rate stats sit next to the sampler stats above: both
        # report the per-request determinism surface (keys fold (seed,
        # position); acceptance compares the target's own replayed draws)
        print(f"speculative: k={eng.spec.k} "
              f"accepted={sp['accepted']}/{sp['proposed']} proposals "
              f"(rate={eng.spec.acceptance_rate:.3f}) "
              f"rounds={sp['rounds']} resamples={sp['resamples']} "
              f"k_changes={sp['k_changes']} "
              f"verify_compiles={stats['spec_verify_compiles']} "
              f"draft_steps={stats['spec_draft_steps']}")
    if ttft:
        print(f"ttft_s: mean={np.mean(ttft):.4f} "
              f"p50={_percentile(ttft, 50):.4f} "
              f"p90={_percentile(ttft, 90):.4f} "
              f"max={max(ttft):.4f} (n={len(ttft)})")
    if eng._injector is not None or eng.health is not None:
        # robustness line: what the fault plan did and where the ladder
        # ended up — the serve-side view of the failure model
        fired = dict(stats.get("faults", {}))
        overruns = stats.get("deadline_overrun_s", {})
        print(f"robustness: health={stats.get('health', 'n/a')} "
              f"transitions={stats.get('health_transitions', 0)} "
              f"faults={fired} poisoned={stats['poisoned']} "
              f"quarantined={stats['quarantined']} "
              f"timed_out={stats['timed_out']} failed={stats['failed']} "
              f"deadline_overruns={len(overruns)}")
        if eng.health is not None and eng.health.transitions:
            for step, frm, to, why in eng.health.transitions:
                print(f"  health step {step}: {frm} -> {to} ({why})")


def generate(bundle, params, prompts: np.ndarray, *, gen_tokens: int,
             depth: int = 2, extras=None, max_slots=None,
             page_size: int = 16, num_pages=None) -> np.ndarray:
    """prompts: (B, S) int32.  Returns (B, gen_tokens) int32.

    Batch-of-equal-length convenience wrapper over the engine (the
    examples' surface).  ``extras`` are batched (B, ...) prefill side
    inputs, sliced per request.
    """
    b, s = prompts.shape
    prefix = (bundle.cfg.n_patch_tokens
              if bundle.cfg.family == "vlm" else 0)
    eng = make_engine(bundle, params, max_slots=max_slots or b,
                      max_seq=s + prefix + gen_tokens + 1, depth=depth,
                      page_size=page_size, num_pages=num_pages)
    for i in range(b):
        eng.submit(Request(
            uid=i, prompt=prompts[i], max_new_tokens=gen_tokens,
            extras={k: np.asarray(v)[i] for k, v in (extras or {}).items()}))
    out = eng.run()
    return np.stack([out[i] for i in range(b)], axis=0)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=list(registry.ARCH_NAMES))
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--slots", type=int, default=None,
                   help="decode slots (default: --requests)")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--pages", type=int, default=None,
                   help="cache pool pages (default: full arena)")
    p.add_argument("--prefill-mode", choices=["monolithic", "chunked"],
                   default="monolithic",
                   help="chunked = stripmined bucket-size prompt ingestion "
                        "interleaved with decode (every LM family)")
    p.add_argument("--chunk-buckets", default=None,
                   help="comma-separated chunk bucket sizes "
                        "(default 32,64,128,256,512)")
    p.add_argument("--prefill-budget", type=int, default=None,
                   help="max prompt tokens ingested per engine step "
                        "(default: largest bucket)")
    p.add_argument("--prompt-mix", default=None,
                   help="comma-separated prompt lengths cycled over the "
                        "requests (a mixed-length prefill-heavy workload), "
                        "or 'shared-prefix' for a common system prefix of "
                        "half --prompt-len plus distinct tails; overrides "
                        "--prompt-len")
    p.add_argument("--prefix-sharing", action="store_true",
                   help="copy-on-write prefix cache: fork repeated "
                        "page-aligned prompt prefixes onto shared pages "
                        "(requires --prefill-mode chunked)")
    p.add_argument("--kv-format", choices=["fp32", "bf16", "int8"],
                   default="fp32",
                   help="KV-arena storage format: fp32 = bit-exact "
                        "reference, bf16 = half the resident bytes, int8 = "
                        "quarter-width rows + per-row scale sidecar "
                        "(quantize-on-write; tolerance-measured vs fp32)")
    p.add_argument("--donate", choices=["auto", "on", "off"], default="auto",
                   help="KV-arena buffer donation: auto = on once the "
                        "arena crosses the in-place pay-off threshold "
                        "(serving.engine.DONATE_MIN_BYTES)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature for sampled requests "
                        "(0 = greedy argmax for every request)")
    p.add_argument("--top-k", type=int, default=0,
                   help="keep only the k highest-probability tokens "
                        "(0 = off)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass bound in (0, 1]")
    p.add_argument("--min-p", type=float, default=0.0,
                   help="drop tokens below min-p * max token probability")
    p.add_argument("--seed", type=int, default=0,
                   help="run-level base PRNG seed; request i samples with "
                        "seed+i, so a rerun replays identical streams")
    p.add_argument("--sampling-mix", type=float, default=1.0,
                   help="fraction of requests that sample (evenly spread); "
                        "the rest decode greedily")
    p.add_argument("--speculative", default=None, metavar="SPEC",
                   help="speculative decoding: draft=<arch>:k=<n>"
                        "[:k-max=<n>][:adaptive=0|1] — a reduced registry "
                        "arch proposes k tokens/round, the target verifies "
                        "them in one chunk-shaped step; output streams stay "
                        "bit-identical to plain decode")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request wall-clock deadline; a request still "
                        "in flight past it departs TIMED_OUT with its "
                        "partial output")
    p.add_argument("--fault-plan", default=None, metavar="PLAN",
                   help="deterministic fault injection: comma-separated "
                        "site:rate[:seed] entries over sites "
                        "alloc/chunk/decode/logits/draft, e.g. "
                        "'alloc:0.05,logits:0.01:7'; seeded by --seed "
                        "unless overridden per site — reruns replay the "
                        "identical failure interleaving")
    p.add_argument("--health", action="store_true",
                   help="enable the degradation ladder (HEALTHY -> "
                        "DEGRADED -> SHEDDING -> DRAINING) over default "
                        "HealthConfig thresholds; transitions are printed "
                        "with the stats")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the router (1 = a bare "
                        "engine, no router); replicas share the model "
                        "object, so the fleet compiles once")
    p.add_argument("--placement", choices=list(PLACEMENT_POLICIES),
                   default="least-pressure",
                   help="router placement policy (only with --replicas "
                        "> 1); token streams are bit-identical under "
                        "every choice")
    p.add_argument("--full", action="store_true",
                   help="build the arch at its published widths (default: "
                        "the reduced smoke-test config)")
    args = p.parse_args(argv)

    enable_compile_cache()
    bundle = registry.build(args.arch, reduced=not args.full)
    cfg = bundle.cfg
    params = jax.jit(bundle.model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = None
    if args.prompt_mix == "shared-prefix":
        # one common system prefix (half the prompt, page-aligned) plus
        # distinct per-request tails — the workload --prefix-sharing wins on
        shared = max(args.page_size,
                     args.prompt_len // 2 // args.page_size * args.page_size)
        head = rng.integers(0, cfg.vocab, shared)
        prompts = [np.concatenate(
            [head, rng.integers(0, cfg.vocab,
                                max(1, args.prompt_len - shared))])
            for _ in range(args.requests)]
        lens = [p.size for p in prompts]
    elif args.prompt_mix:
        mix = [int(x) for x in args.prompt_mix.split(",")]
        lens = [mix[i % len(mix)] for i in range(args.requests)]
    else:
        # mixed lengths: odd requests get a 25%-shorter prompt, so
        # admission / retirement actually interleave
        lens = [args.prompt_len if i % 2 == 0
                else max(1, args.prompt_len * 3 // 4)
                for i in range(args.requests)]
    if prompts is None:
        prompts = [rng.integers(0, cfg.vocab, lens[i])
                   for i in range(args.requests)]
    chunks = None
    if args.prefill_mode == "chunked":
        chunks = (tuple(int(x) for x in args.chunk_buckets.split(","))
                  if args.chunk_buckets else DEFAULT_BUCKETS)
    if args.prefix_sharing and chunks is None:
        p.error("--prefix-sharing requires --prefill-mode chunked")
    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = rng.standard_normal(
            (args.requests, cfg.enc_seq, cfg.d_model)).astype(np.float32)
    if cfg.family == "vlm":
        extras["patch_embeds"] = rng.standard_normal(
            (args.requests, cfg.n_patch_tokens, cfg.d_model)
        ).astype(np.float32)
    prefix = cfg.n_patch_tokens if cfg.family == "vlm" else 0

    donate = {"auto": "auto", "on": True, "off": False}[args.donate]
    econfig = EngineConfig(
        max_slots=args.slots or args.requests,
        max_seq=arena_rows(max(lens), args.gen, chunks, prefix),
        depth=args.depth, page_size=args.page_size,
        num_pages=args.pages, prefill_chunks=chunks,
        prefill_budget=args.prefill_budget,
        prefix_sharing=args.prefix_sharing, donate=donate,
        base_seed=args.seed, kv_format=args.kv_format,
        speculative=(parse_speculative(args.speculative)
                     if args.speculative else None),
        faults=(parse_fault_plan(args.fault_plan, seed=args.seed)
                if args.fault_plan else None),
        health=HealthConfig() if args.health else None)
    plan = sampling_plan(args.requests, temperature=args.temperature,
                         top_k=args.top_k, top_p=args.top_p,
                         min_p=args.min_p, seed=args.seed,
                         mix=args.sampling_mix)

    if args.replicas > 1:
        # sessions cycle over 2x the fleet so the affinity policy has
        # pins to honor without starving any replica of first contact
        router = make_router(bundle, params, config=econfig,
                             replicas=args.replicas,
                             placement=args.placement)
        for i in range(args.requests):
            router.submit(Request(
                uid=i, prompt=prompts[i],
                max_new_tokens=args.gen, sampling=plan[i],
                deadline_ms=args.deadline_ms,
                session=f"s{i % (2 * args.replicas)}",
                extras={k: v[i] for k, v in extras.items()}))
        t0 = time.perf_counter()
        out = router.run()
        dt = time.perf_counter() - t0
        total = sum(o.size for o in out.values())
        print(f"{args.arch}: {args.requests} requests over "
              f"{args.replicas} replicas ({args.placement}), {total} "
              f"tokens in {dt:.2f}s = {total / dt:.1f} tok/s "
              f"(depth={args.depth}, slots={econfig.max_slots}/replica, "
              f"prefill={args.prefill_mode})")
        print("router:", router.stats)
        for row in router.replica_stats():
            print("  replica:", row)
        print("first request:", out[0][:16], "...")
        return 0

    eng = make_engine(bundle, params, config=econfig)
    for i in range(args.requests):
        eng.submit(Request(
            uid=i, prompt=prompts[i],
            max_new_tokens=args.gen, sampling=plan[i],
            deadline_ms=args.deadline_ms,
            extras={k: v[i] for k, v in extras.items()}))

    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    total = sum(o.size for o in out.values())
    print(f"{args.arch}: {args.requests} requests, {total} tokens in "
          f"{dt:.2f}s = {total / dt:.1f} tok/s "
          f"(depth={args.depth}, slots={args.slots or args.requests}, "
          f"prefill={args.prefill_mode})")
    report_stats(eng)
    print("first request:", out[0][:16], "...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
