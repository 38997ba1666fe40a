"""Chip smoke test: serve llama3.2-3b at its published widths on a TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chip   # four one-chip replicas vs one

One chip: builds llama3.2-3b through ``registry.build(..., reduced=False)``
(28 layers, d=3072, 24/8 heads, d_ff 8192, vocab 128256; bf16 params from a
seeded on-device init), serves 12 greedy requests (prompts of 128, 512 and
2048 tokens, 32 new tokens each) through the ``EngineConfig`` /
``ServingEngine`` path of ``launch/serve.py`` with 8 slots, a bf16 KV arena
and chunked prefill, and checks that

  * every request finished with exactly 32 tokens;
  * the compiled decode and chunk-prefill steps contain the Pallas kernels
    (``tpu_custom_call``);
  * the served path agrees with the ``ref`` (pure-jnp) path on the chip:
    one decode step's logits on the served arena, and the greedy tokens of
    three requests scored teacher-forced by the reference (see
    ``LOGIT_RTOL``).

``--four-chip`` runs only the multi-chip path and what it is compared with:
the same requests through four replicas behind the router (one per chip,
``launch.mesh.replica_mesh``) and through one engine.  The token streams
must be identical — the router's (seed, position) contract — and the four
arenas must live on four distinct devices.

Everything runs in this one process, which starts no other.  The lines
before the last are smoke output (timings, compile seconds, peak device
memory), not benchmark numbers.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-3b"
SEED = 0
SLOTS = 8
GEN = 32
PROMPT_LENS = (128, 512, 2048)
N_REQUESTS = 12
# requests scored against the reference: one per prompt length
REF_UIDS = (0, 1, 2)

# Agreement bound between the served (Pallas) path and the ``ref`` path,
# as a fraction of the reference logits' largest magnitude.  Both paths
# round every layer's activations to bf16 (unit roundoff 2**-8 = 3.9e-3)
# and differ only in the attention arithmetic (f32 accumulation order,
# matmul passes), so their logits drift apart by rounding flips that
# accumulate over 28 residual layers, like sqrt(28) * 3.9e-3 = 2.1e-2 of
# the logit scale.  0.05 leaves 2.4x headroom; a kernel fault (wrong mask,
# row or scale) moves the logits by O(1) of their scale.
#
# Greedy tokens follow ``serving/tolerance.py``: greedy decode turns that
# noise into token flips only where the top logits nearly tie, and after a
# flip the streams no longer share a context.  So each served token is
# scored teacher-forced by the reference (same context at every position)
# and must lie within ``LOGIT_RTOL`` of the reference's own top logit; the
# prefix match rate of ``compare_streams`` is reported beside it.
LOGIT_RTOL = 0.05


class SmokeFailure(RuntimeError):
    """A check of this smoke test failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


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

    def since(self, mark: tuple) -> str:
        return (f"{self.count - mark[0]} compiles, "
                f"{self.secs - mark[1]:.3f} s compiling")

    def mark(self) -> tuple:
        return self.count, self.secs


COMPILES = CompileClock()


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# shared set-up
# ---------------------------------------------------------------------------

def build():
    """The model at published widths and its seeded bf16 params."""
    import jax
    from repro.models import registry
    bundle = registry.build(ARCH, reduced=False)
    cfg = bundle.cfg
    log(f"{ARCH}: {cfg.n_layers} layers, d={cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff={cfg.d_ff}, "
        f"vocab={cfg.vocab}, params {cfg.param_dtype}")
    t0, mark = time.perf_counter(), COMPILES.mark()
    params = jax.jit(bundle.model.init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"init: {nbytes / 1e9:.3f} GB of params in "
        f"{time.perf_counter() - t0:.3f} s ({COMPILES.since(mark)})")
    dtypes = {str(x.dtype) for x in jax.tree.leaves(params)}
    check(dtypes == {cfg.param_dtype}, f"params are {cfg.param_dtype}")
    return bundle, params


def workload(cfg):
    """Seeded greedy requests, prompt lengths cycling over PROMPT_LENS."""
    from repro.runtime.serving import Request
    rng = np.random.default_rng(SEED)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        PROMPT_LENS[i % len(PROMPT_LENS)]
                                        ).astype(np.int32),
                    max_new_tokens=GEN)
            for i in range(N_REQUESTS)]


def engine_config():
    from repro.launch.serve import arena_rows
    from repro.runtime.serving import DEFAULT_BUCKETS, EngineConfig
    return EngineConfig(max_slots=SLOTS,
                        max_seq=arena_rows(max(PROMPT_LENS), GEN,
                                           DEFAULT_BUCKETS),
                        prefill_chunks=DEFAULT_BUCKETS, kv_format="bf16",
                        base_seed=SEED)


def serve(bundle, params, requests, econfig):
    """Run ``requests`` through one engine (serve.py's path)."""
    from repro.launch.serve import make_engine
    eng = make_engine(bundle, params, config=econfig)
    states = {r.uid: eng.submit(r) for r in requests}
    t0, mark = time.perf_counter(), COMPILES.mark()
    out = eng.run()
    dt = time.perf_counter() - t0
    total = sum(o.size for o in out.values())
    log(f"served {len(out)} requests, {total} tokens in {dt:.3f} s "
        f"({COMPILES.since(mark)} inside that window); "
        f"decode_steps={eng.stats['decode_steps']} "
        f"prefill_chunks={eng.stats['prefill_chunks']} "
        f"prefill_compiles={eng.stats['prefill_compiles']}")
    return eng, states, out


def check_finished(states, out) -> None:
    from repro.runtime.serving import Status
    bad = {uid: (st.status.name, out.get(uid, np.empty(0)).size)
           for uid, st in states.items()
           if st.status is not Status.FINISHED or out[uid].size != GEN}
    check(not bad, f"all {len(states)} requests FINISHED with {GEN} tokens "
                   f"(failures: {bad or 'none'})")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def _abstract(tree):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def check_kernels(eng) -> None:
    """The compiled decode and chunk steps the engine ran contain the
    Pallas kernels (no fallback to the jnp path)."""
    import jax.numpy as jnp
    from repro.runtime.serving import engine as engine_mod
    state = (eng._tokens, eng._cache, eng._pos, eng._active, eng._samp)
    steps = {
        "decode": (engine_mod._compiled_decode_greedy(eng.model, eng.donate),
                   (eng.params, *state)),
        "chunk": (engine_mod._compiled_prefill_chunk(eng.model, eng.donate),
                  (eng.params, eng._cache,
                   jnp.zeros((1, max(eng.prefill_chunks)), jnp.int32),
                   jnp.int32(0), jnp.int32(0), jnp.int32(0))),
    }
    for name, (fn, args) in steps.items():
        t0 = time.perf_counter()
        text = fn.lower(*_abstract(args)).compile().as_text()
        log(f"{name} step: compiled text in {time.perf_counter() - t0:.3f} s"
            f", {text.count('tpu_custom_call')} tpu_custom_call sites")
        check("tpu_custom_call" in text,
              f"{name} step contains the Pallas kernels")


@contextlib.contextmanager
def ref_mode():
    """Trace the kernels' pure-jnp ``ref`` path inside this block (the
    mode is read when a step is traced, not when it runs)."""
    from repro.kernels import ops
    prev = ops.get_mode()
    ops.set_mode("ref")
    try:
        yield
    finally:
        ops.set_mode(prev)


def compare_logits(bundle, ref_model, params, eng) -> None:
    """One decode step on the served arena, kernel path vs ``ref`` path.

    Every slot decodes at the end of a served request's length (prompt +
    generated), so it attends that many arena rows over several strips:
    the engine's own positions are parked once its requests finish, and a
    parked slot attends no arena rows."""
    import jax
    import jax.numpy as jnp
    pos = jnp.asarray([PROMPT_LENS[i % len(PROMPT_LENS)] + GEN - 1
                       for i in range(eng.max_slots)], jnp.int32)
    args = (params, eng._tokens, eng._cache, pos)
    got = jax.jit(lambda *a: bundle.model.decode_step(*a)[0])(*args)
    with ref_mode():
        want = jax.jit(lambda *a: ref_model.decode_step(*a)[0])(*args)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    same = int(jnp.sum(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    log(f"decode logits vs ref ({got.shape[0]} slots): max abs err "
        f"{err:.6g}, max |ref| {scale:.6g}, rel {err / scale:.6g}, "
        f"argmax agrees on {same}/{got.shape[0]} slots")
    check(bool(np.isfinite(np.asarray(got)).all()), "decode logits finite")
    check(err <= LOGIT_RTOL * scale,
          f"decode logits within {LOGIT_RTOL} of the ref scale")


def ref_scores(ref_model, params, prompt, tokens, econfig):
    """Teacher-forced reference logits (GEN, V) for a served stream: the
    prompt is chunk-ingested as the engine does it, then the served tokens
    are replayed in one verify chunk."""
    import jax
    import jax.numpy as jnp
    from repro.runtime.serving.chunking import chunk_plan
    cache = ref_model.init_cache(1, econfig.max_seq,
                                 kv_format=econfig.kv_format)
    chunk = jax.jit(ref_model.prefill_chunk, donate_argnums=(2,))
    verify = jax.jit(ref_model.verify_chunk, donate_argnums=(2,))
    start = 0
    for size in chunk_plan(prompt.size, econfig.prefill_chunks):
        piece = np.zeros((1, size), np.int32)
        n = min(size, prompt.size - start)
        piece[0, :n] = prompt[start:start + n]
        logits, cache = chunk(params, jnp.asarray(piece), cache,
                              jnp.int32(0), jnp.int32(start),
                              jnp.int32(n - 1))
        start += n
    rest, _ = verify(params, jnp.asarray(tokens[None, :-1]), cache,
                     jnp.int32(0), jnp.int32(start))
    return np.concatenate([np.asarray(logits), np.asarray(rest[0])], 0)


def compare_tokens(ref_model, params, requests, out, econfig) -> None:
    from repro.runtime.serving import compare_streams
    served, argmax = {}, {}
    worst = 0.0
    for r in requests:
        if r.uid not in REF_UIDS:
            continue
        toks = out[r.uid]
        with ref_mode():
            scores = ref_scores(ref_model, params, r.prompt, toks, econfig)
        top = scores.max(-1)
        gap = top - scores[np.arange(toks.size), toks]
        bound = LOGIT_RTOL * np.abs(scores).max(-1)
        worst = max(worst, float((gap / bound).max()))
        served[r.uid] = toks
        argmax[r.uid] = scores.argmax(-1)
    report = compare_streams(argmax, served)
    log(f"greedy tokens vs ref (teacher-forced): {report.describe()}; "
        f"largest served-token gap = {worst:.4f} of the bound")
    check(worst <= 1.0, "every served token is within the logit tolerance "
                        "of the reference's top choice")


def one_chip() -> None:
    import jax
    from repro.models import registry
    bundle, params = build()
    econfig = engine_config()
    requests = workload(bundle.cfg)
    eng, states, out = serve(bundle, params, requests, econfig)
    check_finished(states, out)
    check_kernels(eng)
    log(f"peak device memory after serving: "
        f"{peak_bytes(jax.devices()[0])}")
    # a separately built model object: compiled steps are memoised per
    # model, and the kernel mode is read when a step is traced
    ref_model = registry.build(ARCH, reduced=False).model
    t0, mark = time.perf_counter(), COMPILES.mark()
    compare_logits(bundle, ref_model, params, eng)
    compare_tokens(ref_model, params, requests, out, econfig)
    log(f"reference comparison in {time.perf_counter() - t0:.3f} s "
        f"({COMPILES.since(mark)})")
    log(f"peak device memory: {peak_bytes(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chip() -> None:
    import gc

    import jax
    from repro.launch.serve import make_router
    from repro.runtime.serving import compare_streams
    devs = jax.devices()
    check(len(devs) >= 4, f"four devices present (found {len(devs)})")
    bundle, params = build()
    params = jax.device_put(params, devs[0])
    econfig = engine_config()
    requests = workload(bundle.cfg)
    eng, states, single = serve(bundle, params, requests, econfig)
    check_finished(states, single)
    del eng, states
    gc.collect()

    router = make_router(bundle, params, config=econfig, replicas=4)
    for r in requests:
        router.submit(r)
    t0 = time.perf_counter()
    fleet = router.run()
    log(f"4 replicas served {len(fleet)} requests in "
        f"{time.perf_counter() - t0:.3f} s (compiles included); "
        f"placed {router.stats['placed']}")
    check_finished(router.result_states(), fleet)
    report = compare_streams(single, fleet)
    log(f"4 replicas vs 1: {report.describe()}")
    check(report.identical, "token streams identical to one replica")
    homes = {}
    for rid, rep in router.replicas.items():
        held = {d for leaf in jax.tree.leaves(rep.engine._cache)
                for d in leaf.devices()}
        homes[rid] = held
        log(f"replica {rid}: arena on {sorted(str(d) for d in held)}, "
            f"served {router.stats['placed'][rid]} requests")
    check(all(len(h) == 1 for h in homes.values()),
          "each arena lives on one device")
    distinct = set().union(*homes.values())
    check(len(distinct) == 4, "the four arenas live on four distinct devices")
    check(all(n > 0 for n in router.stats["placed"].values()),
          "every replica served requests")
    for d in devs[:4]:
        log(f"peak device memory {d}: {peak_bytes(d)}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chip", action="store_true",
                   help="run only the four-replica router path and its "
                        "one-replica comparison")
    args = p.parse_args(argv)

    info = device_info()
    log(f"devices: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        print(f"[smoke] no TPU: JAX found {info['platform']!r} devices",
              file=sys.stderr)
        return 1
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(COMPILES)
    t0 = time.perf_counter()
    (four_chip if args.four_chip else one_chip)()
    log(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
