"""The MoE cell's pieces on the CPU at a tiny size: the harness serves a
held share of the experts through the engine and judges it against the
plain reference (``chipbench/models/moe.py``), a broken step is caught,
and the MoE readers turn the engine's routing counters and a trace into
the cell's per-layer metrics."""
import os
import time
import types

import pytest

from chipbench import run, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DOC = {
    "configs": [{"name": "tiny-moe", "source": "tests",
                 "file": "tests/chipbench/data/tiny-moe.json",
                 "reduced": [], "why": "CPU test size"}],
    "workloads": [{"name": "tiny-moe.chat", "config": "tiny-moe",
                   "traffic": "tiny-chat", "chips": 1, "why": "CPU test"}],
    "end_to_end": [
        {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": []}


def _run(seed=11, seconds=3.0):
    bench = spec.Benchmark(doc=DOC, data_dir=DATA)
    return run.run_cell(bench, "tiny-moe.chat", seed, seconds, False,
                        t_start=time.perf_counter(), require_tpu=False,
                        log=lambda msg: None)


def test_moe_share_run_is_correct():
    res = _run()
    assert res["correct"], res["check"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert res["compiles_in_window"] == 0


def test_moe_token_altered_where_produced_is_caught(monkeypatch):
    """The MoE decode step returns its routing counters last; the sampled
    tokens sit before the ok flags."""
    real = run.build

    def build(*args, **kwargs):
        out = real(*args, **kwargs)
        eng = out[-1].eng
        assert eng.stats["moe_rows_here"] == eng.stats[
            "decode_moe_rows_here"] == 0

        def wrap(fn):
            def step(params, *state):
                out = fn(params, *state)
                bad = (out[-3] + 1) % eng.cfg.vocab
                return (*out[:-3], bad, *out[-2:])
            return step
        eng._decode = wrap(eng._decode)
        eng._decode_greedy = wrap(eng._decode_greedy)
        return out

    monkeypatch.setattr(run, "build", build)
    res = _run()
    assert not res["correct"], res["check"]


def _fake_run(counters, steps_s, gmm_s, chunks_s=()):
    """A traced run of a reduced Qwen3-MoE share as the readers see it:
    ``counters`` the engine's window deltas, ``steps_s`` and ``chunks_s``
    the traced decode and chunk programs' seconds, ``gmm_s`` the seconds
    of the ops named ``expert_gmm`` (beside a ``flash_decode`` op the
    readers must leave out)."""
    from chipbench.peaks import peaks
    cfg = spec.read_json(os.path.join(spec.PKG, "configs",
                                      "qwen3-30b-a3b-24l.json"))
    cfg["name"] = "qwen3-30b-a3b-24l"
    arch = spec.model_module("moe").program_config(cfg)
    zero = {k: 0 for k in counters}
    window = types.SimpleNamespace(counters0=zero, counters1=counters,
                                   trace_span=(0.0, 1.0),
                                   steps=[(0.5, [600, 1400], [])])
    target = 'custom_call_target="tpu_custom_call"'
    programs = {"jit_step": list(steps_s), "jit_chunk_step": list(chunks_s)}
    trace = types.SimpleNamespace(
        program_times=lambda name: programs.get(name, []),
        ops={f"%flash_decode.3 = bf16[24,32,128] custom-call(...), {target}":
             0.5,
             f"%expert_gmm.7 = bf16[192,768] custom-call(...), {target}":
             gmm_s / 2,
             f"%expert_gmm = bf16[192,2048] custom-call(...), {target}":
             gmm_s / 2,
             "%fusion.2 = bf16[192,768] fusion(...)": 0.25})
    r = run.Run(arch, cfg, {"kind": "TPU v5 lite"}, window, trace, 0.0)
    r.traced_steps = lambda: window.steps
    assert r.peaks == peaks("TPU v5 lite")
    return r


def test_moe_readers_use_the_routing_counters():
    counters = {"decode_steps": 10, "decode_moe_rows_here": 10 * 24 * 2,
                "decode_moe_experts_touched": 10 * 24 * 2,
                "moe_rows_here": 10 * 24 * 2,
                "moe_experts_touched": 10 * 24 * 2, "prefill_chunks": 0}
    r = _fake_run(counters, [0.01] * 4, 0.02)
    read = lambda name: spec.metric_reader(name).read(r)
    assert read("decode_step_ms") == pytest.approx(10.0)
    # two routed rows on two experts a layer: 48 expert weight sets, the
    # rows' bytes beside them, at 819 GB/s over the 20 ms of the ops
    # named expert_gmm (flash_decode's op is not counted)
    cost = spec.kernel_cost("expert_gmm").cost(r.arch, 48, 48)
    want = 100 * 4 * cost[1] / 819e9 / 0.02
    assert read("expert_gmm_roofline") == pytest.approx(want)
    assert 0 < read("mfu.moe_decode_step") < 100


def test_expert_gmm_roofline_counts_the_chunk_steps():
    """Chunk steps' routed rows are the counters less the decode steps',
    per chunk program; their least time joins the decode steps'."""
    counters = {"decode_steps": 10, "decode_moe_rows_here": 480,
                "decode_moe_experts_touched": 480,
                "moe_rows_here": 480 + 2 * 12288,
                "moe_experts_touched": 480 + 2 * 384, "prefill_chunks": 2}
    r = _fake_run(counters, [0.01] * 4, 0.05, chunks_s=[0.02])
    mod = spec.kernel_cost("expert_gmm")
    from chipbench import peaks
    least = (4 * peaks.least_time(*mod.cost(r.arch, 48, 48), "TPU v5 lite")
             + peaks.least_time(*mod.cost(r.arch, 12288, 384),
                                "TPU v5 lite"))
    got = spec.metric_reader("expert_gmm_roofline").read(r)
    assert got == pytest.approx(100 * least / 0.05)


def test_moe_readers_find_nothing_without_counters():
    """A program that serves no MoE layer has no routing counters (the
    parent of this cell): the readers read nothing and raise nothing."""
    r = _fake_run({"decode_steps": 10, "prefill_chunks": 2}, [0.01] * 4,
                  0.02, chunks_s=[0.02])
    for name in ("mfu.moe_decode_step", "expert_gmm_roofline"):
        assert spec.metric_reader(name).read(r) is None
