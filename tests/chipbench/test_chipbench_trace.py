"""The trace reduction on a trace recorded on the chip: one TPU v5e
("TPU v5 lite") serving ``qwen3-14b-10l.chat``, 0.7 s of its window
(``data/trace/chat.xplane.pb.gz``), with the host's step records of that
span (``data/trace/chat.steps.json``: time, live lengths of the decoding
slots, ``(start, size, valid)`` of each chunk)."""
import json
import os
import types

import pytest

from chipbench import readings, run, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace")
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(os.path.join(DATA, "chat.xplane.pb.gz"))


def test_device_plane_programs_and_window(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(0.702268367)
    assert 0 < summary.busy_s < summary.window_s
    assert len(summary.program_times(readings.DECODE_STEP)) == 7
    assert len(summary.program_times(readings.CHUNK_STEP)) == 6


def test_kernels_are_found_inside_their_programs(summary):
    """Each step program holds one Pallas kernel: ``flash_decode`` in the
    decode step, ``flash_prefill_chunk`` in the chunk step."""
    for program in (readings.DECODE_STEP, readings.CHUNK_STEP):
        kernel = summary.kernel_seconds(program, KERNEL)
        assert 0 < kernel < sum(summary.program_times(program))
    assert summary.kernel_seconds(readings.DECODE_STEP, "no_such") == 0


def test_breakdown_lists_operations_not_their_loops(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) == 10
    assert not any(name.startswith(("%while", "%conditional"))
                   for name, _ in b["device_ops"])
    assert b["idle_gaps"] and all(s > 0 for _, s in b["idle_gaps"])


def test_per_layer_metrics_of_the_recorded_span(summary):
    bench = spec.Benchmark()
    cell = bench.cell("qwen3-14b-10l.chat")
    cfg = bench.config(cell)
    arch = spec.model_module(cfg["model"]).program_config(cfg)
    with open(os.path.join(DATA, "chat.steps.json")) as f:
        rec = json.load(f)
    window = types.SimpleNamespace(
        steps=[tuple(s) for s in rec["steps"]],
        trace_span=(0.0, rec["span_s"]),
        clients=[types.SimpleNamespace(due=0.0, sent=0.001, admitted=0.2)],
        counters0={"decode_steps": 0, "tokens_out": 0},
        counters1={"decode_steps": 5, "tokens_out": 30})
    got = run.per_layer(bench, cell, run.Run(
        arch, cfg, {"kind": "TPU v5 lite"}, window, summary, 0.7))
    values = {k: v["value"] for k, v in got.items()}
    want = {"decode_step_ms": 38.61604542857143,
            "chunk_step_ms": 22.594931666666664,
            "mfu.decode_step": 0.6572020885081763,
            "mfu.chunk_step": 50.52761721775751,
            "flash_decode_roofline": 4.944505636730186,
            "flash_prefill_chunk_roofline": 21.88333744022865,
            "device_idle_share": 42.205442097038095}
    for name, value in want.items():
        assert values[name] == pytest.approx(value, rel=1e-9), name
    for name in ("mfu.decode_step", "mfu.chunk_step",
                 "flash_decode_roofline", "flash_prefill_chunk_roofline"):
        assert 0 < values[name] <= 100


def test_a_metric_that_finds_nothing_fails_the_run(summary):
    bench = spec.Benchmark()
    cell = bench.cell("qwen3-14b-10l.chat")
    cfg = bench.config(cell)
    arch = spec.model_module(cfg["model"]).program_config(cfg)
    empty = trace.TraceSummary(window_s=1.0, busy_s=0.5, programs={},
                               ops={}, kernels={}, idle={}, devices=1)
    window = types.SimpleNamespace(
        steps=[], trace_span=(0.0, 1.0),
        clients=[types.SimpleNamespace(due=0.0, sent=0.001, admitted=0.2)],
        counters0={"decode_steps": 0, "tokens_out": 0},
        counters1={"decode_steps": 5, "tokens_out": 30})
    with pytest.raises(RuntimeError, match="found nothing to read"):
        run.per_layer(bench, cell, run.Run(
            arch, cfg, {"kind": "TPU v5 lite"}, window, empty, 0.7))
