"""The per-layer readers' arithmetic on a synthetic run: host step records
matched to traced programs by count, shares of the published peaks."""
import dataclasses
import types

import pytest

from chipbench import flops, peaks, readings, spec, trace


@dataclasses.dataclass
class Arch:
    n_layers: int = 2
    d_model: int = 8
    n_heads: int = 4
    n_kv_heads: int = 2
    hd: int = 2
    d_ff: int = 16
    vocab: int = 10
    act: str = "silu_gated"


def _run(steps, programs, kernels, busy=0.5, window=1.0):
    summary = trace.TraceSummary(window_s=window, busy_s=busy,
                                 programs=programs, ops={},
                                 kernels=kernels, idle={}, devices=1)
    clients = [types.SimpleNamespace(sent=1.0 + i * 0.01, due=1.0,
                                     admitted=1.2 if i else None)
               for i in range(3)]
    w = types.SimpleNamespace(steps=steps, trace_span=(0.0, 10.0),
                              clients=clients,
                              counters0={"decode_steps": 0, "tokens_out": 0},
                              counters1={"decode_steps": 4,
                                         "tokens_out": 10})
    run = types.SimpleNamespace(
        arch=Arch(), cfg={"serve": {"kv_format": "bf16"}},
        device={"kind": "TPU v5 lite"}, peaks=peaks.peaks("TPU v5 lite"),
        window=w, trace=summary, compile_s=1.5)
    run.traced_steps = lambda: [s for s in steps if 0.0 <= s[0] <= 10.0]
    return run


STEPS = [(1.0, [3, 5], []), (2.0, [4, 6], [(0, 32, 20)]),
         (20.0, [9, 9], [])]                    # the last lies past the span


def test_step_mfu_and_ms():
    run = _run(STEPS, {readings.DECODE_STEP: [0.01, 0.01, 0.01],
                       readings.CHUNK_STEP: [0.02]}, {})
    assert readings.step_ms(run, readings.DECODE_STEP) == pytest.approx(10)
    per = (flops.decode_step(run.arch, [3, 5])
           + flops.decode_step(run.arch, [4, 6])) / 2
    want = 100 * per * 3 / (0.03 * 197e12)       # 3 traced, 2 recorded
    assert readings.step_mfu(run, readings.DECODE_STEP) == pytest.approx(want)
    chunk = 100 * flops.chunk_step(run.arch, 0, 20) / (0.02 * 197e12)
    assert readings.step_mfu(run, readings.CHUNK_STEP) == pytest.approx(chunk)


def test_kernel_roofline_counts_least_time_over_kernel_time():
    run = _run(STEPS, {readings.DECODE_STEP: [0.01, 0.01]},
               {(readings.DECODE_STEP, "tpu_custom_call"): 0.004,
                (readings.CHUNK_STEP, "tpu_custom_call"): 0.01})
    cost = spec.kernel_cost("flash_decode").cost
    least = [peaks.least_time(*cost(run.arch, d, 2), "TPU v5 lite")
             for d in ([3, 5], [4, 6])]
    want = 100 * sum(least) / 2 * 2 / 0.004
    got = readings.kernel_roofline(run, "flash_decode", readings.DECODE_STEP)
    assert got == pytest.approx(want)


def test_nothing_to_read_gives_none():
    run = _run([], {}, {})
    assert readings.step_mfu(run, readings.DECODE_STEP) is None
    assert readings.kernel_roofline(run, "flash_decode",
                                    readings.DECODE_STEP) is None
    run.trace = None
    assert readings.idle_share(run) is None


def test_host_side_readers():
    run = _run(STEPS, {}, {}, busy=0.25, window=1.0)
    assert readings.idle_share(run) == pytest.approx(75.0)
    assert spec.metric_reader("decode_batch_mean").read(run) == 2.5
    assert spec.metric_reader("gen_lag_p99_ms").read(run) \
        == pytest.approx(19.8)
    assert spec.metric_reader("queue_wait_p90_ms").read(run) == float("inf")
    assert spec.metric_reader("compile_s").read(run) == 1.5


def test_trace_busy_union_and_idle_labels():
    merged = trace._union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert merged == [[0, 3], [5, 9]]
    spans = [(0, 10, "chipbench.step"), (2, 4, "chipbench.submit")]
    starts = [0, 2]
    assert trace._label(spans, starts, 3) == "chipbench.submit"
    assert trace._label(spans, starts, 6) == "chipbench.step"
    assert trace._label(spans, starts, 11).startswith("host:")
