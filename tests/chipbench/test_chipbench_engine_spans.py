"""The engine-layer readers (``chipbench.engine_spans``): their arithmetic
on a synthetic run, the idle labeller with nested ``serving.*`` spans, and
the reduction of a chip trace recorded before the engine had spans, where
every reading of the plain reduction stays and every new reader finds
nothing, and of one recorded with them: one TPU v5e ("TPU v5 lite")
serving ``qwen3-14b-10l.chat``, the last 2.0 s of its window
(``data/trace/chat-spans.xplane.pb.gz``).  ``engine_view`` serves the
tiny cell on the CPU and holds the engine's clock to the client's."""
import collections
import os
import types

import pytest

from chipbench import engine_spans, engine_view, readings, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("admit_wait_p90_ms", "first_token_wait_p90_ms", "prefill_slots_mean",
       "step_host_self_ms", "idle_host_work_share")
MS = 1_000_000          # ns


def _state(sub, adm, first):
    return types.SimpleNamespace(submitted_at=sub, admitted_at=adm,
                                 first_token_at=first)


def _summary(spans=(), gaps=(), window=1.0):
    return engine_spans.EngineTrace(
        window_s=window, busy_s=0.5, programs={}, ops={}, kernels={},
        idle={}, devices=1, spans=list(spans), gaps=[list(gaps)])


def _run(states=(), summary=None, c0=None, c1=None):
    clients = [types.SimpleNamespace(state=s) for s in states]
    window = types.SimpleNamespace(
        clients=clients, counters0=c0 or {}, counters1=c1 or {})
    return types.SimpleNamespace(window=window, trace=summary)


def _read(name, run):
    return spec.metric_reader(name).read(run)


def test_request_waits_from_engine_timestamps():
    states = [_state(0.0, 0.1 * i, 0.1 * i + 0.05) for i in range(10)]
    run = _run(states)
    want = readings.percentile([100.0 * i for i in range(10)], 90)
    assert _read("admit_wait_p90_ms", run) == pytest.approx(want)
    assert _read("first_token_wait_p90_ms", run) == pytest.approx(50.0)
    # a request never admitted, or with no first token, is missing
    states[3] = _state(0.0, None, None)
    states[4] = _state(0.0, 0.2, None)
    run = _run(states + [None])                  # and one refused
    assert _read("admit_wait_p90_ms", run) == float("inf")
    assert _read("first_token_wait_p90_ms", run) == float("inf")


def test_prefill_slots_from_counters():
    run = _run(c0={"steps": 10, "slot_steps_prefilling": 4},
               c1={"steps": 30, "slot_steps_prefilling": 54})
    assert _read("prefill_slots_mean", run) == pytest.approx(2.5)


def test_step_host_self_time_less_its_waits():
    spans = [(0, 10 * MS, "serving.step", {"tick": 1}),
             (1 * MS, 3 * MS, "serving.retire", {}),
             (1 * MS, 3 * MS, "serving.wait", {"what": "readback"}),
             (6 * MS, 9 * MS, "serving.wait", {"what": "backpressure"}),
             (20 * MS, 24 * MS, "serving.step", {"tick": 2}),
             (23 * MS, 30 * MS, "serving.wait", {"what": "first_token"})]
    run = _run(summary=_summary(spans))
    # step 1: 10 - 2 - 3 = 5 ms; step 2: 4 - 1 (the wait inside it) = 3 ms
    assert _read("step_host_self_ms", run) == pytest.approx(4.0)


def test_idle_while_the_host_works_in_the_engine():
    spans = [(0, 100 * MS, "serving.step", {"tick": 1}),
             (10 * MS, 30 * MS, "serving.admit", {}),
             (20 * MS, 25 * MS, "serving.wait", {"what": "first_token"}),
             (40 * MS, 50 * MS, "serving.gc", {"generation": 0}),
             (120 * MS, 130 * MS, "serving.step", {"tick": 2})]
    gaps = [(15 * MS, 45 * MS),      # 15-20 and 25-40 work, 20-25 waits
            (60 * MS, 61 * MS),      # work
            (105 * MS, 118 * MS)]    # outside every serving span
    run = _run(summary=_summary(spans, gaps, window=1.0))
    want = (5 + 15 + 1) / 1000 * 100
    assert _read("idle_host_work_share", run) == pytest.approx(want)


def test_idle_labels_name_the_innermost_span():
    host = [(0, 100, "chipbench.step"), (2, 98, "serving.step"),
            (10, 40, "serving.admit"), (20, 30, "serving.wait"),
            (60, 70, "serving.decode"), (120, 130, "chipbench.submit")]
    gaps = [[(21, 23), (12, 14), (50, 56), (99, 99.5), (61, 63),
             (110, 112), (124, 126)]]
    idle = engine_spans.label_gaps(host, gaps)
    assert idle == pytest.approx({
        "serving.wait": 2e-9, "serving.admit": 2e-9, "serving.step": 6e-9,
        "chipbench.step": 0.5e-9, "serving.decode": 2e-9,
        "host:outside any span": 2e-9, "chipbench.submit": 2e-9})


def test_largest_gaps_name_the_wait():
    spans = [(0, 100 * MS, "serving.step", {"tick": 1}),
             (10 * MS, 60 * MS, "serving.wait", {"what": "readback"}),
             (70 * MS, 90 * MS, "serving.chunk", {"uid": 3})]
    summary = _summary(spans, [(20 * MS, 50 * MS), (75 * MS, 76 * MS),
                               (95 * MS, 97 * MS)])
    assert engine_view.largest_gaps(summary, 2) == [
        [30.0, "serving.wait:readback"], [2.0, "serving.step"]]


def test_interval_arithmetic():
    assert engine_spans._subtract([(0, 10), (20, 30)],
                                  [(2, 3), (5, 22), (29, 40)]) \
        == [(0, 2), (3, 5), (22, 29)]
    assert engine_spans._intersect([(0, 10), (20, 30)],
                                   [(5, 25), (28, 29)]) \
        == [(5, 10), (20, 25), (28, 29)]


def test_a_program_without_engine_spans_reads_nothing():
    """The readers on what the engine recorded before it had spans,
    admission timestamps and step counters: each returns None."""
    old_state = types.SimpleNamespace(submitted_at=0.0, ttft_s=0.1)
    plain = trace.TraceSummary(window_s=1.0, busy_s=0.5, programs={},
                               ops={}, kernels={}, idle={}, devices=1)
    run = _run([old_state], plain, c0={"decode_steps": 0},
               c1={"decode_steps": 5})
    for name in NEW:
        assert _read(name, run) is None, name
    run.trace = None
    assert _read("step_host_self_ms", run) is None


def test_reduction_of_a_trace_without_engine_spans():
    path = os.path.join(DATA, "trace", "chat.xplane.pb.gz")
    plain = trace.reduce(path)
    got = engine_spans.reduce(path)
    assert got.spans == []
    # a kernel without a name takes its caller's: both are "closed_call"
    assert got.kernel_names == pytest.approx(
        {"closed_call": sum(plain.kernels.values())}, rel=1e-4)
    for field in ("window_s", "busy_s", "programs", "ops", "kernels",
                  "devices"):
        assert getattr(got, field) == getattr(plain, field), field
    assert got.idle == pytest.approx(plain.idle)
    assert sum(b - a for dev in got.gaps for a, b in dev) / 1e9 \
        == pytest.approx(sum(plain.idle.values()))
    run = _run(summary=got)
    assert _read("step_host_self_ms", run) is None
    assert _read("idle_host_work_share", run) is None


@pytest.fixture(scope="module")
def spans_trace():
    return os.path.join(DATA, "trace", "chat-spans.xplane.pb.gz")


def test_chip_trace_with_engine_spans(spans_trace):
    got = engine_spans.reduce(spans_trace)
    assert got.window_s == pytest.approx(2.022253719)
    assert collections.Counter(n for _, _, n, _ in got.spans) == {
        "serving.step": 35, "serving.retire": 35, "serving.admit": 35,
        "serving.prefill": 35, "serving.decode": 35, "serving.chunk": 7,
        "serving.wait": 74}
    ticks = [st["tick"] for _, _, n, st in got.spans if n == "serving.step"]
    assert ticks == list(range(803, 838))
    assert [(st["uid"], st["size"], st["valid"]) for _, _, n, st in got.spans
            if n == "serving.chunk"] == [
        (53, 256, 256), (53, 128, 128), (53, 32, 32), (53, 32, 9),
        (54, 512, 512), (54, 512, 512), (54, 32, 29)]
    run = _run(summary=got)
    assert _read("step_host_self_ms", run) == pytest.approx(3.98815,
                                                            rel=1e-5)
    assert _read("idle_host_work_share", run) == pytest.approx(0.509483,
                                                               rel=1e-5)


def test_chip_trace_idle_lies_in_engine_phases(spans_trace):
    """The plain reduction puts every gap between device operations under
    the harness's step span; the engine's spans put it down to a phase:
    the host preparing and finishing chunks while the device waits."""
    plain = trace.reduce(spans_trace)
    got = engine_spans.reduce(spans_trace)
    assert set(plain.idle) == {"chipbench.step", "host:outside any span"}
    assert got.idle == pytest.approx({
        "serving.chunk": 0.013412178, "serving.wait": 0.000175682,
        "serving.decode": 4.9e-08, "host:outside any span": 3.4e-08},
        abs=1e-9)
    assert sum(got.idle.values()) == pytest.approx(sum(plain.idle.values()))
    assert engine_view.largest_gaps(got, 2) == [
        [4.211046, "serving.chunk"], [3.799026, "serving.chunk"]]


def test_chip_trace_names_each_kernel(spans_trace):
    """``pallas_call(name=...)`` reaches the ``XLA Ops`` event: the kernel
    is ``%flash_decode.N = ... custom-call`` in the decode step and
    ``%flash_prefill_chunk.N`` in the chunk step."""
    plain = trace.reduce(spans_trace)
    got = engine_spans.reduce(spans_trace)
    assert got.kernel_names == pytest.approx({
        "flash_decode": plain.kernel_seconds(readings.DECODE_STEP,
                                             "tpu_custom_call"),
        "flash_prefill_chunk": plain.kernel_seconds(readings.CHUNK_STEP,
                                                    "tpu_custom_call")})
    assert got.kernel_names["flash_decode"] == pytest.approx(0.22382455)


def test_engine_view_on_the_cpu(monkeypatch):
    """The tiny chat cell served on the CPU: the engine's admission wait
    is inside the client's queue wait, and its time to first token inside
    the client's."""
    import chipbench.peaks
    from test_chipbench_harness import DOC
    monkeypatch.setattr(chipbench.peaks, "peaks",
                        lambda kind: {"flops_bf16": 1e12,
                                      "hbm_bytes_per_s": 1e11})
    doc = dict(DOC, per_layer=[
        {"name": n, "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "engine / scheduler",
         "moves": "ttft_p90_ms", "workloads": ["tiny.chat"]}
        for n in ("queue_wait_p90_ms", "decode_batch_mean")])
    bench = spec.Benchmark(doc=doc, data_dir=DATA)
    out = engine_view.view(bench, "tiny.chat", 2**31 + 17, 3.0, False,
                           require_tpu=False, log=lambda msg: None)
    layer = out["per_layer"]
    for name in ("admit_wait_p90_ms", "first_token_wait_p90_ms",
                 "prefill_slots_mean"):
        assert layer[name] >= 0, name
    assert "step_host_self_ms" not in layer         # untraced
    assert layer["admit_wait_p90_ms"] <= layer["queue_wait_p90_ms"]
    c = out["consistency"]
    assert c["requests"] == out["attempted"] == 12
    assert c["engine_ttft_over_client"] == 0
    assert c["slots_busy"] == pytest.approx(
        layer["prefill_slots_mean"] + layer["decode_batch_mean"])
    occ = out["occupancy"]
    assert 0 < occ["running_mean"] <= occ["running_max"] <= 4
    assert len(occ["long_waits_least_running"]) == occ["long_waits"]
    assert out["span_cost_us"]["off"] > 0
    assert out["end_to_end"]["ttft_p90_ms"] > 0
