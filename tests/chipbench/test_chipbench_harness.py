"""The harness end to end on the CPU at a tiny size: a sound run is
correct, and a run whose timed path is broken underneath is not.  The
look for a chip is skipped (``require_tpu=False``)."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import run, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DOC = {
    "configs": [{"name": "tiny", "source": "tests",
                 "file": "tests/chipbench/data/tiny-dense.json",
                 "reduced": [], "why": "CPU test size"}],
    "workloads": [{"name": "tiny.chat", "config": "tiny",
                   "traffic": "tiny-chat", "chips": 1, "why": "CPU test"},
                  {"name": "tiny.doc", "config": "tiny",
                   "traffic": "tiny-doc", "chips": 1, "why": "CPU test"}],
    "end_to_end": [
        {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.chat"]},
        {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.chat"]},
        {"name": "tok_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.doc"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": []}


def _run(seed=11, seconds=3.0, cell="tiny.chat"):
    bench = spec.Benchmark(doc=DOC, data_dir=DATA)
    return run.run_cell(bench, cell, seed, seconds, False,
                        t_start=time.perf_counter(), require_tpu=False,
                        log=lambda msg: None)


def _tamper(monkeypatch, wrap):
    """Break the engine's decode step underneath the harness."""
    real = run.build

    def build(*args, **kwargs):
        out = real(*args, **kwargs)
        eng = out[-1].eng
        assert not eng.donate        # tiny arena: the cache is not donated
        eng._decode = wrap(eng._decode, eng)
        eng._decode_greedy = wrap(eng._decode_greedy, eng)
        return out

    monkeypatch.setattr(run, "build", build)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["check"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "check"


def test_closed_loop_run_is_correct():
    res = _run(seed=12, cell="tiny.doc")
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"tok_per_s", "setup_s"}
    # three clients keep the engine fed: more requests than clients ran
    assert res["attempted"] > 3 and res["metrics"]["tok_per_s"]["value"] > 0


def test_token_altered_where_produced_is_caught(monkeypatch):
    def wrap(fn, eng):
        def step(params, *state):
            out = fn(params, *state)
            bad = (out[-2] + 1) % eng.cfg.vocab     # the sampled tokens
            return (*out[:-2], bad, out[-1])
        return step
    _tamper(monkeypatch, wrap)
    res = _run()
    assert not res["correct"], res["check"]


def test_step_returning_its_state_unchanged_is_caught(monkeypatch):
    def wrap(fn, eng):
        def step(params, tokens, cache, *rest):
            out = fn(params, tokens, cache, *rest)
            return (out[0], cache, *out[2:])     # K/V rows never written
        return step
    _tamper(monkeypatch, wrap)
    res = _run()
    assert not res["correct"], res["check"]


def test_no_chip_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "qwen3-14b-10l.chat", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.Benchmark(doc=DOC, data_dir=DATA).cell("tiny.nothing")


def test_four_replica_cell_runs_behind_the_router(run8):
    """A ``chips: 4`` cell drives four engine replicas behind the router,
    one per device (four CPU devices here), and is judged the same way.
    Four engines stepped in turn on a loaded host finish fewer requests
    in the window, so this cell checks fewer tokens (its own limits)."""
    script = f"""
import json, sys, time
sys.path.insert(0, {spec.ROOT!r})
from chipbench import run, spec
doc = json.loads({json.dumps(json.dumps(DOC))})
doc["workloads"].append(dict(doc["workloads"][0], name="tiny.router",
                             chips=4))
bench = spec.Benchmark(doc=doc, data_dir={DATA!r})
res = run.run_cell(bench, "tiny.router", 13, 5.0, False,
                   t_start=time.perf_counter(), require_tpu=False,
                   log=lambda msg: None)
print("RESULT", json.dumps(res))
"""
    out = run8(script, n_devices=4, timeout=600)
    res = json.loads(out.split("RESULT ", 1)[1])
    assert res["correct"], res["check"]
    assert res["device"]["count"] == 4 and res["attempted"] == 20
