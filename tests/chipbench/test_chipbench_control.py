"""The control at a size a test run holds: the reference computed in
float8 (one precision step below the configuration's bfloat16), put in the
program's place, reads far above the program and fails the cell's limit;
the judge scores a greedy token by its gap below the reference's best."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import calibrate, judge, run, spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DOC = {
    "configs": [{"name": "tiny-bf16", "source": "tests",
                 "file": "tests/chipbench/data/tiny-dense-bf16.json",
                 "reduced": [], "why": "CPU test size"}],
    "workloads": [{"name": "tiny.chat", "config": "tiny-bf16",
                   "traffic": "tiny-chat", "chips": 1, "why": "CPU test"}],
    "end_to_end": [], "per_layer": []}


def test_control_fails_where_the_program_passes():
    """On four seeds the program passes every time, and the control fails
    the limit on at least one: at this size near-ties are few, and on some
    seeds it picks what the reference picks."""
    bench = spec.Benchmark(doc=DOC, data_dir=DATA)
    cell = bench.cell("tiny.chat")
    limits = bench.limits(cell)
    program, control = [], []
    for seed in (21, 22, 23, 24):
        s = run.serve(bench, cell, seed, 3.0, t_start=time.perf_counter(),
                      require_tpu=False, log=lambda msg: None)
        numbers = run.check(s.family, s.cfg, s.params, s.picked, limits)
        assert run.passes(numbers), numbers
        program.append(numbers["max_logit_gap"]["value"])
        control.append(calibrate.control_gap(s))
    assert max(control) > limits["max_logit_gap"] > max(program)
    assert max(control) > 3 * max(program)


def test_judge_scores_greedy_tokens_by_their_gap():
    v, n = 300, 5
    z = jax.random.normal(jax.random.PRNGKey(1), (n, v)) * 3
    zp = jnp.zeros((64, v)).at[:n].set(z)
    best = np.asarray(jnp.argmax(z, -1))
    np.testing.assert_allclose(judge.served_gaps(zp, best), 0.0)
    other = (best + 1) % v
    want = np.asarray(z.max(-1) - z[jnp.arange(n), other])
    np.testing.assert_allclose(judge.served_gaps(zp, other), want,
                               rtol=1e-6)
    assert (want > 0).all()
    np.testing.assert_allclose(judge.control_gaps(zp, zp, n), 0.0)
    np.testing.assert_array_equal(judge.rows(10, 3)[:4], [9, 10, 11, 11])
