"""DispatchQueue semantics (C6): depth-0 blocks, depth-d bounds in-flight
steps, drain empties the queue.

Execution is observed through an ordered io_callback whose result feeds the
step's output — the step cannot complete without the host counter having
been bumped, so the counter is an exact executed-steps lower bound at every
block point.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro.core import dispatch


def _counted_step():
    counter = {"executed": 0}

    def bump(x):
        counter["executed"] += 1
        return np.int32(1)

    def step(x):
        inc = io_callback(bump, jax.ShapeDtypeStruct((), jnp.int32), x,
                          ordered=True)
        return x + inc          # value-depends on the callback

    return jax.jit(step), counter


def test_depth0_degrades_to_blocking():
    step, counter = _counted_step()
    q = dispatch.DispatchQueue(step, depth=0)
    x = jnp.int32(0)
    for i in range(1, 11):
        x = q.submit(x)
        # blocking mode: every submitted step has executed on return
        assert counter["executed"] == i
    assert int(x) == 10
    assert not q._inflight


def test_depth_bounds_inflight():
    for depth in (1, 2, 4):
        step, counter = _counted_step()
        q = dispatch.DispatchQueue(step, depth=depth)
        x = jnp.int32(0)
        n = 20
        for i in range(1, n + 1):
            x = q.submit(x)
            # at most `depth` steps may still be un-executed...
            assert counter["executed"] >= i - depth, (depth, i)
            # ...and the queue itself never tracks more than `depth`
            assert len(q._inflight) <= depth
        q.drain()
        assert counter["executed"] == n
        assert not q._inflight
        assert int(x) == n


def test_drain_empties_and_blocks_on_all():
    step, counter = _counted_step()
    q = dispatch.DispatchQueue(step, depth=8)
    x = jnp.int32(0)
    for _ in range(5):
        x = q.submit(x)
    q.drain()
    assert counter["executed"] == 5
    assert not q._inflight
    # queue is reusable after a drain
    x = q.submit(x)
    q.drain()
    assert counter["executed"] == 6 and int(x) == 6


def test_ideal_dispatcher_matches_loop():
    step = jax.jit(lambda x: x * 2 + 1)
    run = dispatch.ideal_dispatcher(lambda x: x * 2 + 1, 6)
    got = run(jnp.int32(1))
    want = jnp.int32(1)
    for _ in range(6):
        want = step(want)
    assert int(got) == int(want)


def test_blocks_count_into_host_blocked_s():
    """Every backpressure block is timed into ``counts``: the queue's own
    dict, or the one its owner passes (the serving engine's stats)."""
    step, _ = _counted_step()
    q = dispatch.DispatchQueue(step, depth=0)
    q.submit(jnp.int32(0))
    assert q.counts["host_blocked_s"] > 0
    shared = {"host_blocked_s": 1.0}
    q = dispatch.DispatchQueue(step, depth=1, counts=shared)
    for _ in range(3):
        q.submit(jnp.int32(0))
    q.drain()
    assert q.counts is shared and shared["host_blocked_s"] > 1.0
