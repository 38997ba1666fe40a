"""Tokens emitted per decode step over the window (engine counters):
the mean occupancy of the decode batch."""


def read(run):
    w = run.window
    steps = w.counters1["decode_steps"] - w.counters0["decode_steps"]
    if steps <= 0:
        return None
    return (w.counters1["tokens_out"] - w.counters0["tokens_out"]) / steps
