"""Scheduled arrival to leaving WAITING (admitted to a slot), p90 in ms;
a request never admitted counts as missing (+inf)."""
from chipbench import readings


def read(run):
    return readings.percentile(
        [(c.admitted - c.due) * 1e3 if c.admitted is not None
         else float("inf") for c in run.window.clients], 90)
