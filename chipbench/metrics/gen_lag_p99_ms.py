"""How late the load generator sent requests: p99 of actual send time
minus scheduled time, in ms."""
from chipbench import readings


def read(run):
    return readings.percentile(
        [(c.sent - c.due) * 1e3 for c in run.window.clients], 99)
