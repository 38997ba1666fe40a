"""Share of the traced window in which the device is idle between two
operations while the host works inside the engine (a ``serving.*`` span,
not ``serving.wait`` or ``serving.gc``), in %."""
from chipbench import engine_spans


def read(run):
    return engine_spans.idle_host_work_share(run)
