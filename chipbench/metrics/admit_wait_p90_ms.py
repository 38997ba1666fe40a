"""Submission to leaving WAITING on the engine's clock (``RequestState``
``admitted_at - submitted_at``), p90 in ms; a request never admitted
counts as missing (+inf)."""
from chipbench import engine_spans


def read(run):
    return engine_spans.admit_wait_p90_ms(run)
