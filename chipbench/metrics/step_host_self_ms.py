"""Host time of an engine step not spent waiting on the device: mean
over the traced ``serving.step`` spans of their length less their
``serving.wait`` children, in ms."""
from chipbench import engine_spans


def read(run):
    return engine_spans.step_host_self_ms(run)
