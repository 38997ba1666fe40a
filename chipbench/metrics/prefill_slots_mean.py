"""Slots held PREFILLING per engine step over the window (engine
counters ``slot_steps_prefilling`` / ``steps``)."""
from chipbench import engine_spans


def read(run):
    return engine_spans.prefill_slots_mean(run)
