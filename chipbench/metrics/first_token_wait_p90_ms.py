"""Leaving WAITING to the first sampled token on the engine's clock
(``first_token_at - admitted_at``), p90 in ms: prefill and the first
token's readback; no first token counts as missing (+inf)."""
from chipbench import engine_spans


def read(run):
    return engine_spans.first_token_wait_p90_ms(run)
