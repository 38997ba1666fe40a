"""Kernel and step operation counts, by hand, at live lengths."""
import dataclasses

import pytest

from chipbench import flops, peaks, spec


@dataclasses.dataclass
class Arch:
    n_layers: int = 2
    d_model: int = 8
    n_heads: int = 4
    n_kv_heads: int = 2
    hd: int = 2
    d_ff: int = 16
    vocab: int = 10
    act: str = "silu_gated"


ARCH = Arch()


def test_flash_decode_counts_live_rows_only():
    cost = spec.kernel_cost("flash_decode").cost
    # two live slots of 3 and 5 rows: an arena of 4641 rows changes nothing
    f, b = cost(ARCH, [3, 5], kv_bytes=2)
    assert f == 4 * 4 * 2 * (3 + 5) * 2                 # 4·H·hd·rows·layers
    kv = 2 * 2 * 2 * 2 * (3 + 5)                       # K,V·KVH·hd·bytes·rows
    qo = 2 * 4 * 2 * 2 * 2                              # (q,out)·H·hd·bf16·slots
    assert b == (kv + qo) * 2


def test_flash_prefill_chunk_counts_valid_causal_pairs():
    cost = spec.kernel_cost("flash_prefill_chunk").cost
    # chunk of 32 at row 10 with 3 valid tokens: pairs 3*10 + (1+2+3)
    f, b = cost(ARCH, (10, 32, 3), kv_bytes=2)
    assert f == 4 * 4 * 2 * (30 + 6) * 2
    assert b == (2 * 2 * 2 * 2 * 13 + 2 * 4 * 2 * 2 * 3) * 2


def test_step_flops_by_hand():
    per_layer = 8 * 2 * (4 + 2 * 2) + 4 * 2 * 8 + 3 * 8 * 16
    assert flops.layer_matmul_params(ARCH) == per_layer
    d = flops.decode_step(ARCH, [3, 5])
    assert d == 2 * 2 * (2 * per_layer + 8 * 10) + 4 * 4 * 2 * 2 * 8
    c = flops.chunk_step(ARCH, 10, 3)
    assert c == 2 * 3 * 2 * per_layer + 2 * 8 * 10 + 4 * 4 * 2 * 2 * 36


def test_peaks_table_and_unknown_device():
    assert peaks.least_time(197e12, 0, "TPU v5 lite") == pytest.approx(1.0)
    assert peaks.least_time(0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
