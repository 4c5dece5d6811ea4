"""Work counting and peaks, against hand-computed decode steps."""
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import work  # noqa: E402

PEAKS = work.load_peaks("TPU v5 lite")


def shape(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           f"{name}.json")) as f:
        return work.Shape.from_config(json.load(f))


def test_peaks_by_device_kind():
    assert PEAKS.bf16_flops == 197e12 and PEAKS.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        work.load_peaks("TPU v4")


def test_phi3_decode_step_by_hand():
    s = shape("phi3-mini-3.8b")
    rows = [(1, 255)]          # one decode token at position 255
    g = work.gemm_work(s, rows, emitted=1, peaks=PEAKS)
    # Per layer 4*3072^2 + 3*3072*8192 = 113,246,208 weights; 32 layers
    # plus the 3072 x 32064 head = 3,722,379,264 weights, 2 FLOPs each.
    assert g.flops == 7_444_758_528
    # bf16 weights, plus (k + n) * 2 bytes of rows in and out per product:
    # 4 * 12,288 + 3 * 22,528 = 116,736 a layer, 70,272 for the head.
    assert g.bytes == 7_444_758_528 + 32 * 116_736 + 70_272
    # At one row every product is bound by its bytes.
    assert g.least_s == pytest.approx(g.bytes / 819e9, rel=1e-12)
    a = work.attention_work(s, rows, PEAKS)
    # 256 keys, 4 * 32 heads * 96 per key; K and V of 256 positions and
    # 32 heads, q and o of one: (16,384 + 64) * 96 * 2 bytes, 32 layers.
    assert a.flops == 32 * 4 * 32 * 96 * 256
    assert a.bytes == 32 * (16_384 + 64) * 96 * 2


def test_granite_decode_step_by_hand():
    s = shape("granite-moe-1b-a400m")
    rows = [(1, 127)]
    g = work.gemm_work(s, rows, emitted=1, peaks=PEAKS)
    # Per layer: q 2,097,152 + k 1,048,576 + v 1,048,576 + o 2,097,152
    # + router 65,536 + 3 expert products of 8 rows 8,388,608 each
    # = 31,522,816 FLOPs; 24 layers; head 2 * 1024 * 49155 = 100,669,440.
    assert g.flops == 24 * 31_522_816 + 100_669_440
    # Bytes per layer: q 2,101,248, k and v 1,051,648 each, o 2,101,248,
    # router 67,648, experts 3 * (8 experts * 1,048,576 + 24,576)
    # = 31,612,992; head 100,669,440 + 100,358.
    assert g.bytes == 24 * 31_612_992 + 100_769_798
    a = work.attention_work(s, rows, PEAKS)
    assert a.flops == 24 * 4 * 16 * 64 * 128
    assert a.bytes == 24 * (2 * 8 * 128 + 2 * 16) * 64 * 2


def test_prefill_chunk_counts_causal_keys_and_skips_padding():
    s = shape("granite-moe-1b-a400m")
    # A 64-token chunk at position 64 attends sum(p + 1, p = 64..127) keys.
    a = work.attention_work(s, [(64, 64)], PEAKS)
    assert a.flops == 24 * 4 * 16 * 64 * sum(range(65, 129))
    # Work grows with the tokens processed, never with idle slots.
    one = work.gemm_work(s, [(1, 10)], 1, PEAKS)
    assert work.gemm_work(s, [(1, 10)] + [], 1, PEAKS).flops == one.flops
    assert work.gemm_work(s, [], 0, PEAKS).flops == 0
    mf = work.model_flops(s, [(64, 0), (1, 300)], 1, PEAKS)
    assert mf == (work.gemm_work(s, [(64, 0), (1, 300)], 1, PEAKS).flops
                  + work.attention_work(s, [(64, 0), (1, 300)], PEAKS).flops)
