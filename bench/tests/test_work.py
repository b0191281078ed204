"""Work counts and peaks, each against a count made by hand."""
import numpy as np
import pytest

from bench.harness import spec, work

from .conftest import ROOT


class D:  # a toy model: 2 layers, d 8, ff 16, 4 heads of 2, 2 kv heads
    layers, d_model, d_ff, heads, kv_heads, head_dim, vocab = 2, 8, 16, 4, 2, 2, 10


def test_linear_and_logit_flops():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 -> 64+32+32+64+384 = 576 MACs
    assert work.linear_flops_per_token(D) == 2 * 576 * 2
    assert work.logit_flops(D) == 2 * 8 * 10


def test_contexts_follow_the_segment_spans():
    # a doc token at slot 5 of a doc starting at 3 (no prelude) sees slots 3..5;
    # a tail token at slot 9 sees 0..9
    ctx = work.token_contexts([5, 9], [0, 0], [3, 0])
    assert list(ctx) == [3, 10]


def test_chunk_kernel_counts_each_rows_slots_once():
    # row 0: doc tokens at slots 4,5 (doc starts at 4) and tail tokens 6,7;
    # row 1: one decode token at slot 2
    row_of = [0, 0, 0, 0, 1]
    slots = [4, 5, 6, 7, 2]
    p_end = [0, 0, 0, 0, 0]
    s_start = [4, 4, 0, 0, 0]
    flops, nbytes = work.chunk_kernel_work(D, 2, 2, row_of, slots, p_end, s_start)
    ctx = 1 + 2 + 7 + 8 + 3
    assert flops == 4 * 4 * 2 * ctx * 2
    kv_slots = 8 + 3                  # row 0 reads 0..7 once, row 1 reads 0..2
    assert nbytes == (kv_slots * 2 * 2 * 2 * 2 + 5 * 4 * 2 * 2 * 2) * 2


def test_decode_kernel_and_step_flops():
    flops, nbytes = work.decode_kernel_work(D, 1, 2, [3, 5])
    assert flops == 4 * 4 * 2 * 8 * 2
    assert nbytes == (8 * 2 * 2 * 2 * 1 + 2 * 4 * 2 * 2 * 2) * 2
    plan = {"kind": "decode", "ctx": np.array([3, 5]), "sampled": 2}
    assert work.step_model_flops(D, plan) == (
        2 * work.linear_flops_per_token(D) + flops + 2 * work.logit_flops(D))


def test_min_time_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.min_time(1000.0, 50.0, peaks) == 10.0
    assert work.min_time(100.0, 50.0, peaks) == 5.0


def test_peaks_known_and_unknown_kind():
    p = spec.load_peaks("TPU v5 lite", ROOT)
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99", ROOT)
