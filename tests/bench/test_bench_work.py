"""The FLOP and byte floors behind the roofline and MFU metrics, counted by
the vq-opt configurations' module and summed by ``work.dispatch_totals``,
against shapes worked out by hand. They count real documents, real rows and
the edits taken, never padded capacity or the program's state copies."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))

import run  # noqa: E402
import work  # noqa: E402

vq = run.reference(run.load_json(run.ROOT, "bench", "configs",
                                 "vq-opt-125m.json")["reference"])

# a small model: d 8, 2 heads of 4, 3 codes, MLP 16, 2 layers, 2 VQ heads
M = {"d_model": 8, "n_heads": 2, "codebook_size": 3, "d_ff": 16,
     "n_layers": 2, "vq_heads": 2}


def test_kernel_flops_by_hand():
    # n = 10 rows; one replace = 2 column passes (old out, new in); per
    # layer and column: scores 2*n*H*dh = 160, accumulation 2*n*H*Q = 120
    assert vq.kernel_flops(M, 10, 1, 0, 0) == 2 * 2 * (160 + 120)
    # an insert or a delete is one column pass
    assert vq.kernel_flops(M, 10, 0, 1, 0) == 2 * (160 + 120)
    assert vq.kernel_flops(M, 10, 0, 0, 1) == 2 * (160 + 120)


def test_kernel_bytes_by_hand():
    # per layer and real row: q read H*dh*4 = 32, T read + write 2*H*Q*4 =
    # 48, codes written hq*4 = 8; nothing when the document took no edit
    assert vq.kernel_bytes(M, 10, 0, 1, 0) == 2 * 10 * (32 + 48 + 8)
    assert vq.kernel_bytes(M, 10, 0, 0, 0) == 0.0


def test_edit_flops_by_hand():
    # the replaced row at each layer: qkv 2*8*3*8 = 384, value codes
    # 2*2*4*3 = 48, attention over 10 columns 2*10*2*4 + 2*10*2*3 = 280,
    # MLP 2*2*8*16 = 512; plus the kernel's two column passes
    rows = 2 * (384 + 48 + 280 + 512)
    assert vq.edit_flops(M, 10, 1, 0, 0) == rows + vq.kernel_flops(
        M, 10, 1, 0, 0)
    # a delete recomputes no row: only its column leaves the sums
    assert vq.edit_flops(M, 10, 0, 0, 1) == vq.kernel_flops(M, 10, 0, 0, 1)


def test_floors_scale_with_real_length_not_capacity():
    """Twice the real rows, twice the patch work: the floor follows n, so a
    document in a half-empty capacity class is not charged for the empty
    half."""
    assert vq.kernel_flops(M, 20, 1, 0, 0) == 2 * vq.kernel_flops(
        M, 10, 1, 0, 0)
    assert vq.kernel_bytes(M, 20, 1, 0, 0) == 2 * vq.kernel_bytes(
        M, 10, 1, 0, 0)


def test_dispatch_totals_sum_documents():
    dispatches = [[(10, 1, 0, 0), (12, 0, 2, 0)], [(10, 0, 0, 1)]]
    tot = work.dispatch_totals(vq, M, dispatches)
    assert tot["dispatches"] == 2
    assert tot["kernel_flops"] == (vq.kernel_flops(M, 10, 1, 0, 0)
                                   + vq.kernel_flops(M, 12, 0, 2, 0)
                                   + vq.kernel_flops(M, 10, 0, 0, 1))
    assert tot["edit_flops"] > tot["kernel_flops"]


def test_dispatch_totals_match_the_literal_formulas():
    """The sums the roofline and MFU readers take, against the hand-worked
    numbers above: a replace (2 column passes), an insert and a delete (1
    each) on 10 rows, and a replace on 20."""
    tot = work.dispatch_totals(vq, M, [[(10, 1, 0, 0), (10, 0, 1, 0)],
                                       [(10, 0, 0, 1)], [(20, 1, 0, 0)]])
    column = 2 * (160 + 120)  # one column pass on 10 rows, both layers
    assert tot["kernel_flops"] == 2 * column + column + column + 2 * 2 * column
    assert tot["kernel_bytes"] == (3 * 2 * 10 * (32 + 48 + 8)
                                   + 2 * 20 * (32 + 48 + 8))
    # rows recomputed: the replace and the insert on 10 rows, the replace
    # on 20 (attention over 20 columns: 560)
    rows = 2 * (384 + 48 + 280 + 512) * 2 + 2 * (384 + 48 + 560 + 512)
    assert tot["edit_flops"] == rows + tot["kernel_flops"]
    assert tot["dispatches"] == 3


def test_peaks_unknown_device_is_an_error():
    assert work.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")
