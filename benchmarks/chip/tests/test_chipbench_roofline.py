"""The search's operations and bytes from shapes, and the peak table."""

import pytest

from chipbench.peaks import PEAKS, UnknownDevice, peaks_for, search_least_seconds, search_work


def test_search_work_from_shapes():
    flops, bytes_ = search_work(nq=8, k=10, rows=2_681_468, dim=768)
    assert flops == 2 * 8 * 2_681_468 * 768
    # every row read once, the queries read once, (score, id) pairs written once
    assert bytes_ == 4 * 2_681_468 * 768 + 4 * 8 * 768 + 8 * 8 * 10


def test_least_time_is_the_larger_bound():
    v5e = PEAKS["TPU v5 lite"]
    # 8 queries over the NQ index: bandwidth-bound, ~10.06 ms at 819 GB/s
    t = search_least_seconds(8, 10, 2_681_468, 768, v5e)
    assert t == pytest.approx((4 * 2_681_468 * 768 + 4 * 8 * 768 + 640) / 819e9)
    # 4096 queries: compute-bound against the bf16 peak
    t = search_least_seconds(4096, 10, 2_681_468, 768, v5e)
    assert t == pytest.approx(2 * 4096 * 2_681_468 * 768 / 197e12)


def test_peak_table_entry_for_v5e():
    assert peaks_for("TPU v5 lite") == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    }


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5p", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)
