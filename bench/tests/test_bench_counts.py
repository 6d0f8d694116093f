"""Peak table and the pass kernel's operation and byte counts."""
import pytest

from bench import counts, peaks


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    assert peaks.peak("TPU v5 lite").hbm_bytes_s == 819e9
    assert peaks.peak("TPU v5 lite").flops == 197e12


def test_pass_work_at_32_forks_and_256_slots():
    w = counts.pass_work(32, 256)
    # ops: the shadow's pairwise compare-accumulate, 2·k·J²
    assert w.ops == 2 * 32 * 256 * 256 == 4_194_304
    # bytes: six (k, J) inputs + one (k, J) output + three per-fork
    # scalars, 4 bytes each: 4·32·(7·256 + 3)
    assert w.bytes == 4 * 32 * (7 * 256 + 3) == 229_760


def test_the_pass_is_bound_by_bytes_on_v5e():
    least, bound = counts.least_seconds(counts.pass_work(32, 256),
                                        peaks.peak("TPU v5 lite"))
    assert bound == "bytes"
    assert least == pytest.approx(229_760 / 819e9)
