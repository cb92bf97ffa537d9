"""The trace reduction on a small recorded trace: one chip, a decode and
a prefill program, overlapping operations, a ``while`` op holding its
body's ops, kernels named by their HLO instruction or by their
``pallas_call`` metadata, and the benchmark's host spans
(``bench/testdata/small_trace.pbtxt``; times in ns)."""

import pytest

from bench import trace as T
from conftest import TESTDATA


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    with open(TESTDATA / "small_trace.pbtxt") as f:
        return T.from_profile(ProfileData.from_text_proto(f.read()))


def test_busy_is_a_union(summary):
    # [0, 3000) from a while op and the two ops nested in it,
    # [5000, 6000), [9000, 9500)
    assert summary.busy_s == pytest.approx(4500e-9)
    assert summary.window_s == pytest.approx(12000e-9)


def test_kernels_by_name_and_module(summary):
    assert summary.kernel_time("quantized_matmul") == \
        pytest.approx((2000e-9, 2))
    assert summary.kernel_time("quantized_matmul", module="decode_fn") == \
        pytest.approx((1500e-9, 1))
    assert summary.kernel_time("paged_decode", module="decode_fn") == \
        pytest.approx((1000e-9, 1))
    assert summary.module_time("decode_fn") == pytest.approx((6500e-9, 1))


def test_gaps_attributed_to_host_spans(summary):
    bd = summary.breakdown()
    assert [g[0] for g in bd["idle_gaps"]] == \
        ["bench.idle", "bench.tick", "bench.tick"]
    assert [g[1] for g in bd["idle_gaps"]] == \
        pytest.approx([3000e-9, 2500e-9, 2000e-9])
    ops = dict(bd["device_ops"])
    assert ops["quantized_matmul"] == pytest.approx(2000e-9)
    assert ops["fusion"] == pytest.approx(1500e-9)
    assert ops["paged_decode"] == pytest.approx(1000e-9)
    # the while op's own time: its span minus the two ops nested in it
    assert ops["while"] == pytest.approx(0.0, abs=1e-15)


def test_union_helpers():
    assert T.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.gaps([(0, 2), (1, 3), (5, 6)], 0, 8) == [(3, 5), (6, 8)]
