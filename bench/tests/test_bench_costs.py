"""Kernel operation and byte counts against hand counts; the peak table."""
import pytest

from bench import costs, devtrace


def test_spmm_counts():
    ops = [("s32", (3,)), ("s32", (3,)), ("f32", (3, 64, 64)),
           ("f32", (256, 4))]
    flops, nbytes = costs.spmm_blocksparse(("f32", (192, 4)), ops)
    assert flops == 2 * 3 * 64 * 64 * 4
    assert nbytes == 12 + 12 + 3 * 64 * 64 * 4 + 256 * 4 * 4 + 192 * 4 * 4


def test_gram_counts():
    ops = [("f32", (512, 32)), ("f32", (512, 4)), ("f32", (1,))]
    flops, nbytes = costs.gram(("f32", (32, 4)), ops)
    assert flops == 2 * 512 * 32 * 4
    assert nbytes == 4 * (512 * 32 + 512 * 4 + 1 + 32 * 4)


def test_tsgemm_counts():
    ops = [("f32", (512, 32)), ("f32", (32, 4)), ("f32", (512, 4)),
           ("f32", (1,)), ("f32", (1,))]
    flops, nbytes = costs.tsgemm(("f32", (512, 4)), ops)
    assert flops == 2 * 512 * 32 * 4 + 3 * 512 * 4
    assert nbytes == 4 * (512 * 32 + 32 * 4 + 512 * 4 + 1 + 1 + 512 * 4)


def test_shapes_read_from_hlo_text():
    text = ("%custom-call.1 = f32[192,4]{1,0:T(8,128)} custom-call("
            "s32[3]{0} %p0, s32[3]{0} %p1, f32[3,64,64]{2,1,0} %p2, "
            "f32[256,4]{1,0} %p3), custom_call_target=\"tpu_custom_call\"")
    res, ops = devtrace.shapes(text)
    assert res == ("f32", (192, 4))
    assert ops == [("s32", (3,)), ("s32", (3,)), ("f32", (3, 64, 64)),
                   ("f32", (256, 4))]


def test_least_time_is_the_larger_bound():
    peak = costs.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["flops_per_s"] == 197e12
    assert costs.least_seconds(2 * 819e9, 819e9, peak) == pytest.approx(1.0)
    assert costs.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
