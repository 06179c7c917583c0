"""The roofline count depends on sizes, iterations and device kind only, and
no plan that does the same problem can read above 100% against it."""
import inspect

import pytest

from bench import roofline

SIZES = dict(n=2 ** 19, m=2 ** 19, r=256, d=28, iters=250)
KIND = "TPU v5 lite"


def plan_seconds(plan, n, m, r, d, iters):
    """The time each plan would take at the chip's peaks, by its own count
    of the work it does (a lower bound for that plan)."""
    p = roofline.peaks(KIND)
    f32 = 4
    io = f32 * ((n + m) * d + r * d + 2 * (n + m) + n + m)
    feature_ops = 2 * (n + m) * r * d
    if plan == "per_iteration":          # factors streamed twice an iteration
        ops = feature_ops + 4 * (n + m) * r * iters
        byts = io + f32 * (n + m) * r * (1 + 2 * 2 * iters)
    elif plan == "fused_recompute":      # features recomputed from the points
        ops = (feature_ops + 4 * (n + m) * r) * iters
        byts = io + f32 * (n + m) * d * 2 * iters
    elif plan == "vmem_resident":        # everything held in fast memory
        ops = feature_ops + 4 * (n + m) * r * iters
        byts = io
    else:
        raise ValueError(plan)
    return max(ops / p["flops_per_s"], byts / p["hbm_bytes_per_s"])


def test_takes_no_plan():
    params = set(inspect.signature(roofline.least_seconds).parameters)
    assert params == {"n", "m", "r", "d", "iters", "device_kind", "chips"}


@pytest.mark.parametrize("plan", ["per_iteration", "fused_recompute",
                                  "vmem_resident"])
@pytest.mark.parametrize("sizes", [SIZES, dict(SIZES, n=1024, m=1024, d=3,
                                               iters=40)])
def test_no_plan_reads_above_100_percent(plan, sizes):
    least = roofline.least_seconds(**sizes, device_kind=KIND)
    assert least > 0
    assert 100.0 * least / plan_seconds(plan, **sizes) <= 100.0 + 1e-9


def test_same_value_whatever_plan_ran():
    a = roofline.least_seconds(**SIZES, device_kind=KIND)
    b = roofline.least_seconds(**SIZES, device_kind=KIND)
    assert a == b
    assert roofline.least_seconds(**SIZES, device_kind=KIND, chips=4) == \
        pytest.approx(a / 4)


def test_peaks_table():
    p = roofline.peaks(KIND)
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.least_seconds(**SIZES, device_kind="cpu")
