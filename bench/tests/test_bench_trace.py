"""The trace reduction: busy union, idle share, top operations, exposed
collectives and idle gaps named by host spans, on events written by hand and
on a small trace recorded on a TPU v5e (``fixtures/tiny_tpu.xplane.pb``)."""
from pathlib import Path

import pytest

from bench import tracefile
from bench.tracefile import Event, summarize, union

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny_tpu.xplane.pb"


def test_union_merges_overlaps_and_drops_empty():
    assert union([(5, 15), (0, 10), (20, 30), (30, 31), (40, 40)]) == \
        [(0, 15), (20, 31)]


def hand_trace():
    ops = {
        "/device:TPU:0": [Event("fusion.1", 100, 200), Event("fusion.2", 150, 260),
                          Event("all-reduce.3", 300, 400),
                          Event("fusion.4", 350, 420)],
        "/device:TPU:1": [Event("fusion.1", 100, 300),
                          Event("all-reduce.3", 300, 400)],
    }
    host = [Event("bench.call", 100, 120), Event("bench.block", 120, 500),
            Event("host_work", 410, 470)]
    return ops, host


def test_busy_idle_and_collectives():
    s = summarize(*hand_trace())
    assert s.window_s == pytest.approx(400e-9)
    # device 0 busy [100, 260] + [300, 420] = 280; device 1 busy 300
    assert s.busy_s == pytest.approx(290e-9)
    assert s.idle_share == pytest.approx(1 - 290 / 400)
    # exposed collective: device 0 [300, 350] = 50, device 1 100
    assert s.collective_exposed_s == pytest.approx(75e-9)
    assert s.devices == 2


def test_top_ops_are_per_device_seconds_most_first():
    s = summarize(*hand_trace())
    names = [n for n, _ in s.device_ops]
    assert names[0] == "fusion.1"
    assert dict(s.device_ops)["fusion.1"] == pytest.approx(150e-9)
    assert dict(s.device_ops)["all-reduce.3"] == pytest.approx(100e-9)


def test_idle_gaps_take_the_innermost_host_span():
    s = summarize(*hand_trace())
    gaps = dict(s.idle_gaps)
    # device 0 idles [260, 300] (midpoint under bench.block alone) and
    # [420, 500] (midpoint 460 also under host_work, the innermost);
    # device 1 idles [400, 500] (midpoint 450 under host_work)
    assert gaps == {"bench.block": pytest.approx(20e-9),
                    "host_work": pytest.approx(90e-9)}


def test_no_collectives_reads_none():
    ops, host = hand_trace()
    ops = {k: [e for e in v if "all-reduce" not in e.name]
           for k, v in ops.items()}
    assert summarize(ops, host).collective_exposed_s is None


def test_no_device_ops_raises():
    with pytest.raises(ValueError):
        summarize({}, [])


def test_nested_ops_count_once():
    ops = {"/device:TPU:0": [Event("while.1", 0, 100), Event("fusion.2", 10, 40),
                             Event("fusion.3", 50, 90)]}
    s = summarize(ops, [])
    assert dict(s.device_ops) == {"fusion.2": pytest.approx(30e-9),
                                  "fusion.3": pytest.approx(40e-9)}


def test_recorded_tpu_trace():
    ops, host = tracefile.read_xplane(str(FIXTURE))
    assert ops and all(k.startswith("/device:TPU:") for k in ops)
    assert sum(e.name == tracefile.CALL_SPAN for e in host) == 3
    s = summarize(ops, host)
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert s.device_ops and all(v > 0 for _, v in s.device_ops)
    assert s.collective_exposed_s is None
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
