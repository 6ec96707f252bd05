"""The trace reduction on a constructed trace."""
import pytest

from bench.lib import tracing


def _trace():
    ms = 1_000_000
    dev0 = [("custom-call.1", 10 * ms, 40 * ms),      # kernel 10-50
            ("fusion.3", 45 * ms, 10 * ms),           # overlaps it: 50-55
            ("all-gather.2", 60 * ms, 5 * ms),        # collective 60-65
            ("fusion.3", 80 * ms, 10 * ms),           # 80-90
            ("fusion.9", 95 * ms, 20 * ms)]           # clipped to 95-100
    dev1 = [("custom-call.1", 0, 100 * ms)]
    spans = [("bench.window", 0, 100 * ms),
             ("bench.dispatch", 0, 9 * ms),
             ("bench.read", 55 * ms, 30 * ms),
             ("bench.pop", 66 * ms, 2 * ms)]
    return {"devices": [dev0, dev1], "spans": spans}


def test_reduce_one_device():
    r = tracing.reduce(_trace(), 1)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["kernel_s"] == pytest.approx(0.040)
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["other_s"] == pytest.approx(0.025)
    # union: 10-55, 60-65, 80-90, 95-100
    assert r["busy_s"] == pytest.approx(0.065)
    assert r["device_ops"][0] == ["custom-call.1", pytest.approx(0.040)]
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.015, 0.010, 0.005, 0.005])
    # 65-80 lies mostly in bench.read; 0-10 in bench.dispatch
    assert gaps[0][0] == "bench.read"
    assert gaps[1][0] == "bench.dispatch"


def test_reduce_averages_over_chips():
    r = tracing.reduce(_trace(), 2)
    assert r["busy_s"] == pytest.approx((0.065 + 0.1) / 2)
    assert r["kernel_s"] == pytest.approx((0.040 + 0.1) / 2)


def test_reduce_counts_nested_ops_once():
    """A ``while`` op spans the ops of its body on the same line; a
    consumer that takes a custom call's result is not a kernel."""
    ms = 1_000_000
    dev = [("%while.4 = (f32[2]) while(f32[2] %p), body=%b", 0, 50 * ms),
           ("%fusion.1 = f32[8] fusion(f32[8] %x)", 10 * ms, 10 * ms),
           ("%k.1 = f32[8] custom-call(f32[8] %y), custom_call_target="
            "\"tpu_custom_call\"", 30 * ms, 10 * ms),
           ("%copy.2 = f32[8] copy(f32[8] %custom-call.7)", 60 * ms, 5 * ms),
           ("%ag.1 = f32[8] all-gather(f32[2] %z)", 70 * ms, 5 * ms)]
    r = tracing.reduce({"devices": [dev], "spans": []}, 1)
    assert r["busy_s"] == pytest.approx(0.060)
    assert r["kernel_s"] == pytest.approx(0.010)
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["other_s"] == pytest.approx(0.045)
    assert r["device_ops"][0] == [dev[0][0], pytest.approx(0.030)]


def test_reduce_without_device_events_is_empty():
    assert tracing.reduce({"devices": [[]], "spans": []}, 1) == {}
