"""``trace_reduce`` on the CPU: a hand-made trace with known numbers,
and a short window recorded on a TPU v5e reduced to fixed numbers."""
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import trace_reduce  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
US = 1000.0  # ns


def _hand_made():
    # window 0..100 us; device 0 busy 10-30 and 25-40 (overlap) and
    # 70-80; device 1 busy 0-50.  Host: step 0-100, region 0-60 holding
    # result 45-60, flush 60-100.
    host = [["bench.window", 0, 100 * US], ["bench.step", 0, 100 * US],
            ["bench.region", 0, 60 * US], ["bench.result", 45 * US, 60 * US],
            ["bench.flush", 60 * US, 100 * US]]
    d0 = {"ops": [["fusion", 10 * US, 30 * US], ["fusion", 25 * US, 40 * US],
                  ["kernel", 70 * US, 80 * US]],
          "modules": [["jit_apply_fn(1)", 10 * US, 40 * US],
                      ["jit_other", 70 * US, 80 * US]]}
    d1 = {"ops": [["kernel", -5 * US, 50 * US]],
          "modules": [["jit_apply_fn(1)", -5 * US, 50 * US]]}
    return {"host": host,
            "devices": {"/device:TPU:0": d0, "/device:TPU:1": d1}}


def test_reduce_a_hand_made_trace():
    r = trace_reduce.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["devices"] == 2
    # busy: device 0 30+10 us, device 1 50 us (clipped at 0) -> mean 45
    assert r["busy_s"] == pytest.approx(45e-6)
    assert trace_reduce.module_time(r, "jit_apply_fn") == \
        (2, pytest.approx(80e-6))
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(35e-6 / 2)
    assert ops["kernel"] == pytest.approx(60e-6 / 2)
    # device 0 idle 0-10 (region), 40-70 (mid 55: result), 80-100
    # (flush); device 1 idle 50-100 (mid 75: flush); halved per device
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.region": 5e-6,
                                  "bench.result": 15e-6,
                                  "bench.flush": 35e-6})


def test_reduce_needs_one_window_and_a_device():
    ex = _hand_made()
    ex["host"] = ex["host"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(ex)
    ex = _hand_made()
    ex["devices"] = {}
    with pytest.raises(ValueError, match="no TPU"):
        trace_reduce.reduce(ex)


def test_reduce_a_window_recorded_on_the_chip():
    """Two steps of ``binomial-ranks`` traced on a TPU v5e (64 callers x
    512 rows): one apply program a step, 64 input and 64 output bridge
    programs, and the device idle for all but about 1% of the window."""
    r = trace_reduce.reduce(trace_reduce.extract(trace_reduce.load(
        DATA / "binomial-ranks-2steps.xplane.pb.gz")))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.156939835)
    assert r["busy_s"] == pytest.approx(0.001883828)
    assert trace_reduce.module_time(r, "jit_apply_fn") == \
        (2, pytest.approx(0.001695263))
    assert trace_reduce.module_time(r, "jit_bridge_from") == \
        (128, pytest.approx(7.3581e-05))
    assert r["device_ops"][0] == ["%apply_fn.1 custom-call",
                                  pytest.approx(0.001628208)]
    assert r["idle_gaps"][0] == ["bench.region", pytest.approx(0.098444643)]
    assert len(r["device_ops"]) == len(r["idle_gaps"]) == trace_reduce.TOP


def _recorded():
    return trace_reduce.extract(trace_reduce.load(
        DATA / "binomial-ranks-2steps.xplane.pb.gz"))


@pytest.mark.parametrize("make", [_hand_made, _recorded])
def test_ops_keep_every_op_time(make):
    """``ops`` holds every op name's seconds per chip: they sum to all the
    op time in the window, and ``device_ops`` is their longest ten."""
    ex = make()
    r = trace_reduce.reduce(ex)
    t0, t1 = trace_reduce.window_of(ex)
    total = sum(min(b, t1) - max(a, t0) for d in ex["devices"].values()
                for _, a, b in d["ops"] if b > t0 and a < t1)
    assert sum(r["ops"].values()) == \
        pytest.approx(total / len(ex["devices"]) * 1e-9, rel=1e-12)
    longest = sorted(r["ops"].items(), key=lambda kv: -kv[1])
    assert r["device_ops"] == [list(kv) for kv in longest[:trace_reduce.TOP]]
