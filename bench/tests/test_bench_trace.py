"""The trace reduction on synthetic event lists and on a small trace
recorded on a TPU v5e chip (``bench/tests/data``)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import tracereduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    return tr.Trace(
        window=(1.0, 11.0),
        device={"/device:TPU:0": [
            ("%fusion.1 = u32[8]{0:T(8)} fusion(u32[8]{0} %a), kind=kLoop",
             0.5, 2.0),
            ("%_window_topk_impl.3 = (s32[1,1,256]{2,1,0}, f32[1,1,256]{2,1,"
             "0}) custom-call(s32[1]{0} %x)", 2.0, 5.0),
            ("%copy.2 = u32[2097152,8]{1,0:T(8,128)} copy(u32[2097152,8]{0,1"
             ":T(8,128)} %folded.1)", 4.0, 6.0),
            ("%fusion.1 = u32[8]{0:T(8)} fusion(u32[8]{0} %a), kind=kLoop",
             12.0, 13.0)]},
        host=[("bench.wait_arrival", 0.0, 11.0),
              ("bench.batch", 1.0, 6.5),
              ("PjitFunction(run)", 6.5, 7.5),
              ("bench.draw", 8.0, 8.2)])


def test_union_of_intervals():
    assert tr.union_s([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert tr.union_s([(3, 4), (0, 10)]) == 10
    assert tr.union_s([]) == 0


def test_busy_kernel_and_idle_inside_the_window():
    t = synthetic()
    assert tr.busy_s(t) == pytest.approx(5.0)        # 1..6, clipped at 1
    assert tr.kernel_s(t, ("_window_topk_impl",)) == pytest.approx(3.0)
    assert tr.kernel_s(t, ("_tanimoto_topk_impl",)) == 0
    assert tr.idle_share(t) == pytest.approx(50.0)
    assert tr.gaps(t) == [(6.0, 11.0)]


def test_busy_is_averaged_over_devices():
    t = synthetic()
    t.device["/device:TPU:1"] = [("fusion.9", 1.0, 2.0)]
    assert tr.busy_s(t) == pytest.approx(3.0)


def test_op_labels_drop_layouts():
    t = synthetic()
    names = [tr.op_label(n) for n, _, _ in t.device["/device:TPU:0"]]
    assert names[1] == "_window_topk_impl.3 custom-call (s32[1,1,256], " \
        "f32[1,1,256])"
    assert names[2] == "copy.2 copy u32[2097152,8]"
    assert tr.op_label("fusion") == "fusion"


def test_idle_time_goes_to_what_the_host_did_stretch_by_stretch():
    t = synthetic()
    # the program's span beats the benchmark's, the benchmark's own beat
    # its waiting spans
    assert tr.idle_by_activity([(6.0, 11.0)], t.host) == pytest.approx({
        "bench.batch": 0.5, "PjitFunction(run)": 1.0,
        "bench.wait_arrival": 3.3, "bench.draw": 0.2})
    assert tr.idle_by_activity([(20.0, 21.0)], t.host) == {"unattributed": 1.0}
    nested = [("outer", 0.0, 10.0), ("inner", 2.0, 3.0),
              ("bench.await_answer", 0.0, 10.0)]
    assert tr.idle_by_activity([(1.0, 4.0)], nested) == pytest.approx(
        {"outer": 2.0, "inner": 1.0})
    assert tr.idle_by_activity([(1.0, 2.0), (2.5, 4.0)], nested) == \
        pytest.approx({"outer": 2.0, "inner": 0.5})


def test_breakdown_lists_ops_and_idle_by_host_activity():
    t = synthetic()
    b = tr.breakdown(t)
    assert b["device_ops"][0] == [
        "_window_topk_impl.3 custom-call (s32[1,1,256], f32[1,1,256])",
        pytest.approx(3.0)]
    assert len(b["device_ops"]) == 3
    assert b["idle_gaps"][0] == ["bench.wait_arrival", pytest.approx(3.3)]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(5.0)
    many = tr.Trace(window=(0, 100), device={"d": [
        (f"op{i}", float(i), i + 0.5) for i in range(30)]})
    b = tr.breakdown(many)
    assert len(b["device_ops"]) == tr.TOP
    assert b["idle_gaps"] == [["unattributed", pytest.approx(85.0)]]


def test_a_trace_recorded_on_the_chip():
    """Two 1,024-query batches of ``chembl27-bitbound.screen`` on one TPU v5e
    (the row-window kernel does nearly all the device's work)."""
    t = tr.load(DATA)
    lo, hi = t.window
    assert list(t.device) == ["/device:TPU:0"]
    assert hi - lo == pytest.approx(2.668, abs=0.01)
    assert tr.busy_s(t) == pytest.approx(2.646, abs=0.01)
    assert tr.kernel_s(t, ("_window_topk_impl",)) == pytest.approx(
        2.621, abs=0.01)
    assert tr.kernel_s(t, ("_tanimoto_topk_impl",)) == 0
    b = tr.breakdown(t)
    assert b["device_ops"][0][0].startswith("_window_topk_impl")
    assert len(b["device_ops"]) == tr.TOP
    every = tr.idle_by_activity(tr.gaps(t), t.host)
    assert sum(every.values()) == pytest.approx(hi - lo - tr.busy_s(t))
    assert "bench.batch" in every
    assert every["unattributed"] < 0.01 * sum(every.values())
    assert b["idle_gaps"] == sorted(([n, v] for n, v in every.items()),
                                    key=lambda x: -x[1])[:tr.TOP]
