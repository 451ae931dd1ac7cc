"""The trace reduction: busy time is the union of op intervals inside the
window, op seconds are clipped to it and averaged over devices, idle gaps
are labelled by the benchmark span they fall in and the op before them."""
import pytest

from chipbench import trace


def _data():
    # ns; window [1000, 2000); device 0 has two overlapping ops, a gap of
    # 300 ns after "b" and an op that runs past the window's end
    return {
        "devices": {
            0: [("a", 900, 300),        # clipped to [1000, 1200)
                ("b", 1100, 200),       # overlaps a: union [1000, 1300)
                ("c", 1600, 600)],      # clipped to [1600, 2000)
            1: [("a", 1000, 500)],
        },
        "spans": [("bench.window", 1000, 1000), ("bench.inner", 1300, 100)],
    }


def test_busy_is_the_union_inside_the_window():
    red = trace.reduce(_data())
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s[0] == pytest.approx((300 + 400) * 1e-9)
    assert red.busy_s[1] == pytest.approx(500e-9)
    assert red.mean_busy_s == pytest.approx(600e-9)


def test_op_seconds_clipped_and_averaged_over_devices():
    red = trace.reduce(_data())
    # on device 0, b starts inside a and runs past it: the overlap is b's
    assert red.op_s["a"] == pytest.approx((100 + 500) / 2 * 1e-9)
    assert red.op_s["b"] == pytest.approx(200 / 2 * 1e-9)
    assert red.op_s["c"] == pytest.approx(400 / 2 * 1e-9)
    one = trace.reduce(_data(), devices=[1])
    assert one.op_s == pytest.approx({"a": 500e-9})


def test_gaps_longest_first_with_labels():
    red = trace.reduce(_data(), devices=[0])
    (label, secs), = red.gaps
    assert secs == pytest.approx(300e-9)
    # the gap [1300, 1600) has its midpoint in the window span only
    assert label == "bench.window, after b"


def test_window_needs_its_span():
    data = _data()
    data["spans"] = []
    with pytest.raises(ValueError):
        trace.reduce(data)
    red = trace.reduce(data, window=(0, 3000))
    assert red.gaps[0] == ("outside the benchmark's spans, after the "
                           "window's start", pytest.approx(900e-9))


def test_nested_ops_report_self_time():
    data = {"devices": {0: [("%while.1 = (...) while(...)", 0, 1000),
                            ("%fusion.2 = f32[] fusion(...)", 100, 300),
                            ("%fusion.2 = f32[] fusion(...)", 500, 300)]},
            "spans": [("bench.window", 0, 1000)]}
    red = trace.reduce(data)
    assert red.op_s == pytest.approx({"while.1": 400e-9, "fusion.2": 600e-9})
    assert red.busy_s[0] == pytest.approx(1000e-9)


def _mask_busy_and_longest_gap(events, lo, hi):
    """Nanosecond-grid reading of the same events, written independently."""
    import numpy as np

    busy = np.zeros(int(hi - lo), bool)
    for _, s, d in events:
        a, b = int(max(s, lo) - lo), int(min(s + d, hi) - lo)
        if b > a:
            busy[a:b] = True
    longest = run = 0
    for x in busy:
        run = 0 if x else run + 1
        longest = max(longest, run)
    return busy.sum() * 1e-9, longest * 1e-9


def test_recorded_chip_trace():
    import json
    from pathlib import Path

    rec = json.loads((Path(__file__).parent / "data"
                      / "charlstm_trace_3ms.json").read_text())
    data = {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in rec["devices"].items()},
            "spans": [tuple(s) for s in rec["spans"]]}
    lo, hi = rec["window"]
    red = trace.reduce(data, window=(lo, hi))
    busy, longest = _mask_busy_and_longest_gap(data["devices"][0], lo, hi)
    assert red.busy_s[0] == pytest.approx(busy, abs=2e-9)
    assert red.gaps[0][1] == pytest.approx(longest, abs=2e-9)
    assert red.gaps[0][0].startswith("bench.window, after ")
    # the round loop's while op holds the window's tail; its self time is
    # what its body ops leave uncovered
    assert 0 < red.op_s["while.215"] < (hi - lo) * 1e-9
    assert sum(red.op_s.values()) == pytest.approx(busy, rel=1e-6)


def test_an_op_running_past_its_loop_counts_once():
    """A loop body's last fusion that ends after its ``while`` (as the
    char-LSTM's backward loop shows on the chip): the overlap is the
    fusion's alone, and the self times add up to the busy time."""
    data = {"devices": {0: [("%while.1 = (...) while(...)", 0, 1000),
                            ("%fusion.2 = f32[] fusion(...)", 100, 300),
                            ("%fusion.3 = f32[] fusion(...)", 900, 250),
                            ("%copy.4 = f32[] copy(...)", 1100, 100)]},
            "spans": [("bench.window", 0, 2000)]}
    red = trace.reduce(data)
    # copy.4 starts in fusion.3 and runs past it in turn
    assert red.op_s == pytest.approx({"while.1": 600e-9, "fusion.2": 300e-9,
                                      "fusion.3": 200e-9, "copy.4": 100e-9})
    assert red.busy_s[0] == pytest.approx(1200e-9)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s[0])


@pytest.mark.parametrize("seed", range(4))
def test_self_times_add_up_to_the_busy_time(seed):
    """Random ops -- nested, overlapping, running past each other, cut by
    the window: every nanosecond of busy time is one op's."""
    import numpy as np

    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 10_000, 300).astype(float)
    durations = rng.integers(0, 400, 300).astype(float)
    data = {"devices": {0: [(f"%op.{i % 40} = f32[] fusion(...)", s, d)
                            for i, (s, d) in enumerate(zip(starts,
                                                           durations))]},
            "spans": [("bench.window", 500, 9000)]}
    red = trace.reduce(data)
    busy, _ = _mask_busy_and_longest_gap(data["devices"][0], 500, 9500)
    assert min(red.op_s.values()) >= 0
    assert sum(red.op_s.values()) == pytest.approx(busy, rel=1e-12)
    assert red.busy_s[0] == pytest.approx(busy, rel=1e-12)
