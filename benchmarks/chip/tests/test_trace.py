"""The reduction from a trace to the per-layer numbers.

A hand-made trace checks each number exactly; a small trace recorded on a
v5e (``data/``) checks that the reduction reads a real one."""

import json
import pathlib

import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6  # ns


def _hand():
    # window 0..100 ms (host spans); two chips
    host = [("bench.side_at", 0, 10 * MS), ("bench.step", 10 * MS, 5 * MS), ("bench.block", 15 * MS, 85 * MS)]
    dev0 = [
        ("while.1", 12 * MS, 38 * MS),
        ("fusion.2", 30 * MS, 20 * MS),  # inside while.1
        ("collective-permute-done.3", 60 * MS, 10 * MS),
        ("fusion.1", 90 * MS, 20 * MS),  # runs past the window: clipped at 100
    ]
    dev1 = [("fusion.1", 20 * MS, 40 * MS), ("collective-permute-done.3", 70 * MS, 30 * MS)]
    return tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host)


def test_window_and_busy_union():
    t = _hand()
    assert tr.window(t) == (0, 100 * MS)
    # chip 0: 12..50, 60..70, 90..100 = 58 ms; chip 1: 20..60, 70..100 = 70 ms
    assert tr.busy_seconds(t) == pytest.approx((0.058 + 0.070) / 2)


def test_collective_permute_seconds():
    t = _hand()
    assert tr.op_seconds(t, "collective-permute") == pytest.approx((0.010 + 0.030) / 2)
    assert tr.op_seconds(t, "all-reduce") is None


def test_top_ops_and_idle_gaps():
    t = _hand()
    # self time, ms over 2 chips: fusion.1 10 (clipped) + 40, the permutes
    # 10 + 30, fusion.2 20, while.1 38 less the 20 of fusion.2 inside it
    top = tr.top_ops(t)
    assert [n for n, _ in top] == ["fusion.1", "collective-permute-done.3", "fusion.2", "while.1"]
    assert [v for _, v in top] == pytest.approx([0.025, 0.020, 0.010, 0.009])
    gaps = sorted((name, round(sec, 9)) for name, sec in tr.idle_gaps(t))
    # chip 0 idles 0..12 (side_at most), 50..60 and 70..90 (block);
    # chip 1 idles 0..20 (side_at 10 ms of it) and 60..70 (block)
    assert gaps == [
        ("bench.block", 0.01), ("bench.block", 0.01), ("bench.block", 0.02),
        ("bench.side_at", 0.012), ("bench.side_at", 0.02),
    ]


def test_nothing_on_the_device_reads_nothing():
    t = tr.Trace({}, [("bench.step", 0, MS)])
    assert tr.busy_seconds(t) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("*.json")), ids=lambda p: p.stem)
def test_recorded_trace(path):
    t = tr.load(str(path))
    t0, t1 = tr.window(t)
    win = (t1 - t0) / 1e9
    busy = tr.busy_seconds(t)
    assert 0 < busy <= win
    for evs in t.devices.values():
        spans = tr.busy_intervals(evs, t0, t1)
        assert all(a < b <= c for (a, b), (c, _) in zip(spans, spans[1:]))
    top = tr.top_ops(t)
    assert 0 < len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    gaps = tr.idle_gaps(t)
    assert all(name.startswith("bench.") or name == "host.other" for name, _ in gaps)
    meta = json.load(open(path)).get("expect", {})
    if "ppermute" in meta:
        assert (tr.op_seconds(t, "collective-permute") is not None) == meta["ppermute"]
