"""CPU tests of the readers of the engine's own telemetry
(``chipbench/engine_trace.py`` and the metrics that call it): each reader
on a hand-filled telemetry snapshot and a hand-built reduced trace, the
mapping of the engine's spans onto the trace's clock, and a tiny traced
closed-loop run that reports the host-path metrics."""

from __future__ import annotations

import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import engine_trace, spec  # noqa: E402
from test_chipbench import _run, tiny_root  # noqa: E402,F401

MS = 1_000_000          # ns


def _row(name, start_s, dur_ms, tick, parent="", count=0):
    s = int(round(start_s * 1e9))
    return (name, s, s + int(round(dur_ms * MS)), tick, parent, count)


ROW_BYTES = 1024 * 2048 * 3 * 4 + 4
ROWS = [
    # tick 0, before the window
    _row("engine.stage", 9.001, 50.0, 0, "engine.step", 16),
    _row("engine.step", 9.0, 60.0, 0, count=1),
    # ticks 1 and 2, in the untraced window [10, 20)
    _row("engine.stage", 10.001, 10.0, 1, "engine.step", 16),
    _row("engine.churn_flush", 10.011, 5.0, 1, "engine.step", 3),
    _row("engine.upload", 10.016, 20.0, 1, "engine.step", 16 * ROW_BYTES),
    _row("engine.dispatch", 10.036, 13.0, 1, "engine.step"),
    _row("engine.step", 10.0, 50.0, 1, count=1),
    _row("engine.result", 10.06, 30.0, 1),
    _row("engine.stage", 11.001, 12.0, 2, "engine.step", 8),
    _row("engine.upload", 11.013, 20.0, 2, "engine.step", 8 * ROW_BYTES),
    _row("engine.dispatch", 11.033, 6.0, 2, "engine.step"),
    _row("engine.step", 11.0, 40.0, 2, count=1),
    _row("engine.result", 11.05, 20.0, 2),
    # tick 3, traced
    _row("engine.stage", 20.501, 900.0, 3, "engine.step", 16),
    _row("engine.step", 20.5, 1000.0, 3, count=1),
]

SCOPES = {"fusion.1": "sensor", "fusion.2": "frontend", "cond.3": "encoder",
          "quant.4": "embed", "fusion.5": "encoder", "fusion.6": "policy",
          "copy.7": "unscoped"}


def _snapshot(rows=ROWS, dropped=0):
    cols = list(zip(*rows))
    return {"spans": {"name": np.asarray(cols[0]),
                      "start_ns": np.asarray(cols[1], np.int64),
                      "end_ns": np.asarray(cols[2], np.int64),
                      "tick": np.asarray(cols[3], np.int64),
                      "parent": np.asarray(cols[4]),
                      "count": np.asarray(cols[5], np.int64)},
            "written": len(rows) + dropped, "dropped": dropped,
            "counters": {}, "scopes": {"jit_counted": dict(SCOPES)}}


def _trace():
    """Two step executions wholly in the window, one that ends past it,
    and a scatter whose op shares a name with a step op. In the first
    execution ``cond.3`` encloses the embed and an encoder fusion."""
    ops = [
        (1.0, 1.1, "fusion.1"), (1.1, 1.2, "fusion.2"),
        (1.2, 1.45, "cond.3"), (1.22, 1.3, "quant.4"), (1.3, 1.4, "fusion.5"),
        (1.45, 1.5, "fusion.6"),
        (1.6, 1.7, "fusion.1"),                        # the scatter's
        (2.0, 2.1, "fusion.1"), (2.1, 2.15, "fusion.2"),
        (2.15, 2.35, "cond.3"), (2.2, 2.3, "fusion.5"),
        (2.35, 2.4, "copy.7"),
        (2.9, 3.1, "fusion.1"),                        # past the window
    ]
    modules = [(1.0, 1.5, "jit_counted"), (1.6, 1.7, "jit_scatter"),
               (2.0, 2.4, "jit_counted"), (2.9, 3.2, "jit_counted")]
    return {"ops": [ops], "modules": [modules],
            "spans": [("trace_window", 0.5, 3.0)]}


@pytest.fixture
def filled(monkeypatch):
    snap = _snapshot()
    monkeypatch.setattr(engine_trace, "snapshot", lambda ctx: snap)
    return snap


def _ctx(trace=None, **kw):
    return types.SimpleNamespace(t0=10.0, t_cut=20.0, trace=trace, **kw)


HOST = {"stage_ms.sat": 11.0, "upload_ms.sat": 20.0,
        "h2d_bytes_per_frame.sat": float(ROW_BYTES),
        "result_wait_ms.live": 25.0, "churn_flush_ms.live": 5.0}
DEVICE = {"sensor_device_ms.sat": 100.0, "frontend_device_ms.sat": 75.0,
          # first run: cond's own 0.07 s, embed 0.08, fusion 0.1; second:
          # cond's own 0.1, fusion 0.1
          "encoder_device_ms.sat": 225.0}


@pytest.mark.parametrize("metric", sorted(HOST))
def test_host_readers(filled, metric):
    read = spec.reader(metric)
    assert read(_ctx()) == pytest.approx(HOST[metric])


@pytest.mark.parametrize("metric", sorted(HOST))
def test_host_readers_refuse_a_ring_that_lost_the_window(monkeypatch,
                                                         metric):
    read = spec.reader(metric)
    # rows were lost, and the oldest kept ended after the window opened
    monkeypatch.setattr(engine_trace, "snapshot",
                        lambda ctx: _snapshot(ROWS[2:], dropped=2))
    assert read(_ctx()) is None
    # rows were lost, all of them before the window
    monkeypatch.setattr(engine_trace, "snapshot",
                        lambda ctx: _snapshot(ROWS[1:], dropped=1))
    assert read(_ctx()) == pytest.approx(HOST[metric])


@pytest.mark.parametrize("metric", sorted(DEVICE))
def test_device_readers_count_self_time_of_the_step(filled, metric):
    read = spec.reader(metric)
    assert read(_ctx(_trace())) == pytest.approx(DEVICE[metric])
    per = engine_trace.scope_device_s(_ctx(_trace()))
    assert per["policy"] == pytest.approx(0.025)
    assert per["unscoped"] == pytest.approx(0.025)
    # each instant once: the scopes sum to the busy time of the runs
    assert sum(per.values()) == pytest.approx((0.5 + 0.4) / 2)


@pytest.mark.parametrize("metric", sorted(DEVICE))
def test_device_readers_without_device_ops(filled, metric):
    read = spec.reader(metric)
    assert read(_ctx()) is None
    assert read(_ctx({"ops": [], "modules": [],
                      "spans": [("trace_window", 0.5, 3.0)]})) is None


@pytest.mark.parametrize("metric", sorted(HOST) + sorted(DEVICE))
def test_readers_without_telemetry(monkeypatch, metric):
    """A program that has no telemetry (an older commit) gives each
    reader nothing to read."""
    monkeypatch.setattr(engine_trace, "snapshot", lambda ctx: None)
    assert spec.reader(metric)(_ctx(_trace())) is None


def test_self_times_nested_and_overlapping():
    ops = [(0.0, 10.0, "while"), (1.0, 4.0, "a"), (2.0, 3.0, "b"),
           (5.0, 8.0, "c"), (7.0, 9.0, "d"), (5.0, 6.0, "e")]
    t = engine_trace.self_times(ops)
    assert t.tolist() == pytest.approx([3.0, 2.0, 1.0, 1.0, 2.0, 1.0])
    assert t.sum() == pytest.approx(10.0)


OFFSET = 1000.0
CALLS = (10.0, 11.3, 12.1, 13.7)        # ticks' step calls, host clock


def _mapped(step_end_late_s=0.0):
    """Four ticks on the host clock; the last two traced, their
    ``stage_dispatch`` and ``fetch`` spans on a clock ``OFFSET`` s ahead;
    each tick's engine spans inside its step call and fetch."""
    ticks = [(c - 1e-3, c, c + 0.05, c + 0.09, 4) for c in CALLS]
    rows = []
    for i, c in enumerate(CALLS):
        end = 50.0 - 0.01 + (step_end_late_s * 1e3 if i == 3 else 0.0)
        rows += [_row("engine.upload", c + 0.01, 20.0, i, "engine.step"),
                 _row("engine.step", c + 5e-6, end, i, count=1),
                 _row("engine.result", c + 0.052, 30.0, i)]
    spans = [("trace_window", OFFSET + 11.5, OFFSET + 14.0)]
    for c in CALLS[2:]:
        c += OFFSET
        spans += [("stage_dispatch", c + 2e-6, c + 0.05 - 1e-6),
                  ("fetch", c + 0.05, c + 0.09)]
    # the device runs inside tick 2's fetch and as tick 3's step opens
    c2, c3 = OFFSET + CALLS[2], OFFSET + CALLS[3]
    ops = [(c2 + 0.06, c2 + 0.07, "fusion.1"), (c3, c3 + 0.005, "fusion.1")]
    trace = {"ops": [ops], "modules": [[]], "spans": spans}
    ctx = types.SimpleNamespace(t0=10.0, t_cut=11.5, trace=trace,
                                rec=types.SimpleNamespace(ticks=ticks))
    return ctx, _snapshot(rows)


def test_spans_map_onto_the_trace_clock():
    ctx, snap = _mapped()
    off = engine_trace.to_trace_clock(ctx, snap)
    assert sorted(off) == [2, 3]
    assert all(v == pytest.approx(OFFSET + 2e-6) for v in off.values())
    att = engine_trace.idle_attribution(ctx, snap)
    idle = att["idle_s"]
    assert att["window_s"] == pytest.approx(2.5)
    assert sum(idle.values()) == pytest.approx(2.5 - 0.015)
    # the device idles through both uploads; it runs 10 ms of tick 2's
    # 30 ms result wait, which the fetch encloses by 10 ms in each tick
    assert idle["engine.upload"] == pytest.approx(0.04)
    assert idle["engine.result"] == pytest.approx(0.05)
    assert idle["fetch"] == pytest.approx(0.02)
    assert att["traced_ticks"] == 2
    assert att["traced_ms"]["engine.upload"] == pytest.approx(20.0)
    assert att["untraced_ms"]["engine.upload"] == pytest.approx(20.0)


def test_mapping_refuses_a_step_outside_its_stage_dispatch():
    ctx, snap = _mapped(step_end_late_s=100e-6)
    assert engine_trace.to_trace_clock(ctx, snap) is None
    assert engine_trace.idle_attribution(ctx, snap) is None
    ctx, snap = _mapped(step_end_late_s=20e-6)       # within 50 us
    assert engine_trace.to_trace_clock(ctx, snap) is not None


def test_tiny_traced_closed_run_reports_engine_spans(tiny_root):  # noqa: F811
    res = _run(tiny_root, "tiny.closed", trace_on=1)
    assert res["correct"], res["check"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # a tiny frame is 64 x 96 RGB float32, plus its int32 slot id
    assert m["h2d_bytes_per_frame.sat"] == 64 * 96 * 3 * 4 + 4
    assert 0 < m["stage_ms.sat"] and 0 < m["upload_ms.sat"]
    assert m["stage_ms.sat"] + m["upload_ms.sat"] < m["host_dispatch_ms.sat"]
    # no device plane on the CPU
    assert not {"sensor_device_ms.sat", "frontend_device_ms.sat",
                "encoder_device_ms.sat"} & set(m)
