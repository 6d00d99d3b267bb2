"""The trace reduction, on a hand-built Perfetto trace of a few events."""
import pytest

from harness.reduce import merge, reduce_trace

DEV, HOST = 7, 1


def _meta():
    return [
        {"ph": "M", "pid": HOST, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": DEV, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": DEV, "tid": 1, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": DEV, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": HOST, "tid": 9, "name": "thread_name", "args": {"name": "python"}},
    ]


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur}


def _trace():
    ev = _meta() + [
        _x(HOST, 9, "window", 1000.0, 1000.0),              # window 1000..2000 us
        _x(HOST, 9, "serve_step", 1000.0, 400.0),
        _x(HOST, 9, "client", 1400.0, 100.0),
        _x(HOST, 9, "client", 1500.0, 300.0),
        _x(HOST, 9, "serve_step", 1800.0, 200.0),
        # programs
        _x(DEV, 2, "jit__fused_decode", 900.0, 300.0),      # clipped to 1000..1200
        _x(DEV, 2, "jit__mixed_dispatch", 1850.0, 100.0),
        # ops: two overlap, one outside the window
        _x(DEV, 1, "fusion.1", 900.0, 200.0),               # 1000..1100 inside
        _x(DEV, 1, "fusion.2", 1050.0, 150.0),              # 1050..1200
        _x(DEV, 1, "dot.3", 1850.0, 100.0),                 # 1850..1950
        _x(DEV, 1, "dot.3", 2500.0, 100.0),                 # outside
    ]
    return {"traceEvents": ev}


def test_merge_unions_overlaps():
    assert merge([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_union_and_idle_share():
    r = reduce_trace(_trace())
    assert r.window_s == pytest.approx(1e-3)
    assert r.busy_s == pytest.approx(300e-6)                # 1000..1200, 1850..1950
    assert r.idle_share == pytest.approx(0.7)
    assert r.devices == 1


def test_device_time_per_program_and_op():
    r = reduce_trace(_trace())
    assert r.programs["jit__fused_decode"] == (1, pytest.approx(200e-6))
    assert r.programs["jit__mixed_dispatch"] == (1, pytest.approx(100e-6))
    assert r.runs == [(1000.0, 1200.0), (1850.0, 1950.0)]
    assert r.ops["fusion.1"] == pytest.approx(100e-6)
    assert r.ops["dot.3"] == pytest.approx(100e-6)          # the outside run is dropped


def test_program_runs_are_attributed_to_the_step_they_ran_in():
    from types import SimpleNamespace as NS

    from harness import stats

    r = reduce_trace(_trace())
    # host clock: the window opens at 50.0 s, which is trace time 1000 us
    step = lambda t0, t1, kind: NS(t0=t0, t1=t1, stage=NS(kind=NS(value=kind)))  # noqa: E731
    steps = [step(50.0, 50.0004, "decode"), step(50.0008, 50.00099, "mixed")]
    run = NS(reduction=r, traced=(50.0, 50.001), traced_steps=lambda: steps)
    assert stats.stage_device_seconds(run, "decode") == pytest.approx(200e-6)
    assert stats.stage_device_seconds(run, "mixed") == pytest.approx(100e-6)


def test_top_ops_leave_out_enclosing_loops():
    t = _trace()
    t["traceEvents"].append(_x(DEV, 1, "while.7", 1000.0, 1000.0))
    names = [k for k, _ in reduce_trace(t).top_ops(10)]
    assert "while.7" not in names and names[0] in ("fusion.2", "fusion.1", "dot.3")


def test_idle_gaps_are_labelled_by_host_span():
    r = reduce_trace(_trace())
    # gaps: 1200..1850 (the client span 1500..1800 covers the most) and
    # 1950..2000 (serve_step)
    assert r.gaps[0] == ("client", pytest.approx(650e-6))
    assert r.gaps[1] == ("serve_step", pytest.approx(50e-6))


def test_programs_from_op_arguments_without_a_module_thread():
    t = _trace()
    t["traceEvents"] = [
        e for e in t["traceEvents"] if not (e.get("tid") == 2 and e["pid"] == DEV)
    ]
    for e in t["traceEvents"]:
        if e.get("ph") == "X" and e["pid"] == DEV:
            e["args"] = {"hlo_module": "jit__fused_decode"}
    r = reduce_trace(t)
    assert r.programs["jit__fused_decode"][1] == pytest.approx(350e-6)


def test_a_trace_without_window_or_device_is_refused():
    t = _trace()
    with pytest.raises(ValueError):
        reduce_trace({"traceEvents": [e for e in t["traceEvents"] if e.get("name") != "window"]})
    with pytest.raises(ValueError):
        reduce_trace({"traceEvents": [e for e in t["traceEvents"] if e["pid"] != DEV]})
