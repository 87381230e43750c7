"""The attribution of a traced window to the program's instruments: the
``serve.*`` spans and named scopes on the fixture of ``test_trace_reduce``,
the readers on a small record, and the tool's window on the tiny CPU cell."""
from __future__ import annotations

import os
import time
import types

import pytest

from bench import attribute, harness, trace_reduce
from bench.tests import common
from bench.tests.test_trace_reduce import ev, line, plane, trace


def sev(name, start_ms, dur_ms, path=None):
    """An op event whose stats carry its metadata path, as ``tf_op``."""
    e = ev(name, start_ms, dur_ms)
    e.stats = [("tf_op", path)] if path else []
    return e


def with_serve(planes):
    """The fixture, its host plane given ``serve.*`` spans inside the
    ``bench.*`` ones."""
    host, rest = planes[0], planes[1:]
    spans = [ev("serve.step", 10.5, 29), ev("serve.dispatch.decode", 12, 1.5),
             ev("serve.fetch", 36, 2), ev("serve.step", 50, 19),
             ev("serve.schedule", 43, 4)]
    events = host.lines[0].events + spans
    return [plane(host.name, [line(host.lines[0].name, events)])] + rest


def scoped(planes):
    """Device 0's decode run as a ``while`` around its body ops, with paths;
    the prefill run's op has no stats."""
    body = "jit(decode_fn)/jit(main)/while/body"
    ops = [sev("while.1", 15, 18, "jit(decode_fn)/jit(main)/while"),
           sev("fusion.a", 16, 4, f"{body}/kv_read/gather"),
           sev("fusion.b", 20, 4, f"{body}/kv_write/scatter"),
           sev("fusion.c", 25, 5, f"{body}/mlp/dot_general"),
           sev("copy.d", 33, 2), ev("dot.3", 55, 10), ev("fusion.1", 0, 5)]
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_decode_fn(7)", 15, 20),
                             ev("jit_prefill_fn(8)", 55, 10)]),
        line("XLA Ops", ops)])
    return [planes[0], dev]


def test_serve_spans_leave_the_reduction_as_it_was():
    base = trace_reduce.reduce_planes(trace())
    assert trace_reduce.reduce_planes(with_serve(trace())) == base


def test_idle_gaps_named_by_innermost_serve_span():
    r = attribute.reduce_serve(with_serve(trace()))
    gaps = {name: round(s, 6) for name, s in r["idle_gaps"]}
    # 10-15 (midpoint 12.5: the decode dispatch inside serve.step inside
    # bench.engine_step), 35-55 (midpoint 45: scheduling outside any step),
    # 65-110 (87.5: the loop's wait)
    assert gaps == {"serve.dispatch.decode": 0.005, "serve.schedule": 0.020,
                    "bench.wait": 0.045}
    names = [s[0] for s in r["spans"]]
    assert names.count("serve.step") == 2 and "bench.engine_step" in names
    assert "bench.window" not in names
    # every idle millisecond of the window, not only the longest gaps:
    # 10-15: step span 10-10.5, serve.step 10.5-12 and 14-15, dispatch
    # 12-13.5, bench.call.decode 13.5-14; 35-55: serve.step 35-36, 38-39.5
    # and 50-55, fetch 36-38, step span 39.5-40, no span 40-43 and 47-50,
    # schedule 43-47; 65-110: serve.step 65-69, step span 69-70, wait
    # 80-105, no span 70-80 and 105-110
    idle = {k: round(v * 1e3, 6) for k, v in r["idle_by_span"].items()}
    assert idle == {"bench.engine_step": 0.5 + 0.5 + 1.0,
                    "serve.step": 1.5 + 1.0 + 1.0 + 1.5 + 5.0 + 4.0,
                    "serve.dispatch.decode": 1.5, "bench.call.decode": 0.5,
                    "serve.fetch": 2.0, "serve.schedule": 4.0,
                    "no span": 3.0 + 3.0 + 10.0 + 5.0, "bench.wait": 25.0}
    assert sum(idle.values()) == pytest.approx(70.0)


def test_scopes_count_a_while_body_once():
    planes = with_serve(scoped(trace()))
    base = trace_reduce.reduce_planes(planes)
    sc = attribute.reduce_serve(planes)["scopes"]
    dec = sc["jit_decode_fn"]
    # the while's 18 ms less its body's 13, and the copy with no stats
    assert dec == {"kv_read": pytest.approx(0.004),
                   "kv_write": pytest.approx(0.004),
                   "mlp": pytest.approx(0.005),
                   "(unscoped)": pytest.approx(0.005 + 0.002)}
    assert sum(dec.values()) <= sum(base["modules"]["jit_decode_fn"]) + 1e-12
    assert sc["jit_prefill_fn"] == {"(unscoped)": pytest.approx(0.010)}


def test_scopes_count_the_runs_that_modules_count():
    # a decode run from before the window (5-12) is not one of the
    # module runs; one that starts inside and ends after it (105-115) is,
    # whole
    planes = trace()
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_decode_fn(7)", 5, 7),
                             ev("jit_decode_fn(7)", 15, 20),
                             ev("jit_decode_fn(7)", 105, 10)]),
        line("XLA Ops", [sev("fusion.a", 5, 7, "jit(decode_fn)/kv_read/g"),
                         sev("fusion.b", 15, 20, "jit(decode_fn)/mlp/d"),
                         sev("fusion.c", 105, 10, "jit(decode_fn)/mlp/d")])])
    planes = [planes[0], dev]
    base = trace_reduce.reduce_planes(planes)
    sc = attribute.reduce_serve(planes)["scopes"]
    assert sc == {"jit_decode_fn": {"mlp": pytest.approx(0.030)}}
    assert sum(base["modules"]["jit_decode_fn"]) == pytest.approx(0.030)


def test_scopes_from_each_programs_own_hlo():
    # one instruction name in both programs, a different op in each
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_decode_fn(7)", 15, 20),
                             ev("jit_prefill_fn(8)", 55, 10)]),
        line("XLA Ops", [ev("%fusion.7 = bf16[8]{0} fusion(%a)", 15, 20),
                         ev("%fusion.7 = f32[4]{0} fusion(%b)", 55, 10)])])
    hlo = {"jit_decode_fn": {"fusion.7": "jit(decode_fn)/while/body/kv_read"},
           "jit_prefill_fn": {"fusion.7": "jit(prefill_fn)/while/body/mlp"}}
    sc = attribute.reduce_serve([trace()[0], dev], hlo)["scopes"]
    assert sc == {"jit_decode_fn": {"kv_read": pytest.approx(0.020)},
                  "jit_prefill_fn": {"mlp": pytest.approx(0.010)}}


def test_scopes_of_the_fixture_without_stats():
    sc = attribute.reduce_serve(trace())["scopes"]
    # fusion.1 15-27 and fusion.2 25-35 overlap: 20 ms, each interval once
    assert sc["jit_decode_fn"] == {"(unscoped)": pytest.approx(0.020)}
    assert sc["jit_prefill_fn"] == {"(unscoped)": pytest.approx(0.010)}


def test_paths_from_hlo_text():
    text = '''HloModule jit_decode_fn, is_scheduled=true
  %fusion.192 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(decode_fn)/jit(main)/while/body/kv_read/gather" stack_frame_id=2}
  ROOT %copy.55 = bf16[8]{0} copy(%fusion.192)
  %while.6 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(decode_fn)/jit(main)/while"}
'''
    assert attribute.hlo_module(text) == "jit_decode_fn"
    hlo = attribute.hlo_paths(text)
    assert hlo == {"fusion.192": "jit(decode_fn)/jit(main)/while/body/"
                                 "kv_read/gather",
                   "while.6": "jit(decode_fn)/jit(main)/while"}
    e = types.SimpleNamespace(name="fusion.192", stats=[("hlo_op", "x")])
    assert attribute.scope_of(attribute._path(e, hlo)) == "kv_read"
    # an event named by its HLO text
    e.name = "%fusion.192 = bf16[8]{0} fusion(%p), kind=kLoop"
    assert attribute.scope_of(attribute._path(e, hlo)) == "kv_read"
    e.name = 'copy.9 = bf16[8] copy(%x), metadata={op_name="jit(f)/mlp/c"}'
    assert attribute.scope_of(attribute._path(e, {})) == "mlp"
    assert attribute.scope_of("jit(f)/attention/kv_read/x") == "kv_read"
    assert attribute.scope_of("jit(f)/while") == "(unscoped)"


def record():
    """A 10 s window: three requests and the engine's counters."""
    reqs = [dict(due=-2.0, slot=-1.0, admit=1.0),    # slot before the window
            dict(due=0.5, slot=1.5, admit=4.5),
            dict(due=3.0, slot=3.0, admit=None)]      # no chunk by the close
    tr = {"busy": [[0.1, 0.3], [1.0, 1.5]],
          "modules": {"jit_decode_fn": [0.02, 0.02, 0.02, 0.02]}}
    serve = {"spans": [("serve.step", 0.0, 0.4), ("serve.step", 1.0, 1.6),
                       ("bench.engine_step", 0.0, 0.5)],
             "scopes": {"jit_decode_fn": {"kv_read": 0.030, "kv_write": 0.002,
                                          "mlp": 0.04}}}
    counters = {"open": {"steps": 100, "kv_pages_live": 50,
                         "kv_pages_read": 400, "prefill_backlog_chunks": 900},
                "close": {"steps": 110, "kv_pages_live": 350,
                          "kv_pages_read": 2400,
                          "prefill_backlog_chunks": 1150}}
    return dict(window_s=10.0, requests=reqs, trace=tr, serve=serve,
                counters=counters)


def test_readers_on_a_small_record():
    rec = record()
    r = {k: f(rec) for k, f in attribute.READERS.items()}
    assert r["slot_wait_ms_mean"] == pytest.approx((1.0 + 0.0) / 2 * 1e3)
    assert r["prefill_wait_ms_mean"] == pytest.approx((2.0 + 3.0) / 2 * 1e3)
    assert r["prefill_backlog_chunks.chat"] == pytest.approx(250 / 10)
    assert r["kv_live_share.chat"] == pytest.approx(100 * 300 / 2000)
    # 0.4 - 0.2 busy and 0.6 - 0.5 busy
    assert r["step_host_ms.chat"] == pytest.approx((0.2 + 0.1) / 2 * 1e3)
    assert r["decode_kv_ms.chat"] == pytest.approx(0.032 / 4 * 1e3)


def test_readers_read_nothing_without_trace_or_counters():
    rec = record()
    rec.update(trace=None, serve=None, counters=None)
    for k in ("prefill_backlog_chunks.chat", "kv_live_share.chat",
              "step_host_ms.chat", "decode_kv_ms.chat"):
        assert attribute.READERS[k](rec) is None, k
    rec["requests"] = [dict(due=0.0, admit=0.5)]    # a record with no slot
    assert attribute.slot_wait_ms_mean(rec) is None
    assert attribute.prefill_wait_ms_mean(rec) is None


def test_tool_on_the_tiny_cell(tmp_path):
    cell = harness.resolve(common.WORKLOAD, common.tiny_benchmark(),
                           common.DATA)
    keep = os.environ.get("BENCH_KEEP_TRACE")
    line = attribute.attribute(cell, common.CPU, 5, 3.0, str(tmp_path),
                               t_start=time.time())
    assert os.environ.get("BENCH_KEEP_TRACE") == keep
    m = line["metrics"]
    # no device plane on the CPU: the trace's readers read nothing; the
    # counters and request times do
    assert m["decode_kv_ms.chat"] is None and m["step_host_ms.chat"] is None
    assert line["scopes"] is None
    assert 0.0 < m["kv_live_share.chat"] <= 100.0
    assert m["prefill_backlog_chunks.chat"] >= 0.0
    c = line["counters"]
    assert c["close"]["steps"] > c["open"]["steps"]
    for name in ("jit_decode_fn", "jit_prefill_fn"):
        text = (tmp_path / f"{name}.hlo.txt").read_text()
        assert attribute.hlo_module(text) == name
        assert attribute.hlo_paths(text)
    # the traced loop admits before each step, outside the engine's step:
    # every request that got a first chunk got its slot time there too
    reqs = line["requests"]
    assert any(admit is not None for _, _, admit in reqs)
    for due, slot, admit in reqs:
        if admit is not None:
            assert slot is not None and due <= slot <= admit
