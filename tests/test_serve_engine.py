"""Continuous-batching engine integration tests (smoke arch, host CPU).

The load-bearing claims, each pinned here:

* **paged == dense, bitwise** — both backends run the same compute with
  the same shapes; stale page bytes sit behind exactly-zero softmax
  weights, so per-token logits match bit for bit (not just allclose).
* **chunked prefill is exact** — any chunking of a prompt yields the same
  sampled stream (chunk k attends to earlier chunks through the cache).
* **preemption is transparent** — a page-pressure run (evict → requeue →
  re-prefill) emits token streams identical to an unpressured run, and
  pool accounting stays exact throughout.
* **continuous batching** — requests admitted mid-run join live decode
  without draining the batch; FIFO completion order holds for same-shape
  requests; sampling is reproducible across batch compositions (keys
  derive from request seed + token index, not slot or step).
"""
import numpy as np
import pytest

import jax

from repro.configs import registry
from repro.models import api
from repro.serve import Backpressure, ServeEngine
from repro.serve.scheduler import RequestState

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def setup():
    cfg = registry.smoke("qwen2-1.5b")
    params = api.init_params(jax.random.key(0), cfg)
    return cfg, params


def mk_engine(setup, **kw):
    cfg, params = setup
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(cfg, params, **kw)


PROMPTS = [list(range(1, 6)), list(range(20, 31)), [40, 41]]


def run_requests(eng, prompts=PROMPTS, max_new=(6, 5, 8),
                 temps=(0.0, 0.7, 0.0), seeds=(0, 9, 0)):
    rs = [eng.submit(p, max_new_tokens=m, temperature=t, seed=s)
          for p, m, t, s in zip(prompts, max_new, temps, seeds)]
    eng.run()
    eng.assert_no_leaks()
    return rs


def test_serve_supported_guard(setup):
    cfg, _ = setup
    ok, why = api.serve_supported(cfg)
    assert ok, why


def test_basic_generation_and_metrics(setup):
    eng = mk_engine(setup)
    rs = run_requests(eng)
    for r, m in zip(rs, (6, 5, 8)):
        assert r.state is RequestState.FINISHED
        assert len(r.out_tokens) == m
        assert r.done_reason() == "length"
        assert r.metrics.ttft is not None and r.metrics.ttft >= 0
    assert eng.metrics.tokens_sampled == 6 + 5 + 8
    assert eng.metrics.prefill_chunks >= 3
    assert 0 < eng.metrics.occupancy_mean <= 1.0


def test_paged_matches_dense_bitwise(setup):
    streams, logs = [], []
    for backend in ("paged", "dense"):
        eng = mk_engine(setup, backend=backend, capture_logits=True)
        rs = run_requests(eng)
        streams.append([r.out_tokens for r in rs])
        logs.append([np.stack(r.logits_log) for r in rs])
    assert streams[0] == streams[1]
    for la, lb in zip(*logs):
        assert np.array_equal(la, lb), np.abs(la - lb).max()


def test_chunked_prefill_is_exact(setup):
    streams = []
    for chunk in (4, 16):
        eng = mk_engine(setup, prefill_chunk=chunk)
        streams.append([r.out_tokens for r in run_requests(eng)])
    assert streams[0] == streams[1]


def test_preemption_transparent_and_leak_free(setup):
    prompts = [list(range(1, 9)), list(range(20, 26)), list(range(40, 44))]
    kw = dict(prompts=prompts, max_new=(10, 10, 12),
              temps=(0.0, 0.6, 0.9), seeds=(0, 3, 7))
    ref = run_requests(mk_engine(setup, page_size=4, prefill_chunk=4), **kw)
    eng = mk_engine(setup, page_size=4, prefill_chunk=4, n_pages=10)
    rs = run_requests(eng, **kw)
    assert eng.sched.n_preemptions > 0
    assert sum(r.preemptions for r in rs) > 0
    for ra, rb in zip(ref, rs):
        assert rb.state is RequestState.FINISHED
        assert ra.out_tokens == rb.out_tokens
    assert eng.pool.used_pages == 0


def test_mid_batch_admission(setup):
    # more requests than slots: late requests must join as early ones
    # finish, without the engine ever draining to empty between them
    eng = mk_engine(setup, slots=2)
    rs = [eng.submit([i + 1, i + 2], max_new_tokens=4) for i in range(5)]
    occupied = []
    while eng.sched.has_work():
        eng.step()
        occupied.append(eng.sched.occupancy())
    eng.assert_no_leaks()
    assert all(r.state is RequestState.FINISHED for r in rs)
    # the batch never drained while work remained queued
    assert 0 not in occupied[:-1]
    assert eng.metrics.peak_in_flight == 5


def test_fifo_completion_order(setup):
    eng = mk_engine(setup, slots=2)
    rs = [eng.submit([i + 1], max_new_tokens=3) for i in range(6)]
    eng.run()
    eng.assert_no_leaks()
    finished = [r.rid for r in eng.finished]
    assert finished == sorted(finished)               # arrival order


def test_sampling_reproducible_across_batch_composition(setup):
    # the same (prompt, seed) request yields the same stream whether it
    # runs alone or packed with others in different slots
    eng = mk_engine(setup)
    alone = eng.submit([5, 6, 7], temperature=0.8, seed=11, max_new_tokens=6)
    eng.run()
    eng.assert_no_leaks()
    eng2 = mk_engine(setup)
    eng2.submit([1, 2], max_new_tokens=8)
    eng2.submit([3, 4, 5, 6], max_new_tokens=8, temperature=0.5, seed=2)
    packed = eng2.submit([5, 6, 7], temperature=0.8, seed=11, max_new_tokens=6)
    eng2.run()
    eng2.assert_no_leaks()
    assert alone.out_tokens == packed.out_tokens


def test_stop_token_ends_stream(setup):
    eng = mk_engine(setup)
    probe = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run()
    eng2 = mk_engine(setup)
    r = eng2.submit([1, 2, 3], max_new_tokens=40, stop_token=probe.out_tokens[0])
    eng2.run()
    eng2.assert_no_leaks()
    assert r.out_tokens[-1] == probe.out_tokens[0]
    assert len(r.out_tokens) < 40
    assert r.done_reason() == "stop"


def test_streaming_callback_and_detokenize(setup):
    cfg, params = setup
    pieces = []
    eng = ServeEngine(cfg, params, slots=2, max_len=48, page_size=8,
                      prefill_chunk=8,
                      detokenize=lambda t: f"<{t}>")
    r = eng.submit([1, 2, 3], max_new_tokens=4,
                   stream_cb=lambda piece, req: pieces.append(piece))
    eng.run()
    eng.assert_no_leaks()
    assert pieces == [f"<{t}>" for t in r.out_tokens]


def test_timeout_cancels_request(setup):
    clock = {"t": 0.0}
    eng = mk_engine(setup, clock=lambda: clock["t"])
    slow = eng.submit([1, 2, 3], max_new_tokens=40, timeout=0.5)
    ok = eng.submit([4, 5], max_new_tokens=4)
    for _ in range(40):
        if not eng.sched.has_work():
            break
        eng.step()
        clock["t"] += 0.1
    assert slow.state is RequestState.CANCELLED
    assert slow.error == "timeout"
    assert ok.state is RequestState.FINISHED
    assert eng.metrics.timeouts == 1
    eng.assert_no_leaks()


def test_backpressure_and_capacity_failure(setup):
    eng = mk_engine(setup, max_queue=2, slots=1, max_len=16,
                    prefill_chunk=4, page_size=4)
    hopeless = eng.submit(list(range(1, 15)), max_new_tokens=10)  # 24 > 16
    assert hopeless.state is RequestState.FAILED
    eng.submit([1, 2], max_new_tokens=2)
    eng.submit([3, 4], max_new_tokens=2)
    eng.submit([5, 6], max_new_tokens=2)              # 1 running + 2 queued
    with pytest.raises(Backpressure):
        eng.submit([7, 8], max_new_tokens=2)
    eng.run()
    eng.assert_no_leaks()


def test_kernel_attention_read_close(setup):
    logs = []
    for attn_read in ("gather", "kernel"):
        eng = mk_engine(setup, slots=2, max_len=32, attn_read=attn_read,
                        capture_logits=True)
        r = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.run()
        eng.assert_no_leaks()
        logs.append(np.stack(r.logits_log))
    assert np.allclose(logs[0], logs[1], atol=5e-2), \
        np.abs(logs[0] - logs[1]).max()


def test_unsupported_arch_rejected(setup):
    cfg, params = setup
    import dataclasses
    bad = dataclasses.replace(cfg, kv_quant=True)
    ok, why = api.serve_supported(bad)
    assert not ok and "int8" in why
    with pytest.raises(ValueError):
        ServeEngine(bad, params, slots=2, max_len=32)


def test_engine_under_host_mesh(setup):
    # the engine's jitted steps accept sharding rules: activation
    # constraints installed, sharded over a (1,1) host mesh (no mesh context)
    cfg, params = setup
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import MeshRules

    mesh = make_host_mesh(1, 1)
    rules = MeshRules(mesh)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, page_size=8,
                      prefill_chunk=8, rules=rules)
    r = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.run()
    eng.assert_no_leaks()
    assert r.state is RequestState.FINISHED
    assert len(r.out_tokens) == 4


# ---------------------------------------------------------------------------
# chaos injection (shared fault layer, runtime/chaos.py)
# ---------------------------------------------------------------------------

def test_chaos_backpressure_rejects_deterministically(setup):
    from repro.runtime import chaos
    plan = chaos.ChaosPlan(3, "t", (chaos.ChaosRule(
        "serve.backpressure", "backpressure", rate=0.5),))
    rejected = [rid for rid in range(12)
                if plan.fire("serve.backpressure", str(rid)) is not None]
    assert rejected and len(rejected) < 12       # the plan partitions rids

    eng = mk_engine(setup, chaos=plan)
    got = []
    for rid in range(12):
        try:
            eng.submit([1, 2, 3], max_new_tokens=1)
        except Backpressure:
            got.append(rid)
    assert got == rejected                       # exactly the planned rids
    eng.run()
    eng.assert_no_leaks()
    # accepted requests still complete normally
    done = [r for r in eng.finished if r.state is RequestState.FINISHED]
    assert len(done) == 12 - len(rejected)


def test_chaos_step_delay_trips_straggler_watchdog(setup):
    from repro.runtime import chaos
    from repro.runtime.fault_tolerance import StragglerWatchdog
    plan = chaos.ChaosPlan(5, "t", (chaos.ChaosRule(
        "serve.step", "delay", rate=0.3, seconds=30.0),))
    eng = mk_engine(setup, chaos=plan,
                    watchdog=StragglerWatchdog(window=16, threshold=3.0,
                                               min_samples=4))
    run_requests(eng)
    assert eng.metrics.stragglers > 0            # injected delays flagged

    # same traffic, no chaos: a quiet run for comparison
    eng2 = mk_engine(setup, chaos=chaos.ChaosPlan(5, "off", ()),
                     watchdog=StragglerWatchdog(window=16, threshold=3.0,
                                                min_samples=4))
    rs = run_requests(eng2)
    assert all(r.state is RequestState.FINISHED for r in rs)


def test_chaos_off_by_default(setup, monkeypatch):
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    eng = mk_engine(setup)
    assert eng.chaos is None
