"""The serving engine's own instruments (smoke arch, host CPU).

* host spans: every phase of a step is a ``serve.*`` span on the
  profiler's clock, nested inside ``serve.step``;
* counters: KV pages live against pages read, and prefill chunks owed,
  equal what the scheduler's decisions give by hand, through a preemption;
* request times are ordered: submit <= slot <= first chunk <= first token;
* named scopes are metadata only: the programs serve the tokens they
  served before the scopes were added.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import registry
from repro.models import api
from repro.serve import ServeEngine

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

PROMPTS = [list(range(1, 6)), list(range(20, 31)), [40, 41]]


@pytest.fixture(scope="module")
def setup():
    cfg = registry.smoke("qwen2-1.5b")
    # weights four times the init scale, so greedy decoding does not just
    # repeat the prompt's last token and the stored tokens tell programs apart
    params = jax.tree.map(lambda a: a * 4.0,
                          api.init_params(jax.random.key(0), cfg))
    return cfg, params


def engine(setup, **kw):
    cfg, params = setup
    kw = {"slots": 4, "max_len": 48, "page_size": 8, "prefill_chunk": 8,
          **kw}
    return ServeEngine(cfg, params, **kw)


def serve(eng, prompts=PROMPTS, max_new=(6, 5, 8), temps=(0.0, 0.7, 0.0),
          seeds=(0, 9, 0)):
    rs = [eng.submit(p, max_new_tokens=m, temperature=t, seed=s)
          for p, m, t, s in zip(prompts, max_new, temps, seeds)]
    eng.run()
    eng.assert_no_leaks()
    return rs


# served by the programs before they carried named scopes (paged == dense)
STORED = [[403, 215, 122, 324, 461, 359], [101, 334, 492, 329, 256],
          [270, 356, 428, 264, 182, 160, 194, 381]]


@pytest.mark.parametrize("backend", ["paged", "dense"])
def test_scoped_programs_serve_the_stored_tokens(setup, backend):
    rs = serve(engine(setup, backend=backend))
    assert [r.out_tokens for r in rs] == STORED


def _host_events(path):
    (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)) for ev in line.events
                        if ev.name.startswith("serve.")]
    return out


STEP_CHILDREN = {"serve.schedule", "serve.sync_cache", "serve.inputs",
                 "serve.dispatch.decode", "serve.dispatch.prefill",
                 "serve.fetch", "serve.accept", "serve.pool_check"}


def test_spans_nest_inside_serve_step(setup, tmp_path):
    eng = engine(setup)
    serve(eng)                          # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        rs = serve(eng)
    evs = _host_events(str(tmp_path))
    names = {e[0] for e in evs}
    assert STEP_CHILDREN | {"serve.step", "serve.submit"} <= names
    steps = [e for e in evs if e[0] == "serve.step"]
    assert len(steps) >= eng.metrics.steps // 2      # the traced run's steps
    for name, s, e, _ in evs:
        if name in STEP_CHILDREN:
            assert any(s0 <= s and e <= e0 for _, s0, e0, _ in steps), name
    # spans of one request carry its id
    rids = {r.rid for r in rs}
    assert {a["rid"] for n, *_, a in evs if n == "serve.submit"} == rids
    assert {a["rid"] for n, *_, a in evs
            if n == "serve.dispatch.prefill"} == rids


def test_counters_through_a_preemption(setup):
    # 2 slots, pages of 4 tokens, 4 usable pages, chunks of 4 tokens:
    #   step kind     request  owed chunks  live pages (read 2 x 4)
    #    1   prefill  A 0-4    A2 B1 = 3
    #    2   prefill  A 4-6    A1 B1 = 2    (A decodes: token 1)
    #    3   decode   A        B1    = 1    A 7 tokens: 2
    #    4   prefill  B 0-3    B1    = 1    (B: token 1)
    #    5   decode   A B      0            A 8: 2, B 4: 1 -> 3
    #    6   decode   B        0            A grows to 3 pages, B cannot
    #                                       grow: A preempted, requeued with
    #                                       8 tokens to re-prefill; B 5: 2
    #                                       (B: token 3, finished)
    #    7   prefill  A 0-4    A2    = 2
    #    8   prefill  A 4-8    A1    = 1
    #    9   decode   A        0            A 9: 3
    #   10   decode   A        0            A 10: 3 (A: token 5, finished)
    eng = engine(setup, slots=2, max_len=16, page_size=4, n_pages=5,
                 prefill_chunk=4)
    a, b = serve(eng, prompts=[list(range(1, 7)), [7, 8, 9]], max_new=(5, 3),
                 temps=(0.0, 0.0), seeds=(0, 0))
    m = eng.metrics
    assert (a.preemptions, b.preemptions, m.preemptions) == (1, 0, 1)
    assert (m.steps, m.decode_steps, m.prefill_chunks) == (10, 5, 5)
    assert m.prefill_backlog_chunks == 3 + 2 + 1 + 1 + 0 + 0 + 2 + 1 + 0 + 0
    assert m.kv_pages_live == 2 + 3 + 2 + 3 + 3
    assert m.kv_pages_read == 5 * 2 * 4
    assert eng.sched.queued_chunks == 0


def test_request_times_are_ordered(setup):
    eng = engine(setup, slots=2, max_len=16, page_size=4, n_pages=5,
                 prefill_chunk=4)
    rs = serve(eng, prompts=[list(range(1, 7)), [7, 8, 9], [3, 4]],
               max_new=(5, 3, 4), temps=(0.0,) * 3, seeds=(0,) * 3)
    assert sum(r.preemptions for r in rs) >= 1
    for r in rs:
        m = r.metrics
        assert m.submit_time <= m.slot_time <= m.admit_time \
            <= m.first_token_time


def test_slot_time_when_admitted_outside_step(setup):
    # a caller that admits before each step, as a traced benchmark loop
    # does, still stamps the slot time: the scheduler stamps it on its clock
    t = [100.0]
    eng = engine(setup, slots=2, max_len=16, page_size=4, n_pages=5,
                 prefill_chunk=4, clock=lambda: t[0])
    rs = [eng.submit(p, max_new_tokens=m) for p, m in
          zip([list(range(1, 7)), [7, 8, 9], [3, 4], [5]], (5, 3, 4, 2))]
    given = {r.rid: 100.0 for r in eng.sched.live()}   # admitted at submit
    while eng.sched.has_work():
        t[0] += 1.0
        for r in eng.sched.admit():
            given.setdefault(r.rid, t[0])     # a preempted one keeps its first
        eng.step()
    assert max(given.values()) > 100.0 and sum(r.preemptions for r in rs)
    assert {r.rid: r.metrics.slot_time for r in rs} == given
    for r in rs:
        m = r.metrics
        assert m.submit_time <= m.slot_time <= m.admit_time \
            <= m.first_token_time


@pytest.mark.parametrize("attn_read", ["gather", "kernel"])
def test_decode_program_scopes_its_kv_read(setup, attn_read):
    # the KV read carries its scope on either read path, so decode_kv_ms
    # keeps its meaning when the read path changes; on the kernel path the
    # kernel's pass over the pages is the read
    eng = engine(setup, attn_read=attn_read)
    s = eng.n_slots
    text = eng.steps.decode.lower(
        eng.params, np.zeros(s, np.int32), np.zeros(s, bool),
        np.zeros(s, np.float32), np.zeros((s, 2), np.uint32),
        eng.cache).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    scopes = {sc for sc in ("kv_read", "kv_write", "attention")
              if any(f"/{sc}/" in p for p in paths)}
    want = {"kv_read", "kv_write"}
    assert scopes == (want | {"attention"} if attn_read == "gather"
                      else want)


def test_running_means_and_queue_chunks(setup):
    eng = engine(setup, slots=1)
    rs = [eng.submit(p, max_new_tokens=2) for p in PROMPTS]
    # one slot: the second and third wait in the queue, 2 and 1 chunks
    assert eng.sched.queued_chunks == 2 + 1
    eng.cancel(rs[2])
    assert eng.sched.queued_chunks == 2
    eng.run()
    m = eng.metrics
    assert eng.sched.queued_chunks == 0
    # sampled after each step: prefill, decode (finished, slot and page
    # freed), prefill, prefill, decode (finished); 6 usable pages
    assert m.steps == 5
    assert m.occupancy_mean == pytest.approx((1 + 0 + 1 + 1 + 0) / 5)
    assert m.pool_util_mean == pytest.approx((1 + 0 + 1 + 2 + 0) / 6 / 5)
    assert m.summary()["occupancy_mean"] == 0.6
