"""The program's own spans (``repro.core.tracing``) and the scheduler's
occupancy counters (``AREngine.sched_stats``).

A prefill -> decode graph served under a CPU profiler session records
every span under its documented name, nested as documented, each stage's
spans on one host line; the counters are checked on hand-built
schedules whose page reservations and waits are worked out by hand.
"""
import glob
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from repro.configs.pipelines import build_pd_disaggregated
from repro.core.config import ServeConfig
from repro.core.orchestrator import Orchestrator
from repro.core.request import Request
from repro.engine import scheduler as sched_mod
from repro.engine.kv_cache import PagedKVConfig
from repro.engine.sampling import SamplingParams
from repro.engine.scheduler import Scheduler

STEP_PARTS = ("step", "schedule", "cow", "inject_kv", "prefill",
              "first_token", "decode_inputs", "decode", "hidden_to_host",
              "sample", "emit", "extract_kv", "admit")


def _program_spans(log_dir):
    """[(name, start_ns, end_ns, host line)] of the omni.* spans in the
    session's trace; a line is keyed by its index (names repeat)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("omni."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, i))
    return out


@pytest.fixture(scope="module")
def served_spans(tmp_path_factory):
    """One profiled serve: a prompt, then the same prompt again (a
    whole-prompt prefix hit, so the prefill stage copies a page)."""
    graph, engines, _ = build_pd_disaggregated(max_batch=2, max_new=4,
                                               prefix_cache=True)
    orch = Orchestrator(graph, engines,
                        config=ServeConfig(backend="threaded"))
    orch.start()
    prompt = np.arange(32, dtype=np.int32) % 500
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for _ in range(2):
            orch.submit(Request(inputs={"tokens": prompt}))
            done = orch.completions.get(timeout=300)
            assert done.failed is None
            assert len(done.outputs["decode"][0]["tokens"]) == 4
    finally:
        jax.profiler.stop_trace()
        orch.shutdown(drain=False)
    return _program_spans(log_dir)


def test_every_documented_span_is_recorded(served_spans):
    names = {n for n, *_ in served_spans}
    want = {f"omni.prefill.{p}" for p in
            ("step", "schedule", "cow", "prefill", "first_token", "emit",
             "extract_kv", "admit")}
    want |= {f"omni.decode.{p}" for p in
             ("step", "schedule", "inject_kv", "decode_inputs", "decode",
              "hidden_to_host", "sample", "emit", "admit")}
    want |= {"omni.conn.send", "omni.conn.recv"}
    assert want <= names
    assert names <= ({f"omni.{s}.{p}" for s in ("prefill", "decode")
                      for p in STEP_PARTS}
                     | {"omni.conn.send", "omni.conn.recv"})


def test_each_stage_runs_on_one_host_line(served_spans):
    line = {}
    for n, _, _, ln in served_spans:
        line.setdefault(n.split(".")[1], set()).add(ln)
    assert len(line["prefill"]) == 1 and len(line["decode"]) == 1
    assert line["prefill"] != line["decode"]
    # the router thread publishes, the decode worker receives
    assert line["conn"] - line["decode"]
    recv_line = {ln for n, _, _, ln in served_spans if n == "omni.conn.recv"}
    assert recv_line == line["decode"]


def _inside(spans, name, parent):
    """Every ``name`` span lies inside a ``parent`` span on its line."""
    outer = [(s, e, ln) for n, s, e, ln in spans if n == parent]
    inner = [(s, e, ln) for n, s, e, ln in spans if n == name]
    return inner and all(any(ps <= s and e <= pe and pl == ln
                             for ps, pe, pl in outer)
                         for s, e, ln in inner)


@pytest.mark.parametrize("name,parent", [
    ("omni.prefill.extract_kv", "omni.prefill.emit"),
    ("omni.prefill.emit", "omni.prefill.step"),
    ("omni.prefill.cow", "omni.prefill.step"),
    ("omni.prefill.first_token", "omni.prefill.step"),
    ("omni.decode.inject_kv", "omni.decode.step"),
    ("omni.decode.hidden_to_host", "omni.decode.step"),
    ("omni.decode.sample", "omni.decode.step"),
    ("omni.conn.recv", "omni.decode.admit"),
])
def test_spans_nest_as_documented(served_spans, name, parent):
    assert _inside(served_spans, name, parent)


def test_step_leaves_do_not_overlap(served_spans):
    """Within one step the leaf spans follow each other: one leaf at a
    time on the stage's line (``extract_kv`` nests in ``emit``)."""
    for stage in ("prefill", "decode"):
        leaves = sorted((s, e) for n, s, e, _ in served_spans
                        if n.startswith(f"omni.{stage}.")
                        and n.split(".")[2] not in ("step", "emit",
                                                    "admit"))
        assert all(a[1] <= b[0] for a, b in zip(leaves, leaves[1:]))


class _Clock:
    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


def test_sched_stats_on_a_hand_built_schedule(monkeypatch):
    """8 pages of 4 tokens, admission reserving prompt + max_new: A takes
    2 pages, B 4, and C (4) waits until A releases its 2."""
    clock = _Clock()
    monkeypatch.setattr(sched_mod, "time", SimpleNamespace(
        perf_counter=clock.perf_counter))
    kv = PagedKVConfig(num_pages=8, page_size=4, max_pages_per_seq=4)
    s = Scheduler(kv, max_batch=4)
    s.add(1, 6, SamplingParams(max_new_tokens=2))       # 2 pages
    s.add(2, 10, SamplingParams(max_new_tokens=6))      # 4 pages
    s.add(3, 8, SamplingParams(max_new_tokens=8))       # 4 pages
    clock.now = 1.0
    plan = s.schedule()
    assert plan.admitted == [1, 2]                      # C held back
    assert s.sched_stats == {"steps": 1, "admitted": 2,
                             "reserved_page_steps": 6, "used_page_steps": 0}
    s.note_prefill(1, 6)
    s.note_prefill(2, 10)
    clock.now = 2.0
    assert s.schedule().admitted == []                  # 2 pages free
    # reserved 2 + 4, used ceil(6/4) + ceil(10/4)
    assert s.sched_stats["reserved_page_steps"] == 12
    assert s.sched_stats["used_page_steps"] == 5
    s.release(1)
    clock.now = 3.5
    assert s.schedule().admitted == [3]
    assert s.sched_stats == {"steps": 3, "admitted": 3,
                             "reserved_page_steps": 12 + 4 + 4,
                             "used_page_steps": 5 + 3 + 0}
    assert list(s.admission_waits) == [(1, 1.0), (2, 1.0), (3, 3.5)]


def test_prefilled_requests_wait_from_enqueue(monkeypatch):
    """A KV-seeded request (PD decode) waits from ``add_prefilled``; its
    written prompt counts as used from its first step."""
    clock = _Clock()
    monkeypatch.setattr(sched_mod, "time", SimpleNamespace(
        perf_counter=clock.perf_counter))
    kv = PagedKVConfig(num_pages=16, page_size=4, max_pages_per_seq=8)
    s = Scheduler(kv, max_batch=2)
    clock.now = 5.0
    s.add_prefilled(7, 9, SamplingParams(max_new_tokens=7))   # 4 pages
    clock.now = 5.25
    s.schedule()
    assert list(s.admission_waits) == [(7, 0.25)]
    assert s.sched_stats["reserved_page_steps"] == 4
    assert s.sched_stats["used_page_steps"] == 3               # ceil(9/4)


def test_engine_exposes_sched_stats():
    _, engines, _ = build_pd_disaggregated(max_batch=2, max_new=3)
    eng = engines["decode"]             # prefills its own prompts here
    for rid, n in ((10, 20), (11, 5)):
        eng.enqueue(rid, {"tokens": np.arange(n, dtype=np.int32)},
                    SamplingParams(), {})
    while eng.has_work:
        eng.step()
    st = eng.sched_stats
    assert [r for r, _ in st["admission_waits"]] == [10, 11]
    assert all(w >= 0 for _, w in st["admission_waits"])
    assert st["admitted"] == 2 and st["steps"] >= 1
    assert 0 < st["used_page_steps"] <= st["reserved_page_steps"]
    st["admission_waits"].clear()                  # a copy, not the deque
    assert len(eng.sched_stats["admission_waits"]) == 2
