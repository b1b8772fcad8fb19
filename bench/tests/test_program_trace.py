"""The per-thread idle readings of ``bench/program_trace.py`` on
synthetic traces with hand-computed shares, its scheduler-counter
readings, ``load_spans`` keeping the program's spans per thread, a
smoke-width probe on the CPU, and every trace reader of the benchmark
reading the recorded v5e trace as before."""
import json
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import counts
from bench import program_trace as P
from bench import trace as T
from bench.peaks import peaks_for
from bench.spec import ROOT, load_module

DATA = Path(__file__).parent / "data"
DEV = "/device:TPU:0"


def reader(name):
    return load_module(ROOT / "bench" / "layer_metrics" / f"{name}.py")


@pytest.fixture
def three_threads():
    """Device busy [0, 1) and [3, 4) of a 6 s slice; idle [1, 3), [4, 6).
    decode thread: step [0.5, 5.5) holding decode_inputs [1.0, 1.5),
    inject_kv [2.5, 3.0), sample [4.0, 5.0); router: conn.send
    [1.2, 2.2); prefill thread: step [1.0, 2.8) > emit [1.8, 2.8) >
    extract_kv [2.0, 2.6)."""
    tr = T.Trace(modules={DEV: [("jit__decode_impl(1)", 0.0, 1.0),
                                ("jit__decode_impl(1)", 3.0, 4.0)]})
    spans = [
        ("omni.decode.step", 0.5, 5.5, "python#1"),
        ("omni.decode.decode_inputs", 1.0, 1.5, "python#1"),
        ("omni.decode.inject_kv", 2.5, 3.0, "python#1"),
        ("omni.decode.sample", 4.0, 5.0, "python#1"),
        ("omni.conn.send", 1.2, 2.2, "python#2"),
        ("omni.prefill.step", 1.0, 2.8, "python#3"),
        ("omni.prefill.emit", 1.8, 2.8, "python#3"),
        ("omni.prefill.extract_kv", 2.0, 2.6, "python#3"),
    ]
    return tr, P.idle_by_thread(tr, spans, (0.0, 6.0))


def test_shares_on_three_threads(three_threads):
    tr, segs = three_threads
    # KV hop: send [1.2, 2.2) U extract [2.0, 2.6) U inject [2.5, 3.0),
    # all idle: 1.8 s of 6
    assert P.kv_hop_idle_share(segs, (0.0, 6.0)) == \
        pytest.approx(100 * 1.8 / 6)
    # step host, no hop: [1.0, 1.2) and [4.0, 5.5): 1.7 s of 6
    assert P.step_host_idle_share(segs, (0.0, 6.0), "decode") == \
        pytest.approx(100 * 1.7 / 6)
    # [5.5, 6.0) is idle with no thread in a span: neither reading's
    assert T.idle_share(tr, (0.0, 6.0)) == pytest.approx(4 / 6)


def test_idle_by_thread_reads_each_thread(three_threads):
    _, segs = three_threads
    assert sum(b - a for a, b, _ in segs) == pytest.approx(4.0)
    at = {a: open_ for a, _, open_ in segs}
    assert at[2.0] == {
        "python#1": ("omni.decode.step",),
        "python#2": ("omni.conn.send",),
        "python#3": ("omni.prefill.step", "omni.prefill.emit",
                     "omni.prefill.extract_kv")}
    assert at[5.5] == {}
    # nothing of the busy [3, 4) is returned
    assert not [a for a, b, _ in segs if a < 4.0 and b > 3.0]


def test_idle_by_leaf_gives_each_stretch_once(three_threads):
    _, segs = three_threads
    got, no_leaf = P.idle_by_leaf(segs, "decode")
    assert got == pytest.approx({
        "step:omni.decode.decode_inputs": 0.2,       # [1.0, 1.2)
        "hop:omni.conn.send": 0.8,                   # [1.2, 2.0)
        "hop:omni.conn.send+omni.prefill.extract_kv": 0.2,
        "hop:omni.prefill.extract_kv": 0.3,          # [2.2, 2.5)
        "hop:omni.decode.inject_kv+omni.prefill.extract_kv": 0.1,
        "hop:omni.decode.inject_kv": 0.4,            # [2.6, 3.0)
        "step:omni.decode.sample": 1.0,              # [4.0, 5.0)
        "step:omni.decode.step": 0.5,                # [5.0, 5.5)
        "no span": 0.5})                             # [5.5, 6.0)
    assert sum(got.values()) == pytest.approx(4.0)
    # between the decode step's leaves, or in no span at all
    assert no_leaf == pytest.approx(1.0)


def test_the_cross_thread_label_picks_the_wrong_thread():
    """The decode thread sits in its step [0.5, 3.2) while the router
    packs KV in conn.send [0.0, 3.1) through the idle gap [1, 3).
    ``idle_gaps`` gives the whole gap to the shortest span open on any
    thread, the decode step; read per thread, the gap is KV hop."""
    tr = T.Trace(modules={DEV: [("jit__decode_impl(1)", 0.0, 1.0),
                                ("jit__decode_impl(1)", 3.0, 4.0)]})
    spans = [("omni.decode.step", 0.5, 3.2, "python#1"),
             ("omni.conn.send", 0.0, 3.1, "python#2")]
    tr.spans = list(spans)
    assert T.idle_gaps(tr, (0.0, 4.0)) == [
        ["omni.decode.step", pytest.approx(2.0)]]
    segs = P.idle_by_thread(tr, spans, (0.0, 4.0))
    assert P.kv_hop_idle_share(segs, (0.0, 4.0)) == pytest.approx(50.0)
    assert P.step_host_idle_share(segs, (0.0, 4.0), "decode") == 0.0


def test_step_host_share_is_the_output_stage(three_threads):
    """Read for the prefill stage as the output: its step holds no idle
    time outside the KV hop but [1.0, 1.2)."""
    _, segs = three_threads
    assert P.step_host_idle_share(segs, (0.0, 6.0), "prefill") == \
        pytest.approx(100 * 0.2 / 6)


def test_load_keys_program_spans_by_line(tmp_path):
    """Two Python threads' lines share a name; their spans stay apart."""
    import jax
    import jax.numpy as jnp

    def work(stage):
        with jax.profiler.TraceAnnotation(f"omni.{stage}.step"):
            jnp.ones(4).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ts = [threading.Thread(target=work, args=(s,)) for s in "ab"]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    threads = {n: th for n, _, _, th in P.load_spans(path)}
    assert set(threads) == {"omni.a.step", "omni.b.step"}
    assert threads["omni.a.step"] != threads["omni.b.step"]
    assert T.load(path).spans == []          # no bench.* span was made


def test_probe_reads_spans_and_counters_on_cpu():
    """A smoke-width docqa run: the program's spans reach the trace from
    every stage thread and the router, the counters read the window.  A
    CPU trace has no device plane, so the idle readings are empty."""
    import time
    from bench.smoke import smoke_cell
    from bench.spec import resolve
    res = P.probe(smoke_cell(resolve("internlm2_pd.docqa")), 20260001, 4.0,
                  time.perf_counter())
    assert res["spans"] > 0 and res["threads"] >= 2
    assert res["sched_wait_p90_ms"] is not None
    assert res["sched_wait_p90_ms"] >= 0.0
    assert 0.0 < res["kv_reserved_used_share"] <= 100.0
    assert res["idle_in_kv_hop_share"] is None
    assert res["idle_by_leaf_s"] == []


# ---- the recorded v5e trace (no omni.* spans: the program before them)

@pytest.fixture(scope="module")
def fixture_run():
    tr = T.load(str(DATA / "decode_prefill_2layer.xplane.pb"))
    lo = min(s for _, s, _, _ in tr.spans)
    hi = max(e for _, _, e, _ in tr.spans)
    cfg = json.loads((ROOT / "bench/configs/internlm2_1_8b_pd.json")
                     .read_text())
    cfg["num_hidden_layers"] = 2
    return SimpleNamespace(
        trace=tr, trace_window=(lo, hi), trace_host=(0.0, 10.0),
        dims=counts.Dims.from_config(cfg), peaks=peaks_for("TPU v5 lite"),
        cell=SimpleNamespace(config=cfg), snapshots={},
        system=SimpleNamespace(output="decode"),
        recorder=SimpleNamespace(decodes={"decode": [(1.0, 2.0,
                                                      [200] * 8)] * 3}))


# read with the benchmark's files as they were before the program spans
@pytest.mark.parametrize("name,value", [
    ("decode_step_ms", 10.68328866666666),
    ("prefill_chunk_ms", 6.030618000000001),
    ("device_idle_share.prefill", 50.681095592517124),
    ("device_idle_share.decode", 50.681095592517124),
    ("paged_attn_roofline", 0.3826865912183309),
])
def test_existing_readers_read_as_before(fixture_run, name, value):
    assert reader(name).read(fixture_run) == pytest.approx(value, rel=1e-12)


def test_breakdown_reads_as_before(fixture_run):
    tr, w = fixture_run.trace, fixture_run.trace_window
    assert P.load_spans(str(DATA / "decode_prefill_2layer.xplane.pb")) == []
    assert T.idle_gaps(tr, w) == [
        ["no span (host idle or untraced)", pytest.approx(0.013131499)],
        ["bench.extract_kv", pytest.approx(0.012145618)],
        ["bench.inject_kv", pytest.approx(0.006787962)],
        ["bench.decode", pytest.approx(0.00458555)],
        ["bench.prefill_chunk", pytest.approx(0.003227228)]]
    assert [n for n, _ in T.top_ops(tr, w, k=3)] == [
        "paged_attention", "copy", "constant_dynamic-slice_fusion"]


def _readings(tr, spans, window, waits, snaps):
    segs = P.idle_by_thread(tr, spans, window)
    return {
        "idle_in_kv_hop_share":
            P.kv_hop_idle_share(segs, window) if spans else None,
        "idle_in_step_host_share":
            P.step_host_idle_share(segs, window, "decode") if spans
            else None,
        "sched_wait_p90_ms": P.sched_wait_p90_ms(waits, [1, 2]),
        "kv_reserved_used_share": P.kv_reserved_used_share(*snaps)}


@pytest.mark.parametrize("name", ["idle_in_kv_hop_share",
                                  "idle_in_step_host_share",
                                  "sched_wait_p90_ms",
                                  "kv_reserved_used_share"])
def test_new_readers_read_nothing_without_the_program_spans(fixture_run,
                                                            name):
    """A trace with no program span and counters that did not move give
    no value, and no error."""
    path = str(DATA / "decode_prefill_2layer.xplane.pb")
    still = {"reserved_page_steps": 7, "used_page_steps": 3}
    got = _readings(fixture_run.trace, P.load_spans(path),
                    fixture_run.trace_window, [], (still, still))
    assert got[name] is None


def test_counter_readers_read_the_window():
    """Two snapshots of ``sched_stats``: the share takes what moved
    between them; the wait p90 takes the waits of the requests asked
    for, a preempted one's both waits included."""
    opened = {"reserved_page_steps": 100, "used_page_steps": 40}
    closed = {"reserved_page_steps": 500, "used_page_steps": 240}
    assert P.kv_reserved_used_share(opened, closed) == pytest.approx(50.0)
    waits = [(1, 9.0), (2, 8.0), (3, 0.5), (4, 1.5), (5, 2.5), (6, 3.5),
             (4, 0.5)]
    # numpy's p90 of 0.5, 0.5, 1.5, 2.5, 3.5 s: 3.1 s
    assert P.sched_wait_p90_ms(waits, [3, 4, 5, 6, 7]) == \
        pytest.approx(3100.0)
    assert P.sched_wait_p90_ms(waits, [7]) is None
