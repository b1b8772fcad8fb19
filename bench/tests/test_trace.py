"""The trace reduction on a recorded TPU v5e trace.

``data/decode_prefill_2layer.xplane.pb`` was recorded on one v5e with the
program's PagedRunner at InternLM2 widths cut to 2 layers (pool of 3300
pages, batch 8 x 408 pages): one prefill chunk, three decode steps, one
``extract_kv`` and one ``inject_kv``, each inside a ``bench.*``
TraceAnnotation.  The expected numbers were read from the same file by a
separate listing (plain iteration over ``ProfileData`` events, summing
durations by name), not by ``bench/trace.py``.
"""
from pathlib import Path

import pytest

from bench import trace as T

FIXTURE = Path(__file__).parent / "data" / "decode_prefill_2layer.xplane.pb"
MS = 1e-3


@pytest.fixture(scope="module")
def tr():
    return T.load(str(FIXTURE))


def test_planes_and_spans(tr):
    assert tr.devices == ["/device:TPU:0"]
    assert [s[0] for s in tr.spans] == [
        "bench.prefill_chunk", "bench.decode", "bench.decode",
        "bench.decode", "bench.extract_kv", "bench.inject_kv"]


@pytest.mark.parametrize("program,n,total_ms", [
    ("jit__decode_impl", 3, 32.0499),
    ("jit__prefill_impl", 1, 6.0306),
    ("jit_scatter", 2, 0.6776),
])
def test_program_device_time(tr, program, n, total_ms):
    d = T.program_durations(tr, program)
    assert len(d) == n
    assert sum(d) == pytest.approx(total_ms * MS, abs=1e-7)


def test_paged_kernel_time(tr):
    d = T.op_durations(tr, "paged_attention")
    assert len(d) == 6                       # 3 decodes x 2 layers
    assert sum(d) == pytest.approx(12.735 * MS, abs=1e-6)


def test_busy_and_idle_over_the_decode_spans(tr):
    w = T.span_window(tr, "bench.decode")
    assert w == pytest.approx((63.695919 * MS, 102.930378 * MS), abs=1e-9)
    # only the three decode programs run in it: 3 x ~10.68 ms
    assert T.busy_seconds(tr, w) == pytest.approx(32.0499 * MS, abs=1e-7)
    assert T.idle_share(tr, w) == pytest.approx(
        1 - 32.0499 / (102.930378 - 63.695919), abs=1e-5)


def test_top_ops_leave_out_control_flow(tr):
    w = T.span_window(tr, "bench.decode")
    top = T.top_ops(tr, w)
    assert top[0][0] == "paged_attention"
    assert top[0][1] == pytest.approx(12.735 * MS, abs=1e-6)
    assert all(not name.startswith("while") for name, _ in top)


def test_idle_gaps_are_labelled_by_the_host_span(tr):
    lo = min(s for _, s, _, _ in tr.spans)
    hi = max(e for _, _, e, _ in tr.spans)
    gaps = dict(T.idle_gaps(tr, (lo, hi)))
    # the 10 ms sleep between the last decode and extract_kv has no span
    assert gaps["no span (host idle or untraced)"] > 10 * MS
    # extract_kv's device work is tiny next to its host copy
    assert gaps["bench.extract_kv"] > 10 * MS
    total = sum(gaps.values())
    assert total == pytest.approx((hi - lo) - T.busy_seconds(tr, (lo, hi)),
                                  abs=1e-9)


def test_names():
    assert T.program_name("jit__decode_impl(12145447519785914434)") == \
        "jit__decode_impl"
    assert T.op_name("%paged_attention.6 = bf16[8,8,2,128] custom-call(s32)")\
        == "paged_attention"
    assert T.op_name("%copy.118 = bf16[2] copy(x)") == "copy"
