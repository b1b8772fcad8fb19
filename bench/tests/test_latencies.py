"""The end-to-end arithmetic on hand-made stamps: TPOT samples are
4-token stretches of each request's output, and a request still open at
the drain cap counts as ending there."""
from types import SimpleNamespace as NS

import pytest

from bench.cell import TPOT_STRETCH, end_to_end, latencies


def rec(rid, due, first, done, failed=None):
    return NS(due=due, req=NS(req_id=rid, first_output_time=first,
                              completion_time=done, failed=failed))


def fake_run():
    # request 1: 9 tokens 0.1 s apart from t=11; request 2: 6 tokens,
    # 0.2 s apart, still open at the cap; request 3 failed
    times = {1: [(11.0 + 0.1 * i, 1) for i in range(9)],
             2: [(12.0 + 0.2 * i, 1) for i in range(6)],
             3: [(13.0, 1)]}
    records = [rec(1, 10.0, 11.0, 11.8), rec(2, 10.5, 12.0, None),
               rec(3, 11.0, 13.0, 13.0, failed="boom")]
    recorder = NS(token_times=times,
                  out_tokens=[(t, n) for v in times.values() for t, n in v])
    return NS(records=records, recorder=recorder,
              timeline=NS(open=10.0, close=20.0, end=30.0),
              window=(10.0, 20.0))


def test_latencies_from_stamps():
    lat = latencies(fake_run())
    assert TPOT_STRETCH == 4
    assert lat["ttft_s"] == pytest.approx([1.0, 1.5])
    assert lat["jct_s"] == pytest.approx([1.8, 19.5])    # 2: at the cap
    # request 1: stretches 0-4 and 4-8; request 2: stretch 0-4
    assert lat["tpot_ms"] == pytest.approx([100.0, 100.0, 200.0])


def test_end_to_end_reads_medians_and_the_tpot_tail():
    e = end_to_end(fake_run(), 42.0)
    assert e["ttft_p50_s"] == pytest.approx(1.25)
    assert e["jct_p50_s"] == pytest.approx(10.65)
    assert e["tpot_p90_ms"] == pytest.approx(180.0)
    assert e["out_tok_per_s"] == pytest.approx(16 / 10.0)
    assert e["setup_s"] == 42.0
