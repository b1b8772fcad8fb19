"""The traced slice: it lasts at least TRACE_SECONDS and runs on until a
prefill call made inside it has had PREFILL_TAIL_S to end on the device,
or until the window closes (profiler calls stubbed, clock stubbed)."""
from types import SimpleNamespace as NS

import jax
import pytest

from bench import cell as C


@pytest.fixture
def slice_of(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: NS(__enter__=lambda: None,
                                        __exit__=lambda *a: None))
    clock = {"now": 0.0}
    monkeypatch.setattr(C.time, "perf_counter", lambda: clock["now"])

    def make(seconds, prefills):
        recorder = NS(prefills={"prefill": prefills})
        hooks, state = C._tracer("unused", seconds, recorder)
        (t_on, start), (_, stop) = hooks
        clock["now"] = 100.0 + t_on
        start()
        return hooks, state, stop, clock, calls
    return make


def test_slice_waits_for_a_prefill_and_its_tail(slice_of):
    prefills = []
    hooks, state, stop, clock, calls = slice_of(51, prefills)
    (on, _), (off, _) = hooks
    assert (on, off - on) == (23.0, C.TRACE_SECONDS)
    clock["now"] = state["t0"] + C.TRACE_SECONDS
    assert stop() is not None and "stop" not in calls      # none yet
    prefills.append((state["t0"] - 1.0, state["t0"] - 0.9, 0, 16))
    assert stop() is not None                  # before the slice: no use
    t = state["t0"] + 9.5
    prefills.append((t, t + 0.04, 0, 16))
    clock["now"] = t + 0.04 + C.PREFILL_TAIL_S / 2
    assert stop() is not None                  # device may still run it
    clock["now"] = t + 0.04 + C.PREFILL_TAIL_S
    assert stop() is None and calls[-1] == "stop"
    assert state["t1"] - state["t0"] == pytest.approx(
        9.5 + 0.04 + C.PREFILL_TAIL_S)


def test_slice_ends_at_the_window_close_without_a_prefill(slice_of):
    _, state, stop, clock, calls = slice_of(51, [])
    clock["now"] = state["close"] - 0.01
    assert stop() is not None
    clock["now"] = state["close"]
    assert stop() is None and calls[-1] == "stop"
    assert state["close"] - state["t0"] == pytest.approx(51 - 23.0)
