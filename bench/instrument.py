"""What the benchmark records around the program, from its own files.

Wrappers are installed on the engine, runner and connector *instances*
(nothing in ``src/`` changes).  Each records host-clock intervals and
the work of each call, and, in a traced run, opens a
``jax.profiler.TraceAnnotation`` span named ``bench.<what>`` so that the
device trace's idle gaps can be labelled by what the host was doing:

    bench.step.<stage>     AREngine.step
    bench.prefill_chunk    PagedRunner.prefill_chunk
    bench.decode           PagedRunner.decode
    bench.extract_kv       PagedRunner.extract_kv
    bench.inject_kv        PagedRunner.inject_kv
    bench.copy_pages       PagedRunner.copy_pages
    bench.send / .recv     Connector.send / Connector.recv

``CompileClock`` counts backend compiles (persistent-cache reads
included) from ``jax.monitoring``.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Dict, List, Tuple

import numpy as np


class CompileClock:
    """(perf_counter time, seconds) of each backend compile and the times
    of persistent-cache hits, since ``install``.  jax.monitoring cannot
    unregister a listener, so one clock serves the process."""

    _installed = None

    def __init__(self):
        self.compiles: List[Tuple[float, float]] = []
        self.hits: List[float] = []

    @classmethod
    def install(cls) -> "CompileClock":
        if cls._installed is None:
            import jax.monitoring as mon
            clock = cls()

            def on_duration(event, duration, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    clock.compiles.append((time.perf_counter(), duration))

            def on_event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    clock.hits.append(time.perf_counter())

            mon.register_event_duration_secs_listener(on_duration)
            mon.register_event_listener(on_event)
            cls._installed = clock
        return cls._installed

    def between(self, t0: float, t1: float) -> Tuple[int, float, int]:
        """(compiles, compile seconds, cache hits) in [t0, t1)."""
        c = [d for t, d in self.compiles if t0 <= t < t1]
        return len(c), float(sum(c)), sum(1 for t in self.hits if t0 <= t < t1)


@dataclass
class Recorder:
    dims: Any                   # the model kind's dims(cfg)
    kind: ModuleType            # bench/models/<kind>.py: the step FLOPs
    spans: bool = False
    # stage -> [(t0, t1, model flops of the step)]
    steps: Dict[str, List[Tuple[float, float, int]]] = field(
        default_factory=dict)
    # stage -> [(t0, t1, seq_lens of the active slots)]
    decodes: Dict[str, List[Tuple[float, float, List[int]]]] = field(
        default_factory=dict)
    # stage -> [(t0, t1, start, valid tokens)]
    prefills: Dict[str, List[Tuple[float, float, int, int]]] = field(
        default_factory=dict)
    out_tokens: List[Tuple[float, int]] = field(default_factory=list)
    # output stage: req_id -> [(step end, tokens)] of each streamed chunk
    token_times: Dict[int, List[Tuple[float, int]]] = field(
        default_factory=dict)
    _step_flops: Dict[str, int] = field(default_factory=dict)

    def _span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, obj: Any, method: str, span: str, after=None) -> None:
        orig = getattr(obj, method)

        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            with self._span(span):
                out = orig(*a, **kw)
            if after is not None:
                after(t0, time.perf_counter(), a, out)
            return out

        setattr(obj, method, wrapped)

    def install(self, system) -> None:
        for stage, eng in system.engines.items():
            self.steps[stage], self.decodes[stage] = [], []
            self.prefills[stage] = []
            self._step_flops[stage] = 0
            r = eng.runner

            def on_prefill(t0, t1, a, out, stage=stage):
                start, valid = int(a[2]), int(a[3])
                self.prefills[stage].append((t0, t1, start, valid))
                self._step_flops[stage] += self.kind.prefill_chunk_flops(
                    self.dims, start, valid)

            def on_decode(t0, t1, a, out, stage=stage):
                pos, act = np.asarray(a[2]), np.asarray(a[3], bool)
                lens = [int(p) + 1 for p in pos[act]]
                self.decodes[stage].append((t0, t1, lens))
                self._step_flops[stage] += self.kind.decode_step_flops(
                    self.dims, lens)

            def on_step(t0, t1, a, events, stage=stage,
                        output=stage == system.output):
                self.steps[stage].append((t0, t1, self._step_flops[stage]))
                self._step_flops[stage] = 0
                if output:
                    n = 0
                    for e in events:
                        if e.kind == "chunk":
                            k = len(e.payload["tokens"])
                            self.token_times.setdefault(e.req_id, []).append(
                                (t1, k))
                            n += k
                    if n:
                        self.out_tokens.append((t1, n))

            self._wrap(r, "prefill_chunk", "bench.prefill_chunk", on_prefill)
            self._wrap(r, "decode", "bench.decode", on_decode)
            for m in ("extract_kv", "inject_kv", "copy_pages"):
                self._wrap(r, m, f"bench.{m}")
            self._wrap(eng, "step", f"bench.step.{stage}", on_step)

    def install_connector(self, conn) -> None:
        self._wrap(conn, "send", "bench.send")
        self._wrap(conn, "recv", "bench.recv")
