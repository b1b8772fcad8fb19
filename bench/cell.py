"""One run of one cell: build, warm, serve the window, check, measure.

``run_cell`` is what ``bench/run.py`` calls once it has found the chips;
tests call it directly at smoke widths on the CPU.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from bench import correct, serving
from bench.instrument import CompileClock, Recorder
from bench.spec import Cell
from bench.traffic import Traffic

TRACE_SECONDS = 5.0        # least length of the traced slice, mid-window
#: seconds the traced slice runs on past a prefill call, for the device
#: to finish it behind the work queued ahead of it
PREFILL_TAIL_S = 0.5


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Run:
    """Everything a per-layer reader may read (``bench/layer_metrics``)."""
    cell: Cell
    dims: Any                           # the model kind's dims(cfg)
    peaks: Dict
    system: Any
    recorder: Recorder
    clock: CompileClock
    records: List[serving.Record]       # requests due in the window
    timeline: serving.Timeline
    snapshots: Dict[str, Dict] = field(default_factory=dict)
    trace: Any = None                   # bench.trace.Trace, traced runs
    trace_window: Optional[tuple] = None        # on the trace's clock
    trace_host: Optional[tuple] = None          # perf_counter interval
    sent: int = 0                       # requests sent in all

    @property
    def window(self):
        return self.timeline.open, self.timeline.close

    def delta(self, what: str, key: str) -> float:
        a, b = self.snapshots[f"{what}@open"], self.snapshots[f"{what}@close"]
        return b[key] - a[key]


#: tokens per sample of the time per output token: on the host's clock a
#: sample then spans several decode steps (over 0.3 s at today's steps)
TPOT_STRETCH = 4


def _pct(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def latencies(run: Run) -> Dict[str, List[float]]:
    """Per-request TTFT and JCT of the requests due in the window (a
    request still open at the drain cap counts as ending there), and the
    time per output token of every TPOT_STRETCH consecutive tokens of
    each of them, as the output stage produced them."""
    end = run.timeline.end
    recs = [r for r in run.records if not r.req.failed]
    ttft = [(r.req.first_output_time or end) - r.due for r in recs]
    jct = [(r.req.completion_time or end) - r.due for r in recs]
    tpot, k = [], TPOT_STRETCH
    for r in recs:
        ts = [t for t, n in run.recorder.token_times.get(r.req.req_id, ())
              for _ in range(n)]
        tpot += [(ts[j + k] - ts[j]) / k * 1e3
                 for j in range(0, len(ts) - k, k)]
    return {"ttft_s": ttft, "jct_s": jct, "tpot_ms": tpot}


def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    lat = latencies(run)
    t0, t1 = run.window
    toks = sum(n for t, n in run.recorder.out_tokens if t0 <= t < t1)
    return {"ttft_p50_s": _pct(lat["ttft_s"], 50),
            "tpot_p90_ms": _pct(lat["tpot_ms"], 90),
            "jct_p50_s": _pct(lat["jct_s"], 50),
            "out_tok_per_s": toks / (t1 - t0), "setup_s": setup_s}


def describe(run: Run) -> str:
    """One stderr line: the samples behind the end-to-end metrics, with
    the tails beside the medians."""
    lat = latencies(run)
    parts = [f"{len(lat['ttft_s'])} requests, {len(lat['tpot_ms'])} tpot "
             f"samples"]
    for k, xs in lat.items():
        if xs:
            parts.append(k + " p50/p90/max " + "/".join(
                f"{_pct(xs, q):.4f}" for q in (50, 90, 100)))
    return "latencies: " + "; ".join(parts)


def _warm_programs(system, traffic: Traffic, orch) -> None:
    """Compile what the window will run: the graph's own eager programs
    (``warm_fn``), the copy-on-write page copies a prefix-caching engine
    can make in one step, and one small batch of requests through the
    whole served path (prefill, decode, sampling, the connector)."""
    from repro.core.request import Request
    if system.warm_fn is not None:
        system.warm_fn(system, traffic.prompt_lengths())
    for eng in system.engines.values():
        if eng.enable_prefix_cache:
            for k in range(1, eng.max_batch + 1):
                eng.runner.copy_pages(list(range(k)), list(range(k, 2 * k)))
    rng = np.random.default_rng(0)
    vocab = next(iter(system.engines.values())).cfg.vocab_size
    prompt = rng.integers(0, vocab, size=80, dtype=np.int32)
    for batch in ([prompt, prompt[:33]], [prompt]):   # 2nd: a prefix hit
        reqs = [Request(inputs={"tokens": p},
                        sampling={"max_new_tokens": 3, "temperature": 0.0})
                for p in batch]
        for r in reqs:
            orch.submit(r)
        for _ in reqs:
            r = orch.completions.get(timeout=600)
            if r.failed:
                raise RuntimeError(f"warm-up request failed: {r.failed}")


class Bench:
    """The cell's system, built, warmed and serving: weights of the
    configuration's model kind from the seed, the graph's engines behind
    the program's threaded ``Orchestrator``, the benchmark's recorders
    installed."""

    def __init__(self, cell: Cell, seed: int, trace: bool, t_start: float,
                 seconds: float):
        from repro.core.config import ServeConfig
        from repro.core.orchestrator import Orchestrator

        self.cell, self.seed, self.t_start = cell, seed, t_start
        self.seconds = seconds
        cfg = cell.config
        kind = cell.model_module()
        self.dims = kind.dims(cfg)
        self.clock = clock = CompileClock.install()
        self.split = split = {}
        t = time.perf_counter()
        split["start_s"] = t - t_start

        def phase(name, t):
            now = time.perf_counter()
            split[name] = now - t
            n, sec, hits = clock.between(t, now)
            log(f"set-up: {name}={now - t:.2f} (compiles {n}, {sec:.2f}s, "
                f"cache hits {hits})")
            return now

        self.params = kind.init_params(cfg, seed)
        t = phase("weights_s", t)
        self.system = cell.graph_module().build(
            cfg, kind.model_config(cfg), self.params, seed)
        t = phase("engines_host_embed_s", t)
        self.recorder = Recorder(self.dims, kind, spans=trace)
        self.recorder.install(self.system)
        self.orch = Orchestrator(self.system.graph, self.system.engines,
                                 config=ServeConfig(backend="threaded"))
        if self.system.connector:
            self.recorder.install_connector(
                self.orch.connectors[self.system.connector])
        self.orch.start()
        try:
            _warm_programs(self.system, self.traffic(cell.traffic), self.orch)
        except BaseException:
            self.close()
            raise
        phase("warm_s", t)
        n, sec, hits = self.clock.between(t_start, time.perf_counter())
        split.update(compiles=n, compile_and_load_s=sec, cache_hits=hits)

    def traffic(self, spec: Dict, seed: Optional[int] = None) -> Traffic:
        cfg = self.cell.config
        tr = Traffic(spec, self.seed if seed is None else seed,
                     cfg["vocab_size"], self.seconds)
        if tr.max_total_len() > cfg["serving"]["max_seq"]:
            raise ValueError(f"a request of this mix can reach "
                             f"{tr.max_total_len()} tokens, over max_seq "
                             f"{cfg['serving']['max_seq']}")
        return tr

    def snapshot(self, run: "Run", tag: str) -> None:
        run.snapshots[f"prefix@{tag}"] = dict(
            self.system.engines[self.system.entry].prefix_stats)
        if self.system.connector:
            st = self.orch.connectors[self.system.connector].stats
            run.snapshots[f"conn@{tag}"] = {"calls": st.calls,
                                            "wall": st.wall_time,
                                            "bytes": st.bytes}

    def serve(self, spec: Dict, seed: Optional[int] = None,
              hooks=()) -> "Run":
        """Serve one window of ``spec`` traffic; the Run holds the
        requests due in it."""
        seconds = self.seconds
        run = Run(self.cell, self.dims, None, self.system, self.recorder,
                  self.clock, [], None)
        hooks = [(0.0, lambda: self.snapshot(run, "open")),
                 (seconds, lambda: self.snapshot(run, "close")), *hooks]
        records, tl = serving.drive(self.orch, self.traffic(spec, seed),
                                    spec, seconds, hooks)
        run.timeline = tl
        run.records = [r for r in records if tl.open <= r.due < tl.close]
        run.sent = len(records)
        return run

    def close(self) -> None:
        self.orch.shutdown(drain=False)


def _tracer(tdir: str, seconds: float, recorder: Recorder):
    """Hooks that trace a slice from the middle of the window, and the
    dict they fill with its host-clock ends.  The slice lasts at least
    TRACE_SECONDS, and on until a prefill call made in it has had
    PREFILL_TAIL_S to end, or until the window closes: an open loop's
    fixed schedule can leave a 5 s stretch with no arrival in it."""
    import jax
    tr_len = min(TRACE_SECONDS, seconds)
    tr_off = (seconds - tr_len) / 2
    state = {}

    def start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        state["ann"] = jax.profiler.TraceAnnotation("bench.traced")
        state["ann"].__enter__()
        state["t0"] = time.perf_counter()
        # at or after the window's close: start runs at or after its time
        state["close"] = state["t0"] + seconds - tr_off

    def prefilled(now: float) -> bool:
        return any(a >= state["t0"] and b + PREFILL_TAIL_S <= now
                   for calls in recorder.prefills.values()
                   for a, b, _, _ in reversed(calls))

    def stop():
        now = time.perf_counter()
        if now < state["close"] and not prefilled(now):
            return 0.05
        state["t1"] = now
        state["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        return None

    return [(tr_off, start), (tr_off + tr_len, stop)], state


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Dict, device: Dict) -> Dict:
    """Serve the cell's traffic for ``seconds`` and return the result
    object (``correct``, ``attempted``, ``failed``, ``metrics``,
    ``device``, optional ``breakdown``, and ``checks`` last)."""
    import jax

    b = Bench(cell, seed, trace, t_start, seconds)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        hooks, tstate = (_tracer(tdir, seconds, b.recorder) if trace
                         else ([], {}))
        run = b.serve(cell.traffic, hooks=hooks)
    finally:
        b.close()
    run.peaks = peaks
    tl = run.timeline
    setup_s = tl.open - t_start
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    late = np.asarray(tl.lateness or [0.0])
    compiles = b.clock.between(tl.open, tl.close)[0]
    log("setup split (s): " + " ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in b.split.items()) + f" total={setup_s:.3f}")
    log(f"window {seconds}s: {len(run.records)} requests due, "
        f"{sum(r.done for r in run.records)} done by the drain cap; "
        f"{run.sent} sent in all; generator late p50/max "
        f"{np.median(late) * 1e3:.2f}/{late.max() * 1e3:.2f} ms; "
        f"compiles in window {compiles}")
    log(describe(run))
    log(f"memory: peak_bytes_in_use={peak} "
        f"bytes_limit={mem.get('bytes_limit')}")

    breakdown = None
    if trace:
        from bench import trace as T
        run.trace = T.load(T.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        run.trace_window = T.span_window(run.trace, "bench.traced")
        run.trace_host = (tstate["t0"], tstate["t1"])
        busy = T.busy_seconds(run.trace, run.trace_window)
        device = dict(device, busy_s=busy, window_s=run.trace_window[1]
                      - run.trace_window[0])
        breakdown = {"device_ops": T.top_ops(run.trace, run.trace_window),
                     "idle_gaps": T.idle_gaps(run.trace, run.trace_window,
                                              exclude=("bench.traced",))}
    device = dict(device, memory_peak_bytes=peak)

    # the program's state goes before the reference runs beside the weights
    b.system.free()
    b.orch = None
    gc.collect()
    t = time.perf_counter()
    verdict = correct.check(cell, b.params, run, seed)
    log(f"reference check took {time.perf_counter() - t:.1f}s over "
        f"{verdict['requests']} requests, {verdict['tokens']} served tokens")

    failed = sum(1 for r in run.records if r.req.failed)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    checks = {"failed_requests": {"value": failed, "limit": 0},
              **verdict["checks"]}
    ok = failed == 0 and verdict["ok"] and len(run.records) > 0
    out = {"correct": bool(ok), "attempted": len(run.records),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # every run says whether warm-up missed a shape (0 when it did not)
    out["compiles_in_window"] = compiles
    out["checks"] = checks
    return out
