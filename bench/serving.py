"""The served system and the load that drives it.

``System`` is what a graph file builds: the program's stage graph and
engines, which stage emits the output and which one admits requests.
``drive`` offers a traffic mix to the program's threaded
``Orchestrator`` through ``submit`` and reads results from its
``completions`` queue; the program receives only the generated inputs.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from bench.traffic import Item, Traffic


@dataclass
class System:
    graph: Any
    engines: Dict[str, Any]
    output: str                     # stage whose tokens reach the user
    entry: str                      # stage that admits requests
    connector: Optional[str] = None
    warm_fn: Optional[Callable] = None

    def free(self) -> None:
        """Drop the engines' device state (KV pools) and their references
        to the weights, which belong to the caller."""
        for eng in self.engines.values():
            r = eng.runner
            r.k_pages = r.v_pages = r.k_scales = r.v_scales = None
            r.params = r._embed_np = None
        self.engines = {}


@dataclass
class Record:
    """One request as the load generator saw it."""
    item: Item
    req: Any                        # the program's Request
    due: float                      # perf_counter time it was due / sent
    client: int = -1

    @property
    def done(self) -> bool:
        return self.req.completion_time is not None

    def n_out(self, stage: str) -> int:
        return sum(len(c["tokens"]) for c in self.req.outputs.get(stage, ())
                   if "n_chunks" not in c)


@dataclass
class Timeline:
    start: float = 0.0              # traffic starts
    open: float = 0.0               # window opens
    close: float = 0.0              # window closes
    end: float = 0.0                # measured requests finished or drain cap
    lateness: List[float] = field(default_factory=list)   # open loop


def _request(item: Item, due: float):
    from repro.core.request import Request
    return Request(inputs={"tokens": item.tokens},
                   sampling={"max_new_tokens": item.max_new,
                             "temperature": 0.0},
                   arrival_time=due)


def drive(orch, traffic: Traffic, spec: Dict, seconds: float,
          hooks: List[Tuple[float, Callable[[], Optional[float]]]] = (),
          ) -> Tuple[List[Record], Timeline]:
    """Serve ``spec['warmup_s']`` seconds of traffic, then the measured
    window of ``seconds``, then keep offering load until every request
    due in the window has completed or ``spec['drain_s']`` has passed
    (a request still open then is late, not failed).
    ``hooks`` are (offset from window open, callable) run on this thread;
    a callable that returns a number of seconds is run again that much
    later (after the load stops, at once) until it returns None.
    Returns every request sent, and the timeline."""
    tl = Timeline()
    records: List[Record] = []
    by_id: Dict[int, Record] = {}
    lock = threading.Lock()
    stop = threading.Event()
    items = traffic.items()
    tl.start = time.perf_counter()
    # the same sums as the traffic's due times, so that the window holds
    # exactly the requests the mix puts in it
    tl.open = tl.start + float(spec["warmup_s"])
    tl.close = tl.start + (float(spec["warmup_s"]) + seconds)
    cap = tl.close + float(spec["drain_s"])
    pending_hooks = sorted(((tl.open + off, fn) for off, fn in hooks),
                           key=lambda h: h[0])

    def send(item: Item, due: float, client: int = -1) -> None:
        rec = Record(item, _request(item, due), due, client)
        with lock:
            records.append(rec)
            by_id[rec.req.req_id] = rec
        orch.submit(rec.req)

    submitter = None
    if spec["loop"] == "open":
        def open_loop():
            for item in items:
                due = tl.start + item.at_s
                while not stop.is_set():
                    wait = due - time.perf_counter()
                    if wait <= 0:
                        break
                    stop.wait(min(wait, 0.05))
                if stop.is_set():
                    return
                tl.lateness.append(time.perf_counter() - due)
                send(item, due)
        submitter = threading.Thread(target=open_loop, name="bench-load",
                                     daemon=True)
        submitter.start()
    else:
        for c in range(int(spec["clients"])):
            send(next(items), time.perf_counter(), c)

    def measured() -> List[Record]:
        with lock:
            return [r for r in records if tl.open <= r.due < tl.close]

    try:
        while True:
            now = time.perf_counter()
            while pending_hooks and pending_hooks[0][0] <= now:
                fn = pending_hooks.pop(0)[1]
                again = fn()
                if again is not None:
                    pending_hooks.append((now + again, fn))
                    pending_hooks.sort(key=lambda h: h[0])
            if orch.worker_error:
                raise RuntimeError(f"stage worker died: {orch.worker_error}")
            if now >= tl.close and (now >= cap
                                    or all(r.done for r in measured())):
                break
            try:
                req = orch.completions.get(timeout=0.02)
            except queue.Empty:
                continue
            if spec["loop"] == "closed":
                with lock:
                    rec = by_id.get(req.req_id)
                if rec is not None and rec.client >= 0:
                    send(next(items), time.perf_counter(), rec.client)
        for _, fn in pending_hooks:           # a hook past the end still runs
            while (again := fn()) is not None:
                time.sleep(again)
    finally:
        stop.set()
        if submitter is not None:
            submitter.join(timeout=10.0)
    tl.end = time.perf_counter()
    return records, tl
