"""Process-isolated stage replicas: cross-process shared-memory
transport, spawn lifecycle (start/drain/stop), replica-death re-admission
and connector-routed warm seeding.

Children run jax-free stub engines rebuilt from picklable EngineSpecs,
so every test here is a sub-second spawn plus stub work — fast tier.
Spawn start is exercised for real: this module IS the <15s process-
isolation smoke that `make check` runs.
"""
import os
import signal
import time

import numpy as np
import pytest

from repro.connector import shm_transport
from repro.connector.shm import SharedMemoryConnector
from repro.core.config import EngineSpec, ServeConfig, StageConfig
from repro.core.graph import StageGraph
from repro.core.orchestrator import Orchestrator
from repro.core.request import Request
from repro.core.stage import StageSpec
from repro.core.worker import StageInput, ReplicaSet
from repro.engine.stub_engine import StubEngine


def _spawn_ok() -> bool:
    if not shm_transport.available():
        return False
    try:
        import multiprocessing as mp
        mp.get_context("spawn")
        return True
    except Exception:                    # noqa: BLE001
        return False


pytestmark = pytest.mark.skipif(
    not _spawn_ok(), reason="spawn multiprocessing or shared_memory "
                            "unavailable on this platform")

STUB = EngineSpec("repro.engine.stub_engine:make_stub",
                  {"name": "s", "dwell_ms": 1.0})


def _graph():
    g = StageGraph()
    g.add_stage(StageSpec("s", "custom", is_output=True))
    return g


# ---------------------------------------------------------------------------
# cross-process shared-memory roundtrip
# ---------------------------------------------------------------------------

def _shm_echo_child(manifest, q):
    """Spawn target: rebuild the payload in another process, unlink the
    segment (ownership passed with the manifest), echo scalars back."""
    payload = shm_transport.read_and_release(manifest)
    q.put({"sum": float(payload["x"].sum()),
           "shape": tuple(payload["x"].shape),
           "tag": payload["meta"]["tag"]})


def test_shm_roundtrip_crosses_processes():
    import multiprocessing as mp
    ctx = mp.get_context("spawn")
    x = np.arange(24, dtype=np.float32).reshape(4, 6)
    seg, manifest = shm_transport.write_segment(
        {"x": x, "meta": {"tag": "hello"}})
    assert seg is not None and manifest.nbytes == x.nbytes
    seg.close()                          # child unlinks via the manifest
    q = ctx.Queue()
    p = ctx.Process(target=_shm_echo_child, args=(manifest, q))
    p.start()
    out = q.get(timeout=30)
    p.join(10)
    assert out == {"sum": float(x.sum()), "shape": (4, 6), "tag": "hello"}
    # the receiving side released the segment: re-attach must fail
    with pytest.raises(FileNotFoundError):
        shm_transport.read_manifest(manifest)


def test_release_manifest_is_idempotent():
    seg, manifest = shm_transport.write_segment(
        {"x": np.ones(8, np.float32)})
    seg.close()
    shm_transport.release_manifest(manifest)
    shm_transport.release_manifest(manifest)     # second release: no-op


# ---------------------------------------------------------------------------
# orchestrator end-to-end: process stage serves identically to thread
# ---------------------------------------------------------------------------

def _run_pipeline(isolation):
    stages = {"s": StageConfig(replicas=2, isolation=isolation,
                               engine_spec=STUB,
                               engine_factory=lambda: STUB.build())}
    orch = Orchestrator(_graph(), {"s": StubEngine("s")},
                        config=ServeConfig(stages=stages))
    reqs = [Request(inputs={"x": i}) for i in range(8)]
    for r in reqs:
        orch.submit(r)
    done = orch.run(timeout=60.0)
    assert len(done) == 8 and not any(r.failed for r in done)
    return sorted(r.outputs["s"][0]["x"] for r in done), orch


def test_process_stage_matches_thread_outputs():
    out_thread, _ = _run_pipeline("thread")
    out_proc, orch = _run_pipeline("process")
    assert out_proc == out_thread == list(range(8))
    m = orch.stage_metrics()["s"]
    assert m["admitted"] == m["finished"] == 8
    assert m["errors"] == 0 and m["replica_failures"] == 0
    assert m["n_replicas"] == 2


def test_pre_start_admission_is_deferred_then_served():
    stages = {"s": StageConfig(isolation="process", engine_spec=STUB)}
    orch = Orchestrator(_graph(), {"s": StubEngine("s")},
                        config=ServeConfig(stages=stages))
    # submit BEFORE start(): a process stage has no parent-side engine
    # to step, so admission defers and flushes through the worker
    orch.submit(Request(inputs={"x": 41}))
    done = orch.run(timeout=60.0)
    assert len(done) == 1 and done[0].outputs["s"][0]["x"] == 41


# ---------------------------------------------------------------------------
# lifecycle: drain loses nothing; killed replica re-admits in-flight work
# ---------------------------------------------------------------------------

def test_drain_stops_losing_nothing():
    spec = EngineSpec("repro.engine.stub_engine:make_stub",
                      {"name": "s", "dwell_ms": 20.0})
    events = []
    rs = ReplicaSet("s", [None], lambda st, ev: events.append(ev),
                    isolation="process", engine_spec=spec)
    rs.start()
    assert rs.workers()[0][1].wait_ready(30.0)
    for i in range(10):
        assert rs.submit(StageInput(Request(inputs={"x": i}), None,
                                    inputs={"x": i}), timeout=10.0)
    rs.stop(drain=True)
    rs.join(60.0)
    finished = [e for e in events if e.kind == "finished"]
    assert len(finished) == 10
    assert not [e for e in events if e.kind == "error"]


def test_killed_replica_readmits_to_survivor():
    spec = EngineSpec("repro.engine.stub_engine:make_stub",
                      {"name": "s", "dwell_ms": 30.0})
    events = []
    rs = ReplicaSet("s", [None, None], lambda st, ev: events.append(ev),
                    isolation="process", engine_spec=spec,
                    process_opts={"heartbeat_timeout": 5.0})
    rs.start()
    for _, w in rs.workers():
        assert w.wait_ready(30.0)
    reqs = [Request(inputs={"x": i}) for i in range(12)]
    for r in reqs:
        assert rs.submit(StageInput(r, None, inputs=r.inputs), timeout=10.0)
    time.sleep(0.05)                     # let work start flowing
    victim = rs.workers()[0][1]
    os.kill(victim._proc.pid, signal.SIGKILL)
    deadline = time.time() + 30.0
    while time.time() < deadline:
        if len({e.req_id for e in events if e.kind == "finished"}) == 12:
            break
        time.sleep(0.05)
    rs.stop(drain=True)
    rs.join(30.0)
    finished = {e.req_id for e in events if e.kind == "finished"}
    assert finished == {r.req_id for r in reqs}          # zero lost
    assert not [e for e in events if e.kind == "error"]
    assert rs.n_replicas == 1                            # survivor only
    assert len(rs.failure_events) == 1
    fe = rs.failure_events[0]
    assert fe["reason"] == "process exited" and fe["readmitted"] >= 1
    # the failure is visible in the banked worker metrics
    assert sum(m.snapshot()["replica_failures"]
               for m in rs.metrics_bank.values()) == 1


# ---------------------------------------------------------------------------
# warm seeding routed through the connector channel API
# ---------------------------------------------------------------------------

def _seed_pages(n):
    return [{"hash": i, "k": np.full((4, 8), i, np.float32),
             "v": np.full((4, 8), -i, np.float32)} for i in range(n)]


def test_scale_up_warm_seeds_over_connector():
    spec = EngineSpec("repro.engine.stub_engine:make_seedable",
                      {"name": "s", "pages": 0})
    conn = SharedMemoryConnector(cross_process=True)
    rs = ReplicaSet("s", [None], lambda st, ev: None,
                    isolation="process", engine_spec=spec,
                    seed_connector=conn)
    rs.start()
    w0 = rs.workers()[0][1]
    assert w0.wait_ready(30.0)
    assert w0.seed_snapshot(_seed_pages(6)) == 6         # warm the donor
    rid = rs.scale_up()
    try:
        assert rs.seed_events == [{"rid": rid, "donor_pages": 6,
                                   "pages": 6, "via": "manifest"}]
        snap = rs._replicas[rid].prefix_snapshot()
        assert len(snap) == 6
        for p in snap:                   # byte-equivalent to the donor's
            assert np.array_equal(
                p["k"], np.full((4, 8), p["hash"], np.float32))
            assert np.array_equal(
                p["v"], np.full((4, 8), -p["hash"], np.float32))
    finally:
        rs.stop()
        rs.join(30.0)
    assert conn.resident_bytes == 0      # seed payload fully released


def test_warm_seed_failure_degrades_to_cold_start():
    class RefusingConnector(SharedMemoryConnector):
        def send(self, key, payload, **kw):
            raise RuntimeError("transport down")

    spec = EngineSpec("repro.engine.stub_engine:make_seedable",
                      {"name": "s", "pages": 0})
    rs = ReplicaSet("s", [None], lambda st, ev: None,
                    isolation="process", engine_spec=spec,
                    seed_connector=RefusingConnector(cross_process=True))
    rs.start()
    w0 = rs.workers()[0][1]
    assert w0.wait_ready(30.0)
    assert w0.seed_snapshot(_seed_pages(3)) == 3
    rid = rs.scale_up()                  # advisory: must not raise
    try:
        assert rs.n_replicas == 2
        assert rs._replicas[rid].prefix_snapshot() == []     # cold start
    finally:
        rs.stop()
        rs.join(30.0)


def test_jax_engine_process_replica_refused_on_device_parent(monkeypatch):
    """Once the parent holds an accelerator, a spawned child that rebuilds
    a JAX engine would land on the CPU: refuse at construction.  Host-only
    engines may still be process-isolated."""
    import jax

    class DeviceEngine(StubEngine):
        host_only = False                # stands in for a JAX engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = ServeConfig(stages={"s": StageConfig(isolation="process",
                                                  engine_spec=STUB)})
    with pytest.raises(ValueError, match="would run on the CPU"):
        Orchestrator(_graph(), {"s": DeviceEngine("s")}, config=config)
    orch = Orchestrator(_graph(), {"s": StubEngine("s")}, config=config)
    assert orch._proc_replicas == {"s": 1}
