"""Connector round-trip + stats + channel API tests (incl. hypothesis
payload sweep), the deprecated put/get/delete shims, and the typed
TransferTimeout."""
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.extra import numpy as hnp

from repro.connector.base import TransferTimeout
from repro.connector.mooncake import MooncakeConnector, make_connector


@pytest.mark.parametrize("kind", ["inline", "shm", "mooncake"])
def test_roundtrip_nested(kind):
    conn = make_connector(kind)
    payload = {"tokens": np.arange(7, dtype=np.int32),
               "hidden": np.random.randn(7, 16).astype(np.float32),
               "meta": {"n": 3, "name": "x"}}
    conn.send("k1", payload)
    got = conn.recv("k1", timeout=1.0)
    np.testing.assert_array_equal(got["tokens"], payload["tokens"])
    np.testing.assert_array_equal(got["hidden"], payload["hidden"])
    assert got["meta"] == payload["meta"]
    assert conn.stats.calls == 1
    assert conn.stats.bytes >= payload["tokens"].nbytes + payload["hidden"].nbytes
    assert conn.metadata("k1")["nbytes"] == conn.stats.bytes
    conn.release("k1")
    assert conn.metadata("k1") is None


@pytest.mark.parametrize("kind", ["inline", "shm", "mooncake", "segment"])
def test_roundtrip_keeps_bfloat16(kind):
    """bf16 KV (a published-width PD payload) must come back as bf16,
    not as the void dtype its ``dtype.str`` names."""
    import jax.numpy as jnp

    from repro.connector import shm_transport
    kv = np.arange(12, dtype=np.float32).reshape(3, 4).astype(jnp.bfloat16)
    payload = {"kv_k": kv, "n": 3}
    if kind == "segment":                # the cross-process manifest path
        _, manifest = shm_transport.write_segment(payload)
        got = shm_transport.read_and_release(manifest)
    else:
        conn = make_connector(kind)
        conn.send("k", payload)
        got = conn.recv("k", timeout=1.0)
        conn.release("k")
    assert got["kv_k"].dtype == kv.dtype
    np.testing.assert_array_equal(got["kv_k"], kv)


@given(hnp.arrays(dtype=st.sampled_from([np.float32, np.int32, np.float16]),
                  shape=hnp.array_shapes(min_dims=1, max_dims=3,
                                         max_side=16)))
@settings(max_examples=40, deadline=None)
def test_roundtrip_arbitrary_arrays(arr):
    for kind in ("inline", "shm", "mooncake"):
        conn = make_connector(kind)
        conn.send("k", {"a": arr})
        got = conn.recv("k", timeout=1.0)["a"]
        conn.release("k")
        np.testing.assert_array_equal(np.asarray(got), arr)


def test_mooncake_cost_model():
    conn = MooncakeConnector(bandwidth_gbps=10.0, latency_s=1e-4)
    big = np.zeros((1000, 1000), np.float32)     # 4 MB
    conn.send("k", big)
    conn.recv("k", timeout=1.0)
    conn.release("k")
    # send + recv hops: 2 * (latency + 4e6/10e9)
    expected = 2 * (1e-4 + big.nbytes / 10e9)
    assert abs(conn.stats.modeled_time - expected) < 1e-6


def test_keys_are_independent():
    conn = make_connector("shm")
    conn.send("a", np.ones(3))
    conn.send("b", np.zeros(3))
    np.testing.assert_array_equal(conn.recv("a", timeout=1.0), np.ones(3))
    np.testing.assert_array_equal(conn.recv("b", timeout=1.0), np.zeros(3))
    conn.release("a")
    conn.release("b")


# ---- deprecated put/get/delete shims (one-release compatibility) ----------

def test_legacy_trio_warns_and_forwards_to_channel_api():
    conn = make_connector("shm")
    with pytest.warns(DeprecationWarning, match=r"put\(\) is deprecated"):
        conn.put("k", np.ones(3))              # noqa: DEP001 (shim test)
    assert conn.poll("k")                          # landed via send()
    with pytest.warns(DeprecationWarning, match=r"get\(\) is deprecated"):
        np.testing.assert_array_equal(
            conn.get("k"), np.ones(3))         # noqa: DEP001 (shim test)
    with pytest.warns(DeprecationWarning, match=r"delete\(\) is deprecated"):
        conn.delete("k")                       # noqa: DEP001 (shim test)
    assert conn.metadata("k") is None
    assert conn.resident_bytes == 0                # single accounting path


def test_legacy_get_missing_key_keeps_keyerror_contract():
    conn = make_connector("inline")
    with pytest.warns(DeprecationWarning):
        with pytest.raises(KeyError):
            conn.get("never-sent")             # noqa: DEP001 (shim test)


# ---- typed TransferTimeout (key + edge attribution) -----------------------

def test_recv_timeout_is_typed_and_attributable():
    conn = make_connector("inline")
    with pytest.raises(TransferTimeout) as ei:
        conn.recv("missing", timeout=0.01)
    e = ei.value
    assert isinstance(e, TimeoutError)             # old catch sites survive
    assert e.key == "missing" and e.edge is None
    assert e.connector == "inline" and e.timeout == 0.01
    e2 = e.with_edge("prefill->decode")
    assert e2.key == "missing" and e2.edge == "prefill->decode"
    assert "prefill->decode" in str(e2) and "missing" in str(e2)


def test_transfer_timeout_fails_one_request_naming_the_edge():
    """A timed-out edge transfer fails ONLY the owning request, with the
    edge in the failure message; the stage worker keeps serving."""
    from repro.connector.shm import SharedMemoryConnector
    from repro.core.graph import StageGraph
    from repro.core.orchestrator import Orchestrator
    from repro.core.request import Request
    from repro.core.stage import StageSpec
    from repro.engine.stub_engine import make_stub

    class BlackholeConnector(SharedMemoryConnector):
        """send() publishes nowhere — every recv waits out its timeout."""

        def send(self, key, payload):
            from repro.connector.base import TransferHandle
            return TransferHandle(key=key, nbytes=0, t_send=time.time())

    graph = StageGraph()
    graph.add_stage(StageSpec("a", "custom"))
    graph.add_stage(StageSpec("b", "custom", is_output=True))
    graph.add_edge("a", "b", lambda d, p: p, connector="shm")
    from repro.core.config import ServeConfig
    orch = Orchestrator(graph, {"a": make_stub("a"), "b": make_stub("b")},
                        connectors={"shm": BlackholeConnector()},
                        config=ServeConfig(recv_timeout=0.05))
    orch.submit(Request(inputs={"x": 1}))
    done = orch.run(timeout=30.0)
    assert len(done) == 1 and done[0].failed
    assert "a->b" in done[0].failed and "timed out" in done[0].failed
    assert orch.worker_error is None       # the worker survived the timeout


# ---- async channel API (send -> handle, recv blocks, release evicts) ------

@pytest.mark.parametrize("kind", ["inline", "shm", "mooncake"])
def test_channel_recv_blocks_until_send(kind):
    import threading
    conn = make_connector(kind)
    got = {}

    def consumer():
        got["v"] = conn.recv("k", timeout=5.0)

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.02)                       # consumer is already waiting
    handle = conn.send("k", {"a": np.arange(4, dtype=np.int32)})
    t.join(timeout=5.0)
    assert not t.is_alive()
    np.testing.assert_array_equal(got["v"]["a"], np.arange(4))
    assert handle.key == "k" and handle.nbytes >= 16
    assert conn.poll("k")
    conn.release("k")
    assert not conn.poll("k") and conn.metadata("k") is None


def test_channel_recv_timeout():
    conn = make_connector("inline")
    with pytest.raises(TimeoutError):
        conn.recv("never-sent", timeout=0.01)


def test_shm_pool_accounting_tracks_lifetimes():
    conn = make_connector("shm")
    conn.send("a", np.ones(100, np.float64))           # 800 B resident
    conn.send("b", np.ones(50, np.float64))            # +400 B
    assert conn.resident_bytes == 1200
    conn.release("a")
    assert conn.resident_bytes == 400
    assert conn.peak_resident_bytes == 1200
    conn.release("b")
    assert conn.resident_bytes == 0


def test_mooncake_resident_object_accounting():
    conn = MooncakeConnector()
    conn.send("a", np.ones(3))
    conn.send("b", np.ones(3))
    conn.release("a")
    assert conn.resident_objects == 1
    assert conn.peak_resident_objects == 2
