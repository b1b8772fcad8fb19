"""DiT model + diffusion engine tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.diffusion_engine import DiffusionEngine
from repro.models.dit import DiTConfig, dit_forward, init_dit, sample


CFG = DiTConfig(num_layers=2, d_model=64, num_heads=2, d_ff=128, in_dim=16,
                cond_dim=64, num_steps=4)


def test_forward_shapes():
    p = init_dit(CFG, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 10, 16))
    cond = jax.random.normal(jax.random.PRNGKey(2), (3, 7, 64))
    v = dit_forward(CFG, p, x, jnp.full((3,), 0.5), cond)
    assert v.shape == (3, 10, 16)
    assert bool(jnp.isfinite(v).all())


def test_conditioning_matters():
    p = init_dit(CFG, jax.random.PRNGKey(0))
    # zero-init out_proj means v==0 at init; nudge it so cond flows through
    p["out_proj"] = jnp.ones_like(p["out_proj"]) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 16))
    c1 = jax.random.normal(jax.random.PRNGKey(2), (1, 7, 64))
    c2 = jax.random.normal(jax.random.PRNGKey(3), (1, 7, 64))
    v1 = dit_forward(CFG, p, x, jnp.full((1,), 0.5), c1)
    v2 = dit_forward(CFG, p, x, jnp.full((1,), 0.5), c2)
    assert not np.allclose(np.asarray(v1), np.asarray(v2))


def test_sampler_deterministic_given_key():
    p = init_dit(CFG, jax.random.PRNGKey(0))
    cond = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 64))
    k = jax.random.PRNGKey(5)
    a = sample(CFG, p, cond, 8, k)
    b = sample(CFG, p, cond, 8, k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cache_interval_1_equals_exact():
    p = init_dit(CFG, jax.random.PRNGKey(0))
    cond = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 64))
    k = jax.random.PRNGKey(5)
    a = sample(CFG, p, cond, 8, k, cache_interval=1)
    b = sample(CFG, p, cond, 8, k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_batches_same_bucket():
    p = init_dit(CFG, jax.random.PRNGKey(0))
    eng = DiffusionEngine("d", CFG, p, max_batch=4)
    cond = np.random.randn(6, 64).astype(np.float32)
    for i in range(3):
        eng.enqueue(i, {"cond": cond, "out_len": 8})
    evs = eng.step()
    assert len(evs) == 3                       # one batch, three results
    assert eng.steps == 1
    for ev in evs:
        assert ev.kind == "finished"
        assert ev.payload["latent"].shape == (8, 16)


def test_engine_respects_max_batch():
    p = init_dit(CFG, jax.random.PRNGKey(0))
    eng = DiffusionEngine("d", CFG, p, max_batch=2)
    cond = np.random.randn(6, 64).astype(np.float32)
    for i in range(5):
        eng.enqueue(i, {"cond": cond, "out_len": 8})
    done = []
    while eng.has_work:
        done += eng.step()
    assert len(done) == 5
    assert eng.steps == 3                      # ceil(5/2)


def test_engine_mixed_chunk_shapes_in_queue():
    """Jobs with different cond lengths can coexist in the queue (a
    streaming talker's final short chunk lands among full-size chunks).
    The dequeue must remove by identity — a fieldwise job comparison
    would elementwise-compare mismatched cond arrays and raise."""
    p = init_dit(CFG, jax.random.PRNGKey(0))
    eng = DiffusionEngine("d", CFG, p, max_batch=4)
    short = np.random.randn(3, 64).astype(np.float32)
    full = np.random.randn(6, 64).astype(np.float32)
    eng.enqueue(0, {"cond": short, "out_len": 4,
                    "chunk_index": 1, "is_last_chunk": True})
    for i in range(1, 4):
        eng.enqueue(i, {"cond": full.copy(), "out_len": 8,
                        "chunk_index": 0, "is_last_chunk": False})
    done = []
    while eng.has_work:
        done += eng.step()
    assert sorted(ev.req_id for ev in done) == [0, 1, 2, 3]
    shapes = {ev.req_id: ev.payload["latent"].shape for ev in done}
    assert shapes[0] == (4, 16)
    assert all(shapes[i] == (8, 16) for i in (1, 2, 3))


def test_engine_finishes_a_stream_in_chunk_order():
    """A stream's short last chunk sits in its own shape bucket; even when
    that bucket is the largest, the chunk must not run (and complete the
    request) before the request's earlier chunks."""
    p = init_dit(CFG, jax.random.PRNGKey(0))
    eng = DiffusionEngine("d", CFG, p, max_batch=4)
    full = np.random.randn(6, 64).astype(np.float32)
    short = np.random.randn(3, 64).astype(np.float32)
    for c in range(3):                         # request 0: 2 full + 1 short
        eng.enqueue(0, {"cond": short if c == 2 else full,
                        "out_len": 4 if c == 2 else 8, "chunk_index": c,
                        "is_last_chunk": c == 2})
    for i in (1, 2):                           # single short requests
        eng.enqueue(i, {"cond": short, "out_len": 4})
    done = []
    while eng.has_work:
        done += eng.step()
    assert [ev.chunk_index for ev in done if ev.req_id == 0] == [0, 1, 2]
    assert sorted(ev.req_id for ev in done) == [0, 0, 0, 1, 2]
