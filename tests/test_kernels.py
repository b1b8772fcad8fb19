"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed with interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba1_scan
from repro.kernels.paged_attention import pages_per_block, paged_attention

KEYS = jax.random.split(jax.random.PRNGKey(7), 16)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,nq,nkv,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 128, 8, 1, 128),    # MQA
    (1, 200, 4, 2, 64),     # length not a multiple of the block
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(b, s, nq, nkv, hd, causal, window, dtype):
    q = jax.random.normal(KEYS[0], (b, s, nq, hd), dtype)
    k = jax.random.normal(KEYS[1], (b, s, nkv, hd), dtype)
    v = jax.random.normal(KEYS[2], (b, s, nkv, hd), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("sq,sk", [(200, 24), (40, 8), (64, 130)])
def test_flash_attention_cross_ragged(sq, sk):
    """DiT cross-attention shapes: query and key lengths differ and are not
    multiples of the block; padding must not change the result."""
    q = jax.random.normal(KEYS[0], (2, sq, 4, 32), jnp.float32)
    k = jax.random.normal(KEYS[1], (2, sk, 4, 32), jnp.float32)
    v = jax.random.normal(KEYS[2], (2, sk, 4, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nq,nkv,hd,page,pp", [
    (2, 8, 2, 64, 8, 4),
    (3, 4, 4, 128, 16, 2),
    (1, 16, 2, 64, 8, 8),
])
@pytest.mark.parametrize("window", [0, 16])
def test_paged_attention_sweep(b, nq, nkv, hd, page, pp, window, dtype):
    P = b * pp + 2
    q = jax.random.normal(KEYS[3], (b, nq, hd), dtype)
    kp = jax.random.normal(KEYS[4], (P, nkv, page, hd), dtype)
    vp = jax.random.normal(KEYS[5], (P, nkv, page, hd), dtype)
    bt = jax.random.permutation(KEYS[6], P)[:b * pp].reshape(b, pp)
    bt = bt.astype(jnp.int32)
    max_len = page * pp
    sl = jax.random.randint(KEYS[7], (b,), 1, max_len + 1).astype(jnp.int32)
    got = paged_attention(q, kp, vp, bt, sl, window=window, interpret=True)
    want = ref.paged_attention(q, kp, vp, bt, sl, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_attention_int8_dequant():
    """Quantized page pool with in-kernel dequant vs dequantized-ref."""
    b, nq, nkv, hd, page, pp = 2, 8, 2, 64, 8, 4
    P = b * pp + 2
    q = jax.random.normal(KEYS[3], (b, nq, hd), jnp.float32)
    kf = jax.random.normal(KEYS[4], (P, nkv, page, hd), jnp.float32)
    vf = jax.random.normal(KEYS[5], (P, nkv, page, hd), jnp.float32)

    def quant(x):
        s = jnp.max(jnp.abs(x), axis=-1) / 127.0 + 1e-8
        return jnp.round(x / s[..., None]).astype(jnp.int8), s
    kq, ks = quant(kf)
    vq, vs = quant(vf)
    bt = jax.random.permutation(KEYS[6], P)[:b * pp].reshape(b, pp)
    bt = bt.astype(jnp.int32)
    sl = jnp.array([13, 29], jnp.int32)
    got = paged_attention(q, kq, vq, bt, sl, k_scale_pages=ks,
                          v_scale_pages=vs, interpret=True)
    want = ref.paged_attention(q, kq, vq, bt, sl, k_scale_pages=ks,
                               v_scale_pages=vs)
    exact = ref.paged_attention(q, kf, vf, bt, sl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and close to the unquantized result
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               rtol=0.05, atol=0.05)


# Several blocks per sequence: at page 16, hd 128 a block is 16 pages
# (256 tokens), so pp = 40 makes 3 blocks, the last with 8 of 16 slots.
_BLK = dict(b=3, nq=4, nkv=2, hd=128, page=16, pp=40)


@pytest.mark.parametrize("case,seq_lens,window,dtype,poison", [
    ("inactive_slot", (0, 300, 17), 0, jnp.float32, False),
    ("block_edge", (256, 257, 512), 0, jnp.float32, False),
    ("block_edge_bf16", (256, 257, 512), 0, jnp.bfloat16, False),
    ("ragged_last_block", (640, 630, 513), 0, jnp.float32, False),
    ("window_skips_blocks", (600, 300, 40), 100, jnp.float32, True),
    ("int8_blocks", (600, 257, 1), 0, jnp.int8, True),
    ("poisoned_tail", (300, 17, 0), 0, jnp.float32, True),
])
def test_paged_attention_blocks(case, seq_lens, window, dtype, poison):
    """The blocked grid against the oracle: sequences ending on and just
    past a block edge, a last block that ``pp`` fills only in part, a
    window with whole blocks before it, int8 pools across blocks, and an
    inactive slot (finite zeros).  With ``poison``, every table entry of a
    page that holds no context points at a page of NaN (NaN scales for
    int8): the output stays equal to the oracle's on a clean table, so no
    such page is read."""
    _check_blocks(seq_lens, window, dtype, poison)


@pytest.mark.parametrize("layers,layer,seq_lens,window,dtype", [
    (3, 0, (600, 257, 1), 0, jnp.float32),
    (3, 1, (600, 300, 40), 100, jnp.int8),
    (3, 2, (600, 300, 40), 100, jnp.bfloat16),
    (3, 2, (0, 257, 512), 100, jnp.int8),
])
def test_paged_attention_reads_layer_of_whole_pool(layers, layer, seq_lens,
                                                    window, dtype):
    """A layer scan hands the kernel every layer's pool, (L, P, nkv, page,
    hd), and the layer to read: the output equals the oracle's on that
    layer, at the first, a middle and the last layer, with int8 scales and
    a sliding window.  Every other layer holds NaN (NaN scales for int8),
    so no page of another layer is read."""
    _check_blocks(seq_lens, window, dtype, True, layers=layers, layer=layer)


def _check_blocks(seq_lens, window, dtype, poison, layers=None, layer=None):
    b, nq, nkv, hd, page, pp = (_BLK[k] for k in
                                ("b", "nq", "nkv", "hd", "page", "pp"))
    quant = dtype == jnp.int8
    ppb = pages_per_block(page, pp, nkv, hd, jnp.dtype(dtype).itemsize,
                          quant)
    assert ppb * page == 256 and pp % ppb
    P = b * pp + 1
    poison_page = P - 1
    q = jax.random.normal(KEYS[3], (b, nq, hd), jnp.float32)
    kv = jax.random.normal(KEYS[4], (2, P, nkv, page, hd), jnp.float32)
    scales = {}
    if quant:
        s = jnp.max(jnp.abs(kv), axis=-1) / 127.0 + 1e-8
        pools = jnp.round(kv / s[..., None]).astype(jnp.int8)
        s = s.at[:, poison_page].set(jnp.nan)
    else:
        pools = kv.astype(dtype).at[:, poison_page].set(jnp.nan)
    if layers is not None:
        # the pools of the other layers: NaN, or random int8 at NaN scales
        def whole(x, fill):
            return jnp.full((2, layers) + x.shape[1:], fill,
                            x.dtype).at[:, layer].set(x)
        pools = whole(pools, 1 if quant else jnp.nan)
        if quant:
            s = whole(s, jnp.nan)
    if quant:
        scales = dict(k_scale_pages=s[0], v_scale_pages=s[1])
    bt = jax.random.permutation(KEYS[6], P - 1).reshape(b, pp)
    bt = bt.astype(jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    slot = jnp.arange(pp)[None, :]
    held = slot * page < sl[:, None]
    if window:
        held &= (slot + 1) * page > sl[:, None] - window
    table = jnp.where(held, bt, poison_page) if poison else bt
    got = paged_attention(q, pools[0], pools[1], table, sl, window=window,
                          layer=layer, interpret=True, **scales)
    want = ref.paged_attention(q, pools[0], pools[1], bt, sl, window=window,
                               layer=layer, **scales)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    live = np.asarray(sl) > 0
    np.testing.assert_array_equal(got[~live], 0.0)
    tol = _tol(jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32)
    np.testing.assert_allclose(got[live], want[live], **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bt,s,di,n", [(1, 64, 128, 8), (2, 128, 256, 16)])
def test_mamba_scan_sweep(bt, s, di, n, dtype):
    x = (jax.random.normal(KEYS[8], (bt, s, di)) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(KEYS[9], (bt, s, di))) * 0.1
          ).astype(dtype)
    A = -jnp.exp(jax.random.normal(KEYS[10], (di, n)) * 0.3)
    B = jax.random.normal(KEYS[11], (bt, s, n)).astype(dtype)
    C = jax.random.normal(KEYS[12], (bt, s, n)).astype(dtype)
    D = jnp.ones((di,))
    y1, h1 = mamba1_scan(x, dt, A, B, C, D, bd=128, bs=32, interpret=True)
    y2, h2 = ref.mamba1_scan(x, dt, A, B, C, D)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-2,
                               atol=1e-2)


def test_mamba_scan_state_continuation():
    """Scanning two halves with carried state == scanning the whole."""
    bt, s, di, n = 1, 64, 128, 8
    x = jax.random.normal(KEYS[13], (bt, s, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(KEYS[14], (bt, s, di))) * 0.1
    A = -jnp.exp(jax.random.normal(KEYS[15], (di, n)) * 0.3)
    B = jax.random.normal(KEYS[0], (bt, s, n))
    C = jax.random.normal(KEYS[1], (bt, s, n))
    D = jnp.ones((di,))
    y_full, h_full = ref.mamba1_scan(x, dt, A, B, C, D)
    h = None
    ys = []
    for lo, hi in ((0, 32), (32, 64)):
        y, h = mamba1_scan(x[:, lo:hi], dt[:, lo:hi], A, B[:, lo:hi],
                           C[:, lo:hi], D, h, bd=128, bs=32, interpret=True)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_trainable_grads():
    """jax.grad through the Pallas kernel (custom VJP, recompute backward)
    must match grads of the oracle."""
    from repro.kernels import ops
    b, s, nq, nkv, hd = 1, 64, 4, 2, 32
    q = jax.random.normal(KEYS[5], (b, s, nq, hd))
    k = jax.random.normal(KEYS[6], (b, s, nkv, hd))
    v = jax.random.normal(KEYS[7], (b, s, nkv, hd))

    def loss_kernel(q, k, v):
        return jnp.sum(ops.flash_attention_trainable(q, k, v, True, 0) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.flash_attention(q, k, v, causal=True) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_chunk_attention_matches_flash():
    """chunk_attention over a full history == flash_attention causal."""
    b, s, nq, nkv, hd = 1, 64, 4, 2, 32
    q = jax.random.normal(KEYS[2], (b, s, nq, hd))
    k = jax.random.normal(KEYS[3], (b, s, nkv, hd))
    v = jax.random.normal(KEYS[4], (b, s, nkv, hd))
    want = ref.flash_attention(q, k, v, causal=True)
    got = ref.chunk_attention(q, k, v, jnp.zeros((b,), jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
