"""Ahead-of-time compiles of the main path's Pallas kernels for one TPU v5e.

The TPU compiler is installed without a chip: each test lowers a kernel
at real widths for a described v5e device and compiles it, which catches
what interpret mode cannot (block shapes the tiling refuses, slices not
provably aligned, too much VMEM), or a runner program, whose memory the
compiler reckons.  Nothing runs.

This is the only test file that describes the chip.  The topology is
built inside a module fixture, never at import, so every xdist worker
collects the same tests and only the worker given this file loads the
TPU library.  The persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.configs.pipelines import _kv, tiny_lm
from repro.engine.kv_cache import PagedKVConfig
from repro.engine.runner import PagedRunner
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba1_scan
from repro.kernels.paged_attention import paged_attention
from repro.models import transformer as T


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                   # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles_at_internlm2_widths(one_chip, kv_dtype):
    cfg = get_config("internlm2_1_8b")
    kv = _kv(4)
    b, nkv, hd = 4, cfg.num_kv_heads, cfg.head_dim
    pool = ((kv.num_pages, nkv, kv.page_size, hd), jnp.dtype(kv_dtype))
    shapes = [((b, cfg.num_heads, hd), jnp.bfloat16), pool, pool,
              ((b, kv.max_pages_per_seq), jnp.int32), ((b,), jnp.int32)]
    if kv_dtype == "int8":
        scales = ((kv.num_pages, nkv, kv.page_size), jnp.float32)
        _compile(lambda q, k, v, bt, sl, ks, vs: paged_attention(
            q, k, v, bt, sl, k_scale_pages=ks, v_scale_pages=vs),
            one_chip, *shapes, scales, scales)
    else:
        _compile(paged_attention, one_chip, *shapes)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch,batch,pages,pp", [
    ("internlm2_1_8b", 8, 1600, 408),    # the PD decode stage: GQA 16/8
    ("qwen1_5_4b", 16, 560, 128),        # the unified engine: MHA 20/20
])
def test_paged_decode_compiles_at_cell_shapes(one_chip, arch, batch, pages,
                                              pp, kv_dtype):
    """The blocked kernel at the benchmark cells' decode shapes, so the
    VMEM of the block size it picks is checked by the chip's compiler."""
    cfg = get_config(arch)
    nkv, hd, page = cfg.num_kv_heads, cfg.head_dim, 16
    pool = ((pages, nkv, page, hd), jnp.dtype(kv_dtype))
    shapes = [((batch, cfg.num_heads, hd), jnp.bfloat16), pool, pool,
              ((batch, pp), jnp.int32), ((batch,), jnp.int32)]
    if kv_dtype == "int8":
        scales = ((pages, nkv, page), jnp.float32)
        _compile(lambda q, k, v, bt, sl, ks, vs: paged_attention(
            q, k, v, bt, sl, k_scale_pages=ks, v_scale_pages=vs),
            one_chip, *shapes, scales, scales)
    else:
        _compile(paged_attention, one_chip, *shapes)


def test_paged_decode_compiles_at_tiny_widths(one_chip):
    """The tiny pipeline stages' heads (32 lanes, float32 pools) are
    narrower than a lane row, which a page copy must move whole."""
    cfg = tiny_lm("t")
    kv = _kv(8)
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    assert hd % 128
    pool = ((kv.num_pages, nkv, kv.page_size, hd), jnp.float32)
    _compile(paged_attention, one_chip, ((8, cfg.num_heads, hd), jnp.float32),
             pool, pool, ((8, kv.max_pages_per_seq), jnp.int32),
             ((8,), jnp.int32))


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_runner_updates_pools_in_place(one_chip, monkeypatch, program,
                                       kv_dtype):
    """The runner's prefill and decode programs at the qwen1_5_4b cell's
    widths (40 layers, 560 pages of 16, 16 slots, chunk 64), the pools
    donated: the layer scan carries them whole and writes them in place,
    so the program holds no second pool.  Its temporaries stay under a
    tenth of the pools' bytes (K and V, and the int8 scales), and the
    outputs alias every pool byte."""
    cfg = get_config("qwen1_5_4b").replace(kv_cache_dtype=(
        "int8" if kv_dtype == "int8" else "bfloat16"))
    kv = PagedKVConfig(num_pages=560, page_size=16, max_pages_per_seq=128)
    r = object.__new__(PagedRunner)
    r.cfg, r.kv, r.quant = cfg, kv, kv_dtype == "int8"
    # the kernel as the chip runs it (the CPU would pick interpret mode)
    monkeypatch.setattr(ops, "paged_attention",
                        lambda q, k, v, bt, sl, **kw: paged_attention(
                            q, k, v, bt, sl, interpret=False, **kw))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    shape = (cfg.num_layers, kv.num_pages, cfg.num_kv_heads, kv.page_size,
             cfg.head_dim)
    pool = sds(shape, kv_dtype)
    scales = sds(shape[:-1], jnp.float32) if r.quant else None
    pool_bytes = 2 * pool.size * pool.dtype.itemsize
    if r.quant:
        pool_bytes += 2 * scales.size * 4
    b, pp = 16, kv.max_pages_per_seq
    if program == "decode":
        fn, rest = r._decode_impl, [sds((b, 1, cfg.d_model), cfg.dtype),
                                    sds((b, pp), jnp.int32),
                                    sds((b,), jnp.int32), sds((b,), bool)]
    else:
        fn, rest = r._prefill_impl, [sds((1, 64, cfg.d_model), jnp.float32),
                                     sds((pp,), jnp.int32),
                                     sds((), jnp.int32), sds((), jnp.int32)]
    c = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(
        params, pool, pool, scales, scales, *rest).compile()
    if program == "decode":
        assert "tpu_custom_call" in c.as_text()
    ma = c.memory_analysis()
    assert ma.temp_size_in_bytes < 0.1 * pool_bytes, (
        ma.temp_size_in_bytes, pool_bytes)
    assert ma.alias_size_in_bytes >= pool_bytes, (
        ma.alias_size_in_bytes, pool_bytes)


@pytest.mark.parametrize("sq,sk,nq,nkv,hd,causal", [
    (512, 512, 16, 8, 128, True),     # prefill at internlm2 head widths
    (32, 16, 4, 4, 32, False),        # DiT vocoder self / cross attention
    (64, 32, 4, 4, 32, False),
    (200, 24, 4, 4, 32, False),       # above 128, not a multiple of 128
])
def test_flash_attention_compiles(one_chip, sq, sk, nq, nkv, hd, causal):
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=causal),
             one_chip, ((2, sq, nq, hd), jnp.bfloat16),
             ((2, sk, nkv, hd), jnp.bfloat16),
             ((2, sk, nkv, hd), jnp.bfloat16))


@pytest.mark.parametrize("s", [1, 200])      # decode step, ragged prefill
def test_mamba1_scan_compiles_at_falcon_mamba_widths(one_chip, s):
    cfg = get_config("falcon_mamba_7b")
    di, n = cfg.d_inner, cfg.ssm_state
    assert (di, n) == (8192, 16)
    act = jnp.dtype(cfg.dtype)
    _compile(mamba1_scan, one_chip, ((1, s, di), act), ((1, s, di), act),
             ((di, n), jnp.float32), ((1, s, n), act), ((1, s, n), act),
             ((di,), jnp.float32), ((1, di, n), jnp.float32))
