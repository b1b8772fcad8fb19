"""Rehearsals that need no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config>   # e.g. qwen1_5_4b

Compiles the configuration's prefill and decode programs, at published
widths with the pools the configuration sizes, for a described TPU v5e
(nothing runs), and prints each program's ``memory_analysis`` beside the
configuration's memory reckoning.  The end-to-end rehearsal at smoke
widths on the CPU is ``bench/tests/test_cells.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def aot(config: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.spec import model_module
    from repro.engine.kv_cache import PagedKVConfig
    from repro.engine.runner import PagedRunner
    from repro.kernels import ops
    from repro.kernels import paged_attention as pa

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    kind = model_module(cfg)
    mc = kind.model_config(cfg)
    s = cfg["serving"]
    page = s["page_size"]
    pools = ({"prefill": s["prefill"]["pages"], "decode": s["decode"]["pages"]}
             if "prefill" in s else {"unified": s["pages"]})
    batch = ({"prefill": s["prefill"]["max_batch"],
              "decode": s["decode"]["max_batch"]}
             if "prefill" in s else {"unified": s["max_batch"]})
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)

    # the kernel as the chip runs it (the CPU would pick interpret mode)
    ops.paged_attention = lambda q, k, v, bt, sl, **kw: pa.paged_attention(
        q, k, v, bt, sl, interpret=False, **kw)
    params = jax.tree.map(lambda shp: sds(shp, cfg["torch_dtype"]),
                          kind.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    for stage, pages in pools.items():
        kv = PagedKVConfig(num_pages=pages, page_size=page,
                           max_pages_per_seq=s["max_seq"] // page)
        r = object.__new__(PagedRunner)
        r.cfg, r.kv, r.quant = mc, kv, False
        pool = sds(kind.kv_pool_shape(cfg, pages), cfg["torch_dtype"])
        pp = kv.max_pages_per_seq
        B = batch[stage]
        progs = {
            "prefill": (r._prefill_impl, (params, pool, pool, None, None,
                                          sds((1, s["chunk"], mc.d_model),
                                              "float32"),
                                          sds((pp,), "int32"),
                                          sds((), "int32"), sds((), "int32"))),
            "decode": (r._decode_impl, (params, pool, pool, None, None,
                                        sds((B, 1, mc.d_model),
                                            cfg["torch_dtype"]),
                                        sds((B, pp), "int32"),
                                        sds((B,), "int32"), sds((B,), bool))),
        }
        for prog, (fn, args) in progs.items():
            t = time.perf_counter()
            c = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
            ma = c.memory_analysis()
            kernel = "tpu_custom_call" in c.as_text()
            print(f"{config} {stage} pool={pages} pages, {prog} program: "
                  f"compiled in {time.perf_counter() - t:.1f}s; "
                  f"arguments={ma.argument_size_in_bytes} "
                  f"outputs={ma.output_size_in_bytes} "
                  f"temporaries={ma.temp_size_in_bytes} "
                  f"aliased={ma.alias_size_in_bytes} paged kernel={kernel}",
                  flush=True)
    print("reckoning:", json.dumps(cfg["memory"], indent=1))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    aot(ap.parse_args().config)
