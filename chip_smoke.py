"""Bring-up check: the stage-graph server's main path on one TPU.

    python chip_smoke.py

Every phase runs in this one process, since a chip belongs to one process:

  (a) device check: exit non-zero unless JAX's default backend is a TPU.
      There is no CPU fallback.
  (b) prefill -> decode at InternLM2-1.8B published widths (24 layers,
      d_model 2048, GQA 16/8, vocab 92544, bf16, random weights from a
      seed), through the Orchestrator with the threaded backend and the
      shm connector.  Greedy PD tokens must equal those of a unified
      single-engine run of the same prompts.
  (c) kernel check: one decode step over the same page pool with the
      Pallas paged-attention kernel and with the jnp reference.  The
      logits must agree within KERNEL_REL_TOL, and the compiled program
      must hold the kernel (``tpu_custom_call``).
  (d) any-to-any path: the qwen_omni pipeline (thinker -> talker -> DiT
      vocoder, streaming edge) at its built-in width; every request must
      finish with its audio latents.

Each phase prints its compile and wall seconds and the device's peak bytes
in use so far.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: bound on max|pallas - ref| / max|ref| over one decode step's logits.
#: Both paths round attention outputs to bf16 (8 mantissa bits, relative
#: step 2^-8 = 3.9e-3); the online and the full softmax sum in different
#: orders, so a few of those steps per layer may differ across 24 layers.
KERNEL_REL_TOL = 2e-2

PD_PROMPT_LENS = (6, 44, 82, 121, 161, 200)   # several 64-token chunks
PD_MAX_NEW = 16
PD_MAX_BATCH = 4
OMNI_REQUESTS = 3


class PhaseFailed(RuntimeError):
    """A smoke phase produced a wrong or incomplete result."""


def _serve(graph, engines, inputs, out_stage, timeout=900.0):
    """Serve ``inputs`` through the threaded Orchestrator and return each
    request's ``out_stage`` outputs; raise if any request failed."""
    from repro.core.config import ServeConfig
    from repro.core.orchestrator import Orchestrator
    from repro.core.request import Request
    from repro.launch.serve import serve_status

    orch = Orchestrator(graph, engines,
                        config=ServeConfig(backend="threaded"))
    reqs = [Request(inputs=i) for i in inputs]
    for r in reqs:
        orch.submit(r)
    orch.run(timeout=timeout)
    if serve_status(orch, reqs):
        raise PhaseFailed(f"{out_stage}: not every request was served")
    return [r.outputs[out_stage] for r in reqs]


def pd_prompts(vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PD_PROMPT_LENS]


def pd_vs_unified(cfg, seed: int = 0):
    """Phase (b): serve the prompts prefill -> decode over the shm
    connector, then through one unified engine with the same params, and
    require identical greedy tokens.  Returns (tokens, unified engine)."""
    from repro.configs.pipelines import _kv, build_pd_disaggregated
    from repro.core.graph import StageGraph
    from repro.core.stage import StageSpec
    from repro.engine.ar_engine import AREngine
    from repro.engine.sampling import SamplingParams

    prompts = pd_prompts(cfg.vocab_size, seed)
    inputs = [{"tokens": p} for p in prompts]
    graph, engines, bundle = build_pd_disaggregated(
        cfg, max_batch=PD_MAX_BATCH, max_new=PD_MAX_NEW, temperature=0.0,
        connector="shm", seed=seed)
    pd = [out[0]["tokens"].tolist()
          for out in _serve(graph, engines, inputs, "decode")]

    unified = AREngine(
        "unified", cfg, bundle["params"], kv=_kv(PD_MAX_BATCH),
        max_batch=PD_MAX_BATCH, seed=seed,
        default_sampling=SamplingParams(max_new_tokens=PD_MAX_NEW,
                                        temperature=0.0))
    one = StageGraph()
    one.add_stage(StageSpec("unified", "ar", is_output=True))
    ref = [out[0]["tokens"].tolist()
           for out in _serve(one, {"unified": unified}, inputs, "unified")]

    for i, (got, want) in enumerate(zip(pd, ref)):
        if len(got) != PD_MAX_NEW or got != want:
            raise PhaseFailed(f"request {i} (prompt {len(prompts[i])} "
                              f"tokens): PD {got} != unified {want}")
    return pd, unified


def kernel_check(runner, prompts):
    """Phase (c): write the prompts' KV into ``runner``'s pool, then run
    one decode step compiled with the Pallas kernel and with the jnp
    reference on that pool.  Returns (relative error, argmax agreement)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    kv, page = runner.kv, runner.kv.page_size
    tables = np.zeros((len(prompts), kv.max_pages_per_seq), np.int32)
    free = 0
    for i, p in enumerate(prompts):
        n_pages = -(-(len(p) + 1) // page)
        tables[i, :n_pages] = np.arange(free, free + n_pages)
        free += n_pages
        for start in range(0, len(p), 64):
            emb = runner.embed(p[start:start + 64])
            emb = np.pad(emb, ((0, 64 - emb.shape[0]), (0, 0)))
            runner.prefill_chunk(jnp.asarray(emb)[None], tables[i], start,
                                 min(64, len(p) - start))
    last = np.array([p[-1] for p in prompts], np.int32)
    embeds = jnp.asarray(runner.embed(last)[:, None],
                         jnp.dtype(runner.cfg.dtype))
    args = (runner.params, runner.k_pages, runner.v_pages, runner.k_scales,
            runner.v_scales, embeds, jnp.asarray(tables),
            jnp.asarray([len(p) for p in prompts], jnp.int32),
            jnp.ones(len(prompts), bool))
    logits = {}
    for backend in ("pallas", "ref"):
        def decode(*a):
            # a new callable per backend: jit reuses a known callable's trace
            return runner._decode_impl(*a)

        ops.set_backend(backend)
        try:
            compiled = jax.jit(decode).lower(*args).compile()
        finally:
            ops.set_backend("auto")
        has_kernel = "tpu_custom_call" in compiled.as_text()
        if has_kernel != (backend == "pallas"):
            raise PhaseFailed(f"{backend} decode: tpu_custom_call "
                              f"present={has_kernel}")
        logits[backend] = np.asarray(compiled(*args)[0], np.float32)
    got, want = logits["pallas"], logits["ref"]
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise PhaseFailed("non-finite decode logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    if rel > KERNEL_REL_TOL:
        raise PhaseFailed(f"Pallas vs ref logits: max rel err {rel:.3e} > "
                          f"{KERNEL_REL_TOL:.0e}")
    return rel, agree


def omni_path(seed: int = 0):
    """Phase (d): qwen_omni requests through thinker -> talker -> DiT
    vocoder; every request must end with finite latents for every talker
    token.  Returns the latent frames per request."""
    from repro.configs.pipelines import build_qwen_omni

    graph, engines, bundle = build_qwen_omni(max_batch=4, seed=seed)
    rng = np.random.default_rng(seed)
    inputs = [{"tokens": rng.integers(0, 200, size=n).astype(np.int32)}
              for n in rng.integers(6, 24, size=OMNI_REQUESTS)]
    frames = []
    for i, chunks in enumerate(_serve(graph, engines, inputs, "vocoder")):
        lat = [np.asarray(c["latent"]) for c in chunks]
        n = sum(x.shape[0] for x in lat)
        if n != 2 * bundle["talker_tokens"] or not all(
                np.isfinite(x).all() for x in lat):
            raise PhaseFailed(f"request {i}: {n} latent frames, finite="
                              f"{all(np.isfinite(x).all() for x in lat)}")
        frames.append(n)
    return frames


class _CompileClock:
    """Seconds JAX spent in backend compilation (persistent-cache reads
    included) and persistent-cache hits, since construction."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no libtpu logs in /tmp
    import jax

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    # (a) device check
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not a "
              f"TPU; nothing was run", file=sys.stderr)
        return 1
    from repro.configs.base import get_config
    from repro.kernels import ops

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}  kernels={ops.get_backend()}  "
          f"compile cache: {cache_dir}", flush=True)
    clock = _CompileClock()

    def phase(name, fn):
        c0, h0, t0 = clock.seconds, clock.hits, time.perf_counter()
        result = fn()
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"phase {name}: passed  compile_s={clock.seconds - c0:.2f} "
              f"cache_hits={clock.hits - h0}  "
              f"wall_s={time.perf_counter() - t0:.2f}  "
              f"peak_bytes_in_use={peak}", flush=True)
        return result

    cfg = get_config("internlm2_1_8b")
    print(f"(b) {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} vocab={cfg.vocab_size} "
          f"dtype={cfg.dtype}; prompts {PD_PROMPT_LENS}, "
          f"{PD_MAX_NEW} greedy tokens each", flush=True)
    tokens, unified = phase("b pd_vs_unified", lambda: pd_vs_unified(cfg))
    print(f"(b) {len(tokens)} requests finished, none failed; PD tokens == "
          f"unified tokens", flush=True)
    rel, agree = phase("c kernel_check", lambda: kernel_check(
        unified.runner, pd_prompts(cfg.vocab_size)))
    print(f"(c) tpu_custom_call in the Pallas decode HLO; logits max rel err "
          f"{rel:.3e} <= {KERNEL_REL_TOL:.0e}; argmax agreement {agree:.2f}",
          flush=True)
    frames = phase("d qwen_omni", omni_path)
    print(f"(d) {len(frames)} qwen_omni requests finished with latent "
          f"frames {frames}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
