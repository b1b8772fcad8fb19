"""Multi-replica stage serving: scaling, cache-affinity routing, autoscale.

Three measurements (paper §3.2, flexible resource allocation):

  A. replica scaling — a slowed bottleneck stage under Poisson overload,
     served by 1 vs 2 replicas.  Dwell is a sleep (releases the GIL, like
     real device work), so 2 replicas should approach 2x finished/s.
  B. cache-affinity routing — shared-prefix traffic over 2 replicas.
     ``affinity`` routes each prefix family to the replica already holding
     its pages, keeping the aggregate prefix hit rate at the 1-replica
     level; ``round_robin`` splits families across replicas and pays the
     cold-miss on both.
  C. metrics-driven autoscale — a 2-stage pipeline with one hot stage,
     static even replica split vs the ScalingController moving a replica
     from the cold stage to the bottleneck at runtime (same budget).

  D. process isolation overhead — the same slowed-stage workload served
     by 2 ``isolation="process"`` replicas: spawned workers, items over
     named shared-memory segments.  Compares against B's threaded
     2-replica rate to price the cross-process hop.  The stage is a
     host-only stub: a process replica that needs JAX is refused once
     the parent holds an accelerator.

  PYTHONPATH=src python -m benchmarks.bench_replicas [--smoke]
      [--json OUT.json]
"""
from __future__ import annotations

import argparse
import queue as _queue
import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs.pipelines import tiny_lm
from repro.core.config import EngineSpec, ServeConfig, StageConfig
from repro.core.graph import StageGraph
from repro.core.orchestrator import Orchestrator
from repro.core.request import Request
from repro.core.scaling import ScalingConfig, ScalingController
from repro.core.stage import StageSpec
from repro.engine.ar_engine import AREngine
from repro.engine.kv_cache import PagedKVConfig
from repro.engine.sampling import SamplingParams
from repro.engine.stub_engine import StubEngine
from repro.models import transformer as T


def _poisson_serve(orch: Orchestrator, inputs_list, rate_hz: float,
                   seed: int, time_limit: float = 60.0):
    """Submit a Poisson stream, consume completions; returns (reqs, wall)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, len(inputs_list)))
    orch.start()
    reqs: List[Request] = []
    done = i = 0
    t0 = time.perf_counter()
    while done < len(inputs_list):
        now = time.perf_counter() - t0
        while i < len(inputs_list) and arrivals[i] <= now:
            reqs.append(Request(inputs=inputs_list[i]))
            orch.submit(reqs[-1])
            i += 1
        try:
            orch.completions.get(timeout=0.002)
            done += 1
        except _queue.Empty:
            pass
        if orch.worker_error:
            raise RuntimeError(orch.worker_error)
        if now > time_limit:
            break
    wall = time.perf_counter() - t0
    return reqs, wall


# ----------------------------------------------------------------------------
# A. replica scaling on a slowed bottleneck stage
# ----------------------------------------------------------------------------

def _scaling(n_requests: int, dwell_s: float, seed: int) -> Dict[str, float]:
    out = {}
    rate = 6.0 / dwell_s            # overload even the 2-replica config
    # (well past 2x capacity, so the wall clock measures service rate,
    # not the arrival window)
    for n_rep in (1, 2):
        graph = StageGraph()
        graph.add_stage(StageSpec("slow", "custom", is_output=True))
        engines = {"slow": [StubEngine("slow", dwell_s)
                            for _ in range(n_rep)]}
        orch = Orchestrator(graph, engines,
                            config=ServeConfig(routing="least_loaded"))
        reqs, wall = _poisson_serve(
            orch, [{"x": i} for i in range(n_requests)], rate, seed)
        orch.shutdown(drain=False)
        ok = sum(1 for r in reqs if r.completion_time is not None
                 and not r.failed)
        out[n_rep] = ok / wall
    return out


def _process_scaling(n_requests: int, dwell_s: float, seed: int) -> float:
    """D: the 2-replica scaling run again, but each replica is a spawned
    process worker fed through shared-memory segments (host-only stub
    engines, so the row runs on any parent)."""
    graph = StageGraph()
    graph.add_stage(StageSpec("slow", "custom", is_output=True))
    spec = EngineSpec("repro.engine.stub_engine:make_stub",
                      {"name": "slow", "dwell_ms": dwell_s * 1e3})
    config = ServeConfig(routing="least_loaded", stages={
        "slow": StageConfig(replicas=2, isolation="process",
                            engine_spec=spec)})
    orch = Orchestrator(graph, {"slow": StubEngine("slow", dwell_s)},
                        config=config)
    orch.start()
    for _, w in orch._workers["slow"].workers():
        w.wait_ready(60.0)               # keep spawn cost out of the window
    reqs, wall = _poisson_serve(
        orch, [{"x": i} for i in range(n_requests)], 6.0 / dwell_s, seed)
    orch.shutdown(drain=False)
    ok = sum(1 for r in reqs if r.completion_time is not None
             and not r.failed)
    return ok / wall


# ----------------------------------------------------------------------------
# B. cache-affinity routing vs round-robin on shared-prefix traffic
# ----------------------------------------------------------------------------

def _affinity_orch(n_rep: int, routing: str, *, max_batch: int,
                   max_new: int, seed: int) -> Orchestrator:
    cfg = tiny_lm("aff_lm", vocab=512)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    kv = PagedKVConfig(num_pages=max_batch * 16 + 64, page_size=16,
                       max_pages_per_seq=16)

    def make_engine():
        return AREngine(
            "lm", cfg, params, kv=kv, max_batch=max_batch,
            token_budget=64, chunk_size=32, enable_prefix_cache=True,
            default_sampling=SamplingParams(max_new_tokens=max_new,
                                            temperature=0.0))

    graph = StageGraph()
    graph.add_stage(StageSpec("lm", "ar", is_output=True))
    config = ServeConfig(routing=routing, stages={
        "lm": StageConfig(replicas=n_rep, engine_factory=make_engine)})
    return Orchestrator(graph, {"lm": make_engine()}, config=config)


def _affinity_hit_rate(n_rep: int, routing: str, *, families: int,
                       per_family: int, prefix_len: int, max_new: int,
                       seed: int) -> float:
    """Serve warm + measured shared-prefix traffic sequentially (each
    request completes — and publishes — before the next routes) and
    return the aggregate prefix-cache hit rate across replicas."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, 500, prefix_len).astype(np.int32)
                for _ in range(families)]
    prompts = [np.concatenate([p, rng.integers(0, 500, 4).astype(np.int32)])
               for p in prefixes]          # warm: first arrival per family
    for _ in range(per_family):
        for f in range(families):
            sfx = rng.integers(0, 500, int(rng.integers(4, 12))
                               ).astype(np.int32)
            prompts.append(np.concatenate([prefixes[f], sfx]))
    orch = _affinity_orch(n_rep, routing, max_batch=4, max_new=max_new,
                          seed=seed)
    orch.start()
    for p in prompts:
        orch.submit(Request(inputs={"tokens": p}))
        r = orch.completions.get(timeout=30.0)
        if r.failed:
            raise RuntimeError(r.failed)
    stats = {"cached_tokens": 0, "computed_tokens": 0}
    for eng in orch._live_engines("lm"):
        for k in stats:
            stats[k] += eng.prefix_stats[k]
    orch.shutdown(drain=False)
    tot = stats["cached_tokens"] + stats["computed_tokens"]
    return stats["cached_tokens"] / tot if tot else 0.0


# ----------------------------------------------------------------------------
# C. autoscale: move a replica to the bottleneck at runtime
# ----------------------------------------------------------------------------

def _two_stage(heavy_s: float, light_s: float, heavy_reps: int,
               light_reps: int):
    graph = StageGraph()
    graph.add_stage(StageSpec("pre", "custom"))
    graph.add_stage(StageSpec("gen", "custom", is_output=True))
    graph.add_edge("pre", "gen", lambda d, p: p, connector="inline")
    engines = {"pre": [StubEngine("pre", light_s)
                       for _ in range(light_reps)],
               "gen": [StubEngine("gen", heavy_s)
                       for _ in range(heavy_reps)]}
    config = ServeConfig(routing="least_loaded", stages={
        "pre": StageConfig(engine_factory=lambda: StubEngine("pre",
                                                             light_s)),
        "gen": StageConfig(engine_factory=lambda: StubEngine("gen",
                                                             heavy_s))})
    return Orchestrator(graph, engines, config=config)


def _autoscale(n_requests: int, heavy_s: float, seed: int):
    light_s = heavy_s / 12.0
    rate = 4.0 / heavy_s            # well past the 2-replica gen capacity
    inputs = [{"x": i} for i in range(n_requests)]

    orch = _two_stage(heavy_s, light_s, 2, 2)          # static even split
    reqs, _ = _poisson_serve(orch, inputs, rate, seed)
    orch.shutdown(drain=False)
    static_jct = float(np.mean([r.jct for r in reqs if r.jct is not None]))

    orch = _two_stage(heavy_s, light_s, 2, 2)          # same budget of 4
    scaler = ScalingController(orch, ScalingConfig(
        interval=0.08, cooldown=1, hi=0.75, lo=0.40,
        replica_budget=4)).start()
    reqs, _ = _poisson_serve(orch, inputs, rate, seed)
    actions = list(scaler.actions)
    counts = orch.replica_counts()
    orch.shutdown(drain=False)
    dyn_jct = float(np.mean([r.jct for r in reqs if r.jct is not None]))
    return static_jct, dyn_jct, actions, counts


# ----------------------------------------------------------------------------

def run(n_requests: int = 24, dwell_ms: float = 20.0, families: int = 4,
        per_family: int = 6, prefix_len: int = 48, max_new: int = 6,
        autoscale_requests: int = 60, seed: int = 0) -> list:
    rows = []

    thr = _scaling(n_requests, dwell_ms / 1e3, seed)
    speedup = thr[2] / thr[1] if thr[1] else 0.0
    rows.append(("replicas_1x_finished_per_s", thr[1] * 1e3,
                 f"{thr[1]:.1f} req/s (dwell {dwell_ms:.0f}ms)"))
    rows.append(("replicas_2x_finished_per_s", thr[2] * 1e3,
                 f"{thr[2]:.1f} req/s speedup={speedup:.2f}x"))

    proc = _process_scaling(n_requests, dwell_ms / 1e3, seed)
    ratio = proc / thr[2] if thr[2] else 0.0
    rows.append(("replicas_2x_process_finished_per_s", proc * 1e3,
                 f"{proc:.1f} req/s isolation=process host-only "
                 f"({100*ratio:.0f}% of threaded 2x)"))

    base = _affinity_hit_rate(1, "affinity", families=families,
                              per_family=per_family, prefix_len=prefix_len,
                              max_new=max_new, seed=seed)
    aff = _affinity_hit_rate(2, "affinity", families=families,
                             per_family=per_family, prefix_len=prefix_len,
                             max_new=max_new, seed=seed)
    rr = _affinity_hit_rate(2, "round_robin", families=families,
                            per_family=per_family, prefix_len=prefix_len,
                            max_new=max_new, seed=seed)
    rows.append(("affinity_hit_rate_1rep", base * 1e4,
                 f"{base*100:.1f}% (single-replica baseline)"))
    rows.append(("affinity_hit_rate_2rep", aff * 1e4,
                 f"{aff*100:.1f}% affinity routing "
                 f"(drop {100*(base-aff):.1f} pts)"))
    rows.append(("round_robin_hit_rate_2rep", rr * 1e4,
                 f"{rr*100:.1f}% round-robin "
                 f"(drop {100*(base-rr):.1f} pts)"))

    static_jct, dyn_jct, actions, counts = _autoscale(
        autoscale_requests, dwell_ms / 1e3, seed)
    moved = sum(1 for a in actions if a["stage"] == "gen")
    rows.append(("autoscale_static_jct", static_jct * 1e6,
                 f"mean={static_jct*1e3:.0f}ms (even 2/2 split)"))
    rows.append(("autoscale_dynamic_jct", dyn_jct * 1e6,
                 f"mean={dyn_jct*1e3:.0f}ms actions={len(actions)} "
                 f"to_bottleneck={moved} final={counts} "
                 f"improvement={static_jct/dyn_jct:.2f}x"
                 if dyn_jct else "no completions"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny settings for the pre-commit bench tier")
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="also write machine-readable rows")
    args = ap.parse_args()
    kw = (dict(n_requests=16, dwell_ms=15.0, families=3, per_family=4,
               max_new=4, autoscale_requests=40) if args.smoke else {})
    rows = run(**kw)
    for r in rows:
        print(",".join(map(str, r)))
    if args.json:
        from benchmarks.run import write_json
        write_json(args.json, rows)


if __name__ == "__main__":
    main()
