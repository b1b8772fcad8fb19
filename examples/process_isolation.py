"""Process-isolated stage replicas via ServeConfig.

A prefill→decode pipeline where the decode stage runs in spawned OS
processes: the child rebuilds its engine from a picklable EngineSpec,
prompt KV crosses the process boundary through the shared-memory
connector (named segments + manifests), and greedy outputs match the
all-thread run exactly.  Killing a process replica mid-run re-admits
its in-flight requests to the survivor — zero requests lost.

Host-only: both stages are JAX engines, and a spawned child cannot reach
an accelerator the parent already holds (the Orchestrator refuses to
spawn one there), so the example pins JAX to the CPU in parent and child.

  PYTHONPATH=src python examples/process_isolation.py
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"     # before jax is imported; inherited

import numpy as np  # noqa: E402

from repro.configs.pipelines import build_pd_disaggregated  # noqa: E402
from repro.core.config import ServeConfig, StageConfig  # noqa: E402
from repro.core.orchestrator import Orchestrator  # noqa: E402
from repro.core.request import Request  # noqa: E402


def main():
    # 1) a pipeline bundle: every builder attaches picklable
    #    `engine_specs` ("module:callable" + kwargs) alongside the live
    #    engines — specs are the only engine form that can cross a
    #    spawn boundary (deterministic builders, same seed → same params)
    graph, engines, bundle = build_pd_disaggregated(max_batch=4, max_new=8)

    # 2) one typed config for the whole serving surface: decode runs as
    #    2 spawned process replicas, prefill stays a thread
    config = ServeConfig(
        routing="affinity",
        stages={"decode": StageConfig(
            replicas=2,
            isolation="process",
            engine_spec=bundle["engine_specs"]["decode"])})

    orch = Orchestrator(graph, engines, config=config)
    orch.start()                         # spawn now, before timing anything

    # 3) serve: prompt KV travels prefill→decode through the shm
    #    connector — cross_process=True ships segment manifests, so the
    #    decode child attaches the same named segment the prefill thread
    #    wrote (one copy, no pickling of the KV arrays)
    rng = np.random.default_rng(0)
    reqs = [Request(inputs={"tokens":
                            rng.integers(0, 500, size=n).astype(np.int32)})
            for n in (5, 19, 33, 12)]
    for r in reqs:
        orch.submit(r)
    for req in orch.run(timeout=300.0):
        toks = req.outputs["decode"][0]["tokens"]
        print(f"req {req.req_id}: jct={req.jct:.3f}s "
              f"tokens={[int(t) for t in toks]}")

    # 4) the process replicas report the same metrics as threads —
    #    WorkerMetrics snapshots ride the control pipe home
    m = orch.stage_metrics()["decode"]
    print(f"decode: finished={m['finished']} n_replicas={m['n_replicas']} "
          f"replica_failures={m['replica_failures']}")


if __name__ == "__main__":
    main()
