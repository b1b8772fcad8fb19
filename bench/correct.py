"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and always holding the
request with the most served tokens, is run through the plain reference
(``bench/references/<reference>.py``) over its prompt and the tokens the
program served.  For each served token the gap is

    max(reference logits) - reference logit of the served token,

0 when the program served the reference's best token.  The number
compared is the widest gap over the sample, against the limit the
configuration file states under ``check`` with the readings it was set
from.  Traffic is greedy (temperature 0), so the served token is the
program's own argmax.

The control (``gaps(..., control=True)``) is the reference computed with
float8 (e4m3) matmul inputs, one step below the bfloat16 the
configurations state; the gap of the token it ranks first, read against
the float32 reference, is what the limit has to reject.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List, Tuple

import numpy as np

CONTROL = "fp8"      # the control's matmul input precision


def sample(run, seed: int, k: int) -> List:
    """Up to ``k`` finished requests: the one with the most served tokens
    and the rest drawn from the seed."""
    out = run.system.output
    done = [r for r in run.records if r.done and not r.req.failed
            and r.n_out(out) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_out(out), r.req.req_id))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    pick = [rest[i] for i in sorted(rng.permutation(len(rest))[:k - 1])]
    return [longest] + pick


def served_tokens(rec, stage: str) -> np.ndarray:
    return np.concatenate([np.asarray(c["tokens"], np.int32)
                           for c in rec.req.outputs[stage]])


def _gap_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap(lr, picks, valid):
        """Widest (row max - logit of the picked token) over valid rows."""
        got = jnp.take_along_axis(lr, picks[:, None], -1)[:, 0]
        return jnp.max(jnp.where(valid, jnp.max(lr, -1) - got, 0.0))

    @jax.jit
    def argmax(lc):
        return jnp.argmax(lc, -1).astype(jnp.int32)

    return gap, argmax


_GAP = None


def gaps(ref, cfg: Dict, params, prompt: np.ndarray, served: np.ndarray,
         control: bool = False, buckets: Tuple[int, int] = (0, 0),
         ) -> Tuple[float, float]:
    """(widest gap of the served tokens, widest gap of the control's
    first-ranked tokens or nan) for one request; every array has the
    run's one padded shape, so nothing compiles per request."""
    global _GAP
    if _GAP is None:
        _GAP = _gap_fn()
    gap, argmax = _GAP
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pos = np.arange(len(prompt) - 1, len(seq))
    t = time.perf_counter()
    lr = ref.final_logits(cfg, params, seq, pos, None, *buckets)
    picks = np.zeros(lr.shape[0], np.int32)
    picks[:len(served)] = served
    valid = np.arange(lr.shape[0]) < len(served)
    g = float(gap(lr, picks, valid))
    print(f"reference: {len(seq)} tokens in {time.perf_counter() - t:.2f}s",
          file=sys.stderr, flush=True)
    gc = float("nan")
    if control:
        lc = ref.final_logits(cfg, params, seq, pos, CONTROL, *buckets)
        gc = float(gap(lr, argmax(lc), valid))
    return g, gc


def check(cell, params, run, seed: int, control: bool = False) -> Dict:
    chk = cell.config["check"]
    ref = cell.reference_module()
    stage = run.system.output
    recs = sample(run, seed, int(chk["requests"]))
    # one compiled shape per run: the longest context and answer it serves
    buckets = (cell.config["serving"]["max_seq"],
               int(cell.traffic["output"]["max"]))
    widest, widest_ctl, ntok = 0.0, 0.0, 0
    for r in recs:
        t = time.perf_counter()
        served = served_tokens(r, stage)
        g, gc = gaps(ref, cell.config, params, r.item.tokens, served,
                     control, buckets)
        print(f"reference: prompt {len(r.item.tokens)} served "
              f"{len(served)} gap {g:.6f} control {gc:.6f} "
              f"({time.perf_counter() - t:.2f}s)", file=sys.stderr,
              flush=True)
        widest, ntok = max(widest, g), ntok + len(served)
        if control:
            widest_ctl = max(widest_ctl, gc)
    limit = float(chk["max_logit_gap"])
    out = {"ok": bool(recs) and widest <= limit, "requests": len(recs),
           "tokens": ntok,
           "checks": {"max_logit_gap": {"value": widest, "limit": limit}}}
    if control:
        out["control_gap"] = widest_ctl
    return out
