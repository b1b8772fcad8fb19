"""Model kinds are resolved by name (``bench/models/<model>.py``).

The kind both configuration files run (they name none) reproduces what
the harness read at commit 142de0b, when its code sat in
``bench/model.py``, ``bench/counts.py`` and ``bench/smoke.py``: the same
weights bit for bit, parameter and pool shapes, FLOP counts and smoke
widths.  A configuration of a new kind is served, counted and checked
with no edit to an existing ``bench/`` file.
"""
import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import correct, counts
from bench.smoke import smoke_cell
from bench.spec import ROOT, model_module, resolve
from conftest import smoke_run

CONFIGS = ROOT / "bench" / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        out.update(flat(v, path) if isinstance(v, dict) else {path: v})
    return out


#: computed at commit 142de0b from bench/model.py and bench/smoke.py
PARENT = {
    "internlm2_1_8b_pd": {
        "smoke": {"hidden_size": 256, "intermediate_size": 512,
                  "num_hidden_layers": 2, "num_attention_heads": 8,
                  "num_key_value_heads": 4, "head_dim": 32,
                  "vocab_size": 1024},
        "sha256": {
            7: "793b9133be949acdaaaaae5927a4cbbcd24017aebbda2660168af0ad6f70d94a",
            3000000019:
                "e73520b67bd28f6fe4b646e9987072e4a96595ff7154221c910982290717d69c",
        },
        "shapes": {
            "embed": (92544, 2048), "final_ln/scale": (2048,),
            "lm_head": (2048, 92544), "blocks/ln1/scale": (24, 2048),
            "blocks/attn/wq": (24, 2048, 16, 128),
            "blocks/attn/wk": (24, 2048, 8, 128),
            "blocks/attn/wv": (24, 2048, 8, 128),
            "blocks/attn/wo": (24, 16, 128, 2048),
            "blocks/ln2/scale": (24, 2048),
            "blocks/mlp/wg": (24, 2048, 8192),
            "blocks/mlp/wu": (24, 2048, 8192),
            "blocks/mlp/wd": (24, 8192, 2048)},
        # bench/rehearse.py: (layers, pages, kv heads, page, head_dim)
        "pool": (24, 1600, 8, 16, 128),
    },
    "qwen1_5_4b": {
        "smoke": {"hidden_size": 256, "intermediate_size": 512,
                  "num_hidden_layers": 2, "num_attention_heads": 8,
                  "num_key_value_heads": 8, "head_dim": 32,
                  "vocab_size": 1024},
        "sha256": {
            7: "3dbda6c11b38d991a35b369b331d1ac3d49e5b9c52d758da352c1536331a6234",
            3000000019:
                "04efe71401f87aedaaec92520fcc83127eea3be35152e6b7dc0ae623f4617850",
        },
        "shapes": {
            "embed": (151936, 2560), "final_ln/scale": (2560,),
            "lm_head": (2560, 151936), "blocks/ln1/scale": (40, 2560),
            "blocks/attn/wq": (40, 2560, 20, 128),
            "blocks/attn/wk": (40, 2560, 20, 128),
            "blocks/attn/wv": (40, 2560, 20, 128),
            "blocks/attn/wo": (40, 20, 128, 2560),
            "blocks/attn/bq": (40, 20, 128), "blocks/attn/bk": (40, 20, 128),
            "blocks/attn/bv": (40, 20, 128),
            "blocks/ln2/scale": (40, 2560),
            "blocks/mlp/wg": (40, 2560, 6912),
            "blocks/mlp/wu": (40, 2560, 6912),
            "blocks/mlp/wd": (40, 6912, 2560)},
        "pool": (40, 560, 20, 16, 128),
    },
}
SEEDS = (7, 3000000019)


def weights_digest(params):
    """SHA-256 over the leaves' bytes, in tree order."""
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT))
def test_smoke_widths_are_the_parents(name):
    cfg = config(name)
    assert model_module(cfg).smoke_widths(cfg) == PARENT[name]["smoke"]


@pytest.mark.parametrize("name", sorted(PARENT))
@pytest.mark.parametrize("seed", SEEDS)
def test_weights_are_the_parents_bit_for_bit(name, seed):
    cfg = config(name)
    kind = model_module(cfg)
    small = dict(cfg, **kind.smoke_widths(cfg))
    assert weights_digest(kind.init_params(small, seed)) == \
        PARENT[name]["sha256"][seed]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_shapes_at_published_widths_are_the_parents(name):
    cfg = config(name)
    kind = model_module(cfg)
    assert flat(kind.param_shapes(cfg)) == PARENT[name]["shapes"]
    pages = cfg["serving"].get("decode", cfg["serving"])["pages"]
    assert kind.kv_pool_shape(cfg, pages) == PARENT[name]["pool"]


CHUNKS = [(0, 1), (0, 64), (64, 64), (1280, 37), (4672, 64), (6464, 64)]
DECODES = [[1], [10, 20], [1317, 4745, 2000, 6528], list(range(1, 17)),
           [52 + 783] * 16]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_flop_counts_are_bench_counts(name):
    cfg = config(name)
    kind = model_module(cfg)
    m = counts.Dims.from_config(cfg)
    assert kind.dims(cfg) == m
    for start, valid in CHUNKS:
        assert kind.prefill_chunk_flops(kind.dims(cfg), start, valid) == \
            counts.prefill_chunk_flops(m, start, valid)
    for lens in DECODES:
        assert kind.decode_step_flops(kind.dims(cfg), lens) == \
            counts.decode_step_flops(m, lens)


#: a kind written by the test: the kind of a configuration that names
#: none, with every call and what it returned recorded
PROBE = '''
from pathlib import Path

from bench.spec import model_module

INNER = model_module({}, Path(__file__).resolve().parents[1])
CALLS = {}


def _seen(name, out):
    CALLS.setdefault(name, []).append(out)
    return out


def model_config(cfg):
    return _seen("model_config", INNER.model_config(cfg))


def param_shapes(cfg):
    return _seen("param_shapes", INNER.param_shapes(cfg))


def kv_pool_shape(cfg, pages):
    return _seen("kv_pool_shape", INNER.kv_pool_shape(cfg, pages))


def init_params(cfg, seed):
    return _seen("init_params", INNER.init_params(cfg, seed))


def dims(cfg):
    return _seen("dims", INNER.dims(cfg))


def prefill_chunk_flops(m, start, valid):
    return _seen("prefill_chunk_flops",
                 INNER.prefill_chunk_flops(m, start, valid))


def decode_step_flops(m, seq_lens):
    return _seen("decode_step_flops", INNER.decode_step_flops(m, seq_lens))


def smoke_widths(cfg):
    return _seen("smoke_widths", dict(INNER.smoke_widths(cfg), probe=1))
'''


def tree_digest(root):
    h = hashlib.sha256()
    for p in sorted(root.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(root)).encode() + p.read_bytes())
    return h.hexdigest()


def test_a_configuration_of_a_new_kind_is_served_counted_and_checked(
        tmp_path, monkeypatch):
    before = tree_digest(ROOT / "bench")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (tmp_path / "bench/models/probe.py").write_text(PROBE)
    cfg = config("qwen1_5_4b")
    cfg["model"] = "probe"
    (tmp_path / "bench/configs/probe.json").write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "probe", "source": "test",
                             "file": "bench/configs/probe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "probe.longgen", "config": "probe",
                               "traffic": "longgen", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = resolve("probe.longgen", root=tmp_path)
    probe = cell.model_module()
    assert Path(probe.__file__).resolve() == \
        (tmp_path / "bench/models/probe.py").resolve()
    checked = {}
    real_check = correct.check

    def spy(cell, params, run, seed, **kw):
        checked.update(params=params, run=run)
        return real_check(cell, params, run, seed, **kw)

    monkeypatch.setattr(correct, "check", spy)
    small = smoke_cell(cell)
    assert small.config["probe"] == 1
    assert small.config["hidden_size"] == \
        probe.CALLS["smoke_widths"][-1]["hidden_size"]
    res = smoke_run(None, cell=small)

    assert res["correct"] is True, json.dumps(res["checks"])
    assert small.config["reference"] == "dense_gqa"
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert checked["params"] is probe.CALLS["init_params"][-1]
    run = checked["run"]
    assert run.dims is probe.CALLS["dims"][-1]
    rec = run.recorder
    assert rec.kind is probe
    recorded = sum(f for steps in rec.steps.values() for *_, f in steps) + \
        sum(rec._step_flops.values())
    assert probe.CALLS["prefill_chunk_flops"] and \
        probe.CALLS["decode_step_flops"]
    assert recorded == sum(probe.CALLS["prefill_chunk_flops"]) + \
        sum(probe.CALLS["decode_step_flops"])
    assert probe.CALLS["model_config"]
    assert tree_digest(ROOT / "bench") == before
