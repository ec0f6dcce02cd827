"""The benchmark's model families, work counts, peaks table, trace
reduction and manifest (CPU only, no chip)."""
from __future__ import annotations

import ast
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import run  # noqa: E402
import tracereduce  # noqa: E402
import workcount  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in MANIFEST["configs"]}


def _model(name):
    """The configuration's family and its sizes, through the resolver."""
    fam = run.family(CONFIGS[name])
    return fam, fam.spec(CONFIGS[name])


@pytest.mark.parametrize("name,arch", [("qwen2-1.5b", "qwen2_1_5b"),
                                       ("chatglm3-6b-pp4", "chatglm3_6b")])
def test_param_count_matches_the_model_config(name, arch):
    """The benchmark's count adds what ModelConfig.param_count leaves
    out (q/k/v biases, the final norm); the rest agrees exactly, at the
    benchmark's depth."""
    import dataclasses

    from repro.configs import get_config
    fam, spec = _model(name)
    cfg = dataclasses.replace(get_config(arch), n_layers=spec.n_layers)
    bias = (spec.n_heads + 2 * spec.n_kv_heads) * spec.hd
    assert fam.param_count(spec) - spec.n_layers * bias \
        - spec.d_model == cfg.param_count()


def test_param_counts_as_stated():
    fam, qwen = _model("qwen2-1.5b")
    _, glm = _model("chatglm3-6b-pp4")
    assert round(fam.param_count(qwen) / 1e9, 3) == 1.544
    # 7 layers of 203,956,224 (matmuls and norms) plus q/k/v biases,
    # the embedding, the head and the final norm
    assert fam.layer_matmul_params(glm) + 2 * 4096 == 203_956_224
    bias = (32 + 2 * 2) * 128
    assert fam.param_count(glm) == \
        7 * (203_956_224 + bias) + 2 * 65024 * 4096 + 4096
    assert round(fam.param_count(glm) / 1e9, 3) == 1.960


# the dense counts as they stood before the families were split out:
# param_count, weight_bytes, kv_row_bytes, decode_flops_per_token(1024),
# prompt_flops_per_token(512)
PINNED = {
    "qwen2-1.5b": (1_543_714_304, 3_087_428_608, 28_672, 3_263_299_584,
                   2_708_471_808),
    "chatglm3-6b-pp4": (1_960_406_528, 3_388_136_448, 7_168,
                        3_505_389_568, 2_913_992_704),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_counts_are_pinned(name):
    """The rooflines read these counts: a move of the family must leave
    them as they were. A dense step reads every weight at any batch."""
    fam, spec = _model(name)
    assert (fam.param_count(spec), fam.weight_bytes(spec, 32),
            fam.kv_row_bytes(spec), fam.decode_flops_per_token(spec, 1024),
            fam.prompt_flops_per_token(spec, 512)) == PINNED[name]
    assert fam.weight_bytes(spec, 1) == fam.weight_bytes(spec, 256)


def test_dense_draw_is_pinned():
    """The seeded weights as they were drawn before the families were
    split out: the same leaves, in the same order, from the same key."""
    import jax
    import numpy as np
    fam = run.family({})
    params = fam.make_params(fam.Spec(n_layers=2, d_model=64, n_heads=4,
                                      n_kv_heads=2, d_ff=128,
                                      vocab_size=300), 7)
    leaves = jax.tree.leaves(params)
    assert len(leaves) == 15
    total = sum(float(np.abs(np.asarray(a, np.float64)).sum())
                for a in leaves)
    assert total == pytest.approx(9794.6904, rel=1e-6)


def test_decode_least_time_is_bandwidth_bound_at_32_slots():
    fam, glm = _model("chatglm3-6b-pp4")
    peak = workcount.peaks("TPU v5 lite")
    t, bound = workcount.decode_least_s(fam, glm, 32, 1000, 0.2e9, peak)
    assert bound == "bytes"
    assert t == pytest.approx((fam.weight_bytes(glm, 32) + 0.2e9) / 819e9)
    assert fam.kv_row_bytes(glm) == 7 * 2 * 2 * 128 * 2


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_config_resolves(name):
    fam, spec = _model(name)
    assert isinstance(spec, fam.Spec)
    assert fam.model_config(name, CONFIGS[name]).n_layers == spec.n_layers


def test_no_family_means_dense_gqa():
    dense = run.family({"family": "dense_gqa"})
    assert run.family({}) is dense
    assert dense.__name__ == "family_dense_gqa"
    for cfg in CONFIGS.values():
        if "family" not in cfg:
            assert run.family(cfg) is dense


def test_unknown_family_names_the_known():
    with pytest.raises(ValueError, match=r"no_such.*known: \[.*dense_gqa"):
        run.family({"family": "no_such"})


def test_a_key_the_family_does_not_serve_is_refused():
    """A configuration that asks the served model for experts is not
    drawn, served and compared as a dense model."""
    cfg = dict(CONFIGS["qwen2-1.5b"], moe={"n_experts": 8, "top_k": 2})
    with pytest.raises(ValueError, match="moe"):
        run.family(cfg).model_config("qwen2-1.5b", cfg)


# what a family's reference side starts from: the weights it draws and
# the layers and rounding the comparison runs
REFERENCE_SIDE = ("make_params", "hidden", "logits_at", "quantize")


def _program_imports(node):
    """The modules of the program (``repro``) that ``node`` imports."""
    out = []
    for n in ast.walk(node):
        if isinstance(n, ast.Import):
            out += [a.name for a in n.names]
        elif isinstance(n, ast.ImportFrom) and n.level == 0:
            out.append(n.module)
    return [m for m in out if m.split(".")[0] == "repro"]


@pytest.mark.parametrize("path", sorted(run.FAMILIES.glob("*.py")),
                         ids=lambda p: p.stem)
def test_every_family_keeps_the_contract(path):
    """The resolver loads the family (it refuses one that lacks a name of
    ``run.FAMILY_NAMES``), and its reference imports nothing of the
    program: only ``model_config`` may, and only ``repro.models.config``;
    nothing that the reference side calls reaches ``model_config``. The
    shared harness modules it builds on import nothing of the program
    either."""
    run.family({"family": path.stem})
    tree = ast.parse(path.read_text())
    defs = {n.name: n for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    for name, node in defs.items():
        allowed = ["repro.models.config"] if name == "model_config" else []
        assert set(_program_imports(node)) <= set(allowed), name
    rest = [n for n in tree.body if n not in defs.values()]
    assert not _program_imports(ast.Module(body=rest, type_ignores=[]))
    seen, todo = set(), list(REFERENCE_SIDE)
    while todo:
        name = todo.pop()
        if name in seen or name not in defs:
            continue
        seen.add(name)
        todo += [n.id for n in ast.walk(defs[name])
                 if isinstance(n, ast.Name)]
    assert "model_config" not in seen
    for shared in ("reference", "weights", "workcount"):
        text = (CHIP / f"{shared}.py").read_text()
        assert not _program_imports(ast.parse(text)), shared


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        workcount.peaks("TPU v9 imaginary")


def _ev(name, a, b):
    return (name, float(a), float(b))


def test_trace_reduction_on_a_built_trace():
    """Window 0..100 ns; the device is busy 10..30 and 25..40 (union 30)
    and 70..80; the host sat in 'sample' during 40..70."""
    planes = {
        "/host:CPU": [
            ("python", [_ev(tracereduce.WINDOW, 0, 100)]),
            ("python", [_ev("dispatch", 5, 12), _ev("sample", 40, 70),
                        _ev("outer", 0, 100)]),
        ],
        "/device:TPU:0": [
            ("XLA Ops", [_ev("fusion.1", 10, 30), _ev("dot.2", 25, 40),
                         _ev("%fusion.1 = bf16[8]{0} fusion(%p)", 70, 80),
                         _ev("before_window", -50, -10)]),
            ("XLA Modules", [_ev("jit__lambda", 10, 40),
                             _ev("jit__lambda", 70, 80)]),
        ],
    }
    r = tracereduce.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["devices"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert r["modules"] == [["jit__lambda", pytest.approx(40e-9)]]
    gaps = r["idle_gaps"]
    assert gaps[0] == ["sample", pytest.approx(30e-9)]
    # 0..10: 'dispatch' overlaps 5 ns, 'outer' 10 ns -> the larger wins
    assert ["outer", pytest.approx(10e-9)] in gaps
    assert sum(g[1] for g in gaps) == pytest.approx(60e-9)


def test_trace_reduction_needs_its_window():
    with pytest.raises(ValueError, match="bench_window"):
        tracereduce.reduce_planes({"/host:CPU": [("python", [])]})


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_names_files_that_exist():
    """Every cell, configuration, traffic mix and metric the manifest
    names has its file, found by that name."""
    assert MANIFEST["command"][1:] == ["benchmarks/chip/run.py"]
    for c in MANIFEST["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert (CHIP / "cells" / f"{w['name']}.json").is_file()
        assert w["config"] in CONFIGS
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"])
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)
