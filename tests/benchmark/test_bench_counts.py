"""The benchmark's work counts, peaks table, trace reduction and
manifest (CPU only, no chip)."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import tracereduce  # noqa: E402
import workcount  # noqa: E402
from weights import Spec  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in MANIFEST["configs"]}


@pytest.mark.parametrize("name,arch", [("qwen2-1.5b", "qwen2_1_5b"),
                                       ("chatglm3-6b-pp4", "chatglm3_6b")])
def test_param_count_matches_the_model_config(name, arch):
    """The benchmark's count adds what ModelConfig.param_count leaves
    out (q/k/v biases, the final norm); the rest agrees exactly, at the
    benchmark's depth."""
    import dataclasses

    from repro.configs import get_config
    spec = Spec.from_config(CONFIGS[name])
    cfg = dataclasses.replace(get_config(arch), n_layers=spec.n_layers)
    bias = (spec.n_heads + 2 * spec.n_kv_heads) * spec.hd
    assert workcount.param_count(spec) - spec.n_layers * bias \
        - spec.d_model == cfg.param_count()


def test_param_counts_as_stated():
    qwen = Spec.from_config(CONFIGS["qwen2-1.5b"])
    glm = Spec.from_config(CONFIGS["chatglm3-6b-pp4"])
    assert round(workcount.param_count(qwen) / 1e9, 3) == 1.544
    # 7 layers of 203,956,224 (matmuls and norms) plus q/k/v biases,
    # the embedding, the head and the final norm
    assert workcount.layer_matmul_params(glm) + 2 * 4096 == 203_956_224
    bias = (32 + 2 * 2) * 128
    assert workcount.param_count(glm) == \
        7 * (203_956_224 + bias) + 2 * 65024 * 4096 + 4096
    assert round(workcount.param_count(glm) / 1e9, 3) == 1.960


def test_decode_least_time_is_bandwidth_bound_at_32_slots():
    glm = Spec.from_config(CONFIGS["chatglm3-6b-pp4"])
    peak = workcount.peaks("TPU v5 lite")
    t, bound = workcount.decode_least_s(glm, 32, 1000, 0.2e9, peak)
    assert bound == "bytes"
    assert t == pytest.approx((workcount.weight_bytes(glm) + 0.2e9) / 819e9)
    assert workcount.kv_row_bytes(glm) == 7 * 2 * 2 * 128 * 2


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
