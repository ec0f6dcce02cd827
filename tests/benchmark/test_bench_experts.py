"""The expert family (``families/moe_gqa.py``, Qwen3-MoE) and the reader
of the expert counters (CPU, small sizes, no chip): its counts against
the program's and by hand, its float32 reference against the served
model through ``Engine``, and a whole traced run of a small expert
cell."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from test_bench_reference import (  # noqa: E402
    _chunked_prefill_then_paged_decode, _tiny_cell)

CONFIG = "qwen3-moe-235b-a22b-ep16"
# the expert family at a small size: q/k-normed GQA, 16 experts of
# width 32, top 4
TINY_MOE = {"family": "moe_gqa", "n_layers": 2, "d_model": 64,
            "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
            "vocab_size": 250, "n_experts": 16, "top_k": 4,
            "d_ff_expert": 32, "qk_norm": True, "rope_theta": 1e6,
            "norm_eps": 1e-6, "dtype": "float32", "param_dtype": "float32"}


def _family():
    return run.family({"family": "moe_gqa"})


def _small(fam):
    """2 layers, d 8, heads 2/1 of 4, vocab 10, 2 of 4 experts held (of
    width 3), top 2, bf16."""
    return fam.Spec(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                    head_dim=4, vocab_size=10, n_experts=4, top_k=2,
                    d_ff_expert=3, experts_held=2)


def test_param_count_matches_the_model_config():
    """The family's count is ModelConfig.param_count of the listed
    qwen3-moe config at the benchmark's depth and held share, plus the
    final norm. A layer holds 222,830,848 parameters (445.7 MB in
    bf16), the embedding and head 2 x 151,936 x 4,096."""
    from repro.configs import get_config
    cfg_json = json.loads((CHIP / "configs" / f"{CONFIG}.json").read_text())
    fam = run.family(cfg_json)
    spec = fam.spec(cfg_json)
    cfg = get_config("qwen3_moe_235b_a22b")
    cfg = dataclasses.replace(cfg, n_layers=spec.n_layers, moe=dataclasses
                              .replace(cfg.moe, held=(0, spec.held)))
    assert fam.param_count(spec) - spec.d_model == cfg.param_count()
    per_layer = (fam.layer_attn_params(spec) + fam.layer_small_params(spec)
                 + fam.router_params(spec) + 8 * fam.expert_params(spec))
    assert per_layer == 222_830_848
    assert fam.param_count(spec) == \
        8 * per_layer + 2 * 151_936 * 4096 + 4096
    served = fam.model_config(CONFIG, cfg_json)
    assert served.qk_norm and served.moe.held_range == (0, 8)
    assert served.moe.n_experts == 128 and served.moe.top_k == 8


def test_counts_by_hand():
    """The small spec, counted by hand. Per layer: attention
    8*(2+2)*4 + 2*4*8 = 192, norms 2*8 + 2*4 = 24 (the q/k norms too),
    router 8*4 = 32, one expert 3*8*3 = 72; head 10*8 = 80; final norm
    8. A step always reads 2*(192+24+32) + 80 + 8 = 584 weights. One
    token reaches one held expert per layer in expectation
    (2 * (1 - (1 - 2/4)**1)); a large batch reaches both. Held pairs per
    token and layer: k*H/E = 2*2/4 = 1."""
    fam = _family()
    s = _small(fam)
    assert fam.weight_bytes(s, 1) == (584 + 2 * 1 * 72) * 2
    assert fam.weight_bytes(s, 64) == pytest.approx((584 + 2 * 2 * 72) * 2)
    assert fam.held_pairs_per_token(s) == 1.0
    # matmuls: 2 layers x 2 x (192 + 32 + 1 x 72), head 2 x 80;
    # attention 4 x 2 layers x 2 heads x 4 dims per key
    assert fam.decode_flops_per_token(s, 10) == 2 * 2 * 296 + 160 + 64 * 10
    assert fam.prompt_flops_per_token(s, 10) == 2 * 2 * 296 + 64 * 10
    assert fam.kv_row_bytes(s) == 2 * 2 * 1 * 4 * 2
    assert fam.param_count(s) == 2 * (192 + 24 + 32 + 2 * 72) + 80 + 80 + 8


def test_a_dense_key_is_refused():
    """A key the served model reads and the expert family does not (a
    dense MLP width here) is an error: the reference would not follow
    it."""
    with pytest.raises(ValueError, match="d_ff"):
        _family().model_config("tiny", dict(TINY_MOE, d_ff=128))


def test_routing_without_renormalisation_is_refused():
    """The program renormalises the top-k probabilities, as Qwen3-MoE
    publishes (``norm_topk_prob`` true); a configuration that says
    otherwise is an error, and one that says so is served."""
    fam = _family()
    with pytest.raises(ValueError, match="norm_topk_prob"):
        fam.model_config("tiny", dict(TINY_MOE, norm_topk_prob=False))
    assert fam.model_config("tiny", dict(TINY_MOE, norm_topk_prob=True)).moe


@pytest.mark.parametrize("first,held", [(0, 0), (4, 4), (12, 4)])
def test_reference_matches_chunked_prefill_then_paged_decode(
        monkeypatch, first, held):
    """The expert family served through ``Engine`` (dropless routing over
    the held experts, chunked prefill resumed from the prefix cache,
    paged decode) against its reference, which computes every held
    expert densely for every token: every expert, and two shares of four
    of the sixteen. The dense family's tolerance holds (1e-3): in
    float32 the router picks the same experts on both sides, and the
    rest differs only in the order of sums."""
    _chunked_prefill_then_paged_decode(
        monkeypatch, dict(TINY_MOE, held_first=first, experts_held=held))


def test_harness_run_of_an_expert_cell():
    """A traced run of a small expert cell (a held share of 4 of 16
    experts) is correct, reads the fp8 control as not correct, and
    reports the expert counters' reading: held rows per held expert per
    decode layer-step, at most a decode batch's k choices spread over
    the held experts (4 slots x 4 / 4)."""
    cell, cfg, mix, load, manifest = _tiny_cell("closed")
    cfg = dict(TINY_MOE, held_first=4, experts_held=4, engine=cfg["engine"],
               server=cfg["server"])
    out = run.run_cell(cell, cfg, mix, load, manifest, seed=2 ** 31 + 99,
                       seconds=2.0, trace=True, control="fp8")
    assert out["correct"] is True, out["compared"]
    assert out["control"]["fp8"]["correct"] is False
    rows = out["metrics"]["expert_rows_per_step.offline"]["value"]
    assert 0 < rows <= 4


def _expert_run(counters, hists):
    """A run record as the harness hands it to the readers (the small
    spec: 2 of 4 experts held)."""
    fam = _family()
    status0 = {"metrics": {"counters": {k: 0 for k in counters},
                           "histograms": {k: {"count": 0, "sum": 0}
                                          for k in hists}}}
    status1 = {"metrics": {"counters": counters, "histograms": hists}}
    return {"trace": None, "window": (0.0, 10.0), "family": fam,
            "spec": _small(fam), "status0": status0, "status1": status1}


def test_expert_rows_are_read_from_the_counters():
    """The window's decode steps routed 1,280 held pairs over 20 expert
    layer-steps (one sample of the most-loaded expert's rows each): each
    of the 2 held experts computed 1,280 / 20 / 2 = 32 rows per
    layer-step. A program without the expert counters (a dense model,
    or a commit without them) reads nothing, and raises nothing."""
    rows = run._load(CHIP / "metrics" / "expert_rows_per_step.offline.py",
                     "m_rs")
    counters = {"repro_moe_routed_pairs_total": 2560,
                "repro_moe_held_pairs_total": 1280,
                "repro_moe_decode_held_pairs_total": 1280}
    hists = {"repro_moe_max_expert_rows": {"count": 20, "sum": 900}}
    assert rows.read(_expert_run(counters, hists)) == pytest.approx(32.0)
    assert rows.read(_expert_run({}, {})) is None
