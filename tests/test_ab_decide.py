"""tools/ab_decide.py — the A/B decision rules must read the evidence
exactly as documented (docs/performance.md): latest successful leg wins,
>=2% end-to-end margin to flip a default, honest 'unmeasured' otherwise."""

import importlib
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
ab_decide = importlib.import_module("tools.ab_decide")


def _hist(tmp_path, runs):
    path = str(tmp_path / "ab.json")
    with open(path, "w") as f:
        json.dump(runs, f)
    return path


def _run(at, **legs):
    return {"at": at,
            "results": [{"name": n, "ok": r is not None, "result": r}
                        for n, r in legs.items()]}


def test_latest_successful_leg_wins(tmp_path):
    path = _hist(tmp_path, [
        _run("t0", lm_base_bs128_remat={"tokens_per_sec": 100}),
        _run("t1", lm_base_bs128_remat=None),               # failed run
        _run("t2", lm_base_bs128_remat={"tokens_per_sec": 200}),
    ])
    latest = ab_decide.latest_results(path)
    assert latest["lm_base_bs128_remat"]["result"]["tokens_per_sec"] == 200


def test_two_percent_margin_is_not_a_win(tmp_path):
    base = {"tokens_per_sec": 10000}
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", lm_base_bs128_remat=base,
        lm_chunk16384_bs128={"tokens_per_sec": 10100})])))
    assert d["xent_chunk"]["verdict"] != "DEFAULT_16384"    # 1% < margin
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", lm_base_bs128_remat=base,
        lm_chunk16384_bs128={"tokens_per_sec": 10300})])))
    assert d["xent_chunk"]["verdict"] == "DEFAULT_16384"


def test_ring_needs_both_shards_correctness_margin_and_tpu(tmp_path):
    good = {"fwd_pallas_speedup": 1.3, "bwd_pallas_speedup": 1.2,
            "bwd_correctness_ok": True, "platform": "tpu"}
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", ring_ab_local2048=good, ring_ab_local8192=good)])))
    assert d["ring"]["verdict"] == "DEFAULT_RING_PALLAS"
    # correctness failure on one shard
    bad = dict(good, bwd_correctness_ok=False)
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", ring_ab_local2048=good, ring_ab_local8192=bad)])))
    assert d["ring"]["verdict"] == "KEEP_JNP"
    # a 1.00-1.02x "win" is inside within-window variance
    noise = dict(good, fwd_pallas_speedup=1.01)
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", ring_ab_local2048=noise, ring_ab_local8192=good)])))
    assert d["ring"]["verdict"] == "KEEP_JNP"
    # interpret-mode CPU rows are not chip evidence
    cpu = dict(good, platform="cpu")
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", ring_ab_local2048=cpu, ring_ab_local8192=good)])))
    assert d["ring"]["verdict"] == "unmeasured"
    # one shard measured mid-outage is incomplete evidence, not a loss
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", ring_ab_local2048=good)])))
    assert d["ring"]["verdict"] == "unmeasured"


def _probe_rows(**over):
    rows = []
    for s in sorted(ab_decide.PROBE_SHAPES):
        r = {"shape": s, "correctness_ok": True, "pallas_vs_conv": 0.9,
             "matmul_vs_conv": 1.0, "platform": "tpu"}
        r.update(over.get(s, {}))
        rows.append(r)
    return rows


def test_resnet_probe_rows(tmp_path):
    rows = _probe_rows(s3_contract={"pallas_vs_conv": 1.2})
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_1x1_probe=rows)])))
    assert d["resnet_1x1"]["verdict"] == "WIRE_FUSED_KERNEL"
    assert d["resnet_1x1"]["winning_shapes"] == ["s3_contract"]

    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_1x1_probe=_probe_rows())])))
    assert d["resnet_1x1"]["verdict"] == "CLOSE_LEVER"


def test_resnet_partial_or_failed_probe_is_unmeasured(tmp_path):
    """CLOSE_LEVER is permanent — a crashed (partial) or
    correctness-failed probe must stay 'unmeasured', never close the
    lever off missing Pallas measurements (code-review r5)."""
    partial = _probe_rows()[:2]
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_1x1_probe=partial)])))
    assert d["resnet_1x1"]["verdict"] == "unmeasured"
    assert len(d["resnet_1x1"]["missing"]) == 2

    failed = _probe_rows(
        s4_expand={"correctness_ok": False, "pallas_vs_conv": None})
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_1x1_probe=failed)])))
    assert d["resnet_1x1"]["verdict"] == "unmeasured"
    assert d["resnet_1x1"]["missing"] == ["s4_expand"]

    # a complete, correctness-passing CPU/interpret run is NOT chip
    # evidence (code-review r5: the bench.py last-good discipline)
    cpu = _probe_rows()
    for r in cpu:
        r["platform"] = "cpu"
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_1x1_probe=cpu)])))
    assert d["resnet_1x1"]["verdict"] == "unmeasured"


def test_probe_shapes_in_sync_with_harness():
    """ab_decide hardcodes the shape list (resnet_probe imports jax at
    module scope); this pin breaks if they drift."""
    probe = importlib.import_module("tools.resnet_probe")
    assert {s[0] for s in probe.SHAPES} == ab_decide.PROBE_SHAPES


def test_train_probe_shares_the_rule(tmp_path):
    rows = _probe_rows(s4_contract={"pallas_vs_conv": 1.3})
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_1x1_train_probe=rows)])))
    assert d["resnet_1x1_train"]["verdict"] == "WIRE_FUSED_KERNEL"
    assert d["resnet_1x1"]["verdict"] == "unmeasured"   # affine separate


def test_resnet_e2e_fused_rule(tmp_path):
    base = {"value": 2700.0, "platform": "tpu"}
    win = {"value": 2800.0, "platform": "tpu"}
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_bench_default=base, resnet_bench_fused=win)])))
    assert d["resnet_e2e_fused"]["verdict"] == "DEFAULT_FUSED"
    noise = {"value": 2710.0, "platform": "tpu"}
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_bench_default=base, resnet_bench_fused=noise)])))
    assert d["resnet_e2e_fused"]["verdict"] == "KEEP_XLA_CONV"
    # a stale fallback headline is not window evidence
    stale = {"value": 2800.0, "platform": "tpu", "stale": True}
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [_run(
        "t", resnet_bench_default=stale, resnet_bench_fused=win)])))
    assert d["resnet_e2e_fused"]["verdict"] == "unmeasured"
    # legs from DIFFERENT runs are cross-window — never paired
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [
        _run("t0", resnet_bench_default=base),
        _run("t1", resnet_bench_fused=win)])))
    assert d["resnet_e2e_fused"]["verdict"] == "unmeasured"


def test_everything_unmeasured_is_honest(tmp_path):
    d = ab_decide.decide(ab_decide.latest_results(_hist(tmp_path, [])))
    assert all(v["verdict"] == "unmeasured" for v in d.values())
