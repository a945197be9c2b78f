"""The ``qwen3_next`` family and its cell ``qwen3_next_s16384``: the
configuration file against the contract and the catalog, the operation and
byte counts against hand-worked numbers, the family against its plain
reference through the harness's own check, one toy run of the cell through
``harness.run_cell``, and the new readers on a step that lacks their
scopes.  CPU only."""

import dataclasses
import json
import os
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, manifest  # noqa: E402
from benchmark.families import laguna, qwen3_next  # noqa: E402
from benchmark.layer_metrics import roofline  # noqa: E402
from benchmark.layer_metrics.gdn_scan_steps import scan_loop_trips  # noqa: E402

CELL = "qwen3_next_s16384"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# The published pattern at a size the CPU takes (tests/
# test_models_qwen3_next.py holds the same): one period, 16 experts of
# which 4 are held, 3 picks.
TOY_CONFIG = dict(
    hidden_size=64, head_dim=32, num_attention_heads=4,
    num_key_value_heads=2, num_attention_heads_per_layer=[4] * 48,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=16,
    num_experts_per_tok=3, vocab_size=512, experts=4, experts_first=4,
    vocab=256, loss_chunk=96)
TOY_TRAFFIC = dict(seq=128, per_chip_batch=2)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3_next_80b.json")) as f:
        return json.load(f)


def toy_cell(**config_changes):
    cell = manifest.load_cell(CELL)
    cell["config_data"] = {**cell["config_data"], **TOY_CONFIG,
                           **config_changes}
    cell["traffic"] = dict(cell["traffic"], **TOY_TRAFFIC)
    return cell


def toy_family(**config_changes):
    cell = toy_cell(**config_changes)
    return manifest.load_family("qwen3_next").build(cell["config_data"],
                                                    cell["traffic"])


@pytest.fixture()
def v5e_peaks(monkeypatch):
    real = manifest.load_peaks
    monkeypatch.setattr(manifest, "load_peaks",
                        lambda kind: real("TPU v5 lite"))


# ---------------------------------------------------------------------------
# The configuration file.
# ---------------------------------------------------------------------------


def test_the_configuration_keeps_the_contract_and_the_cut_the_issue_states():
    cfg = config()
    entry = [c for c in manifest.load_manifest()["configs"]
             if c["name"] == "qwen3_next_80b"][0]
    manifest.check_config(entry, cfg)
    assert cfg["reduced"] == ["layers", "experts", "vocab"]
    assert cfg["published"] == {"layers": 48, "experts": 512,
                                "vocab": 151936}
    assert (cfg["layers"], cfg["experts"], cfg["vocab"],
            cfg["experts_first"]) == (4, 32, 18992, 0)
    assert "16 chips" in cfg["deployment"] and len(cfg["deployment"]) <= 200
    assert set(cfg["assumed"]) >= {
        "projection_column_order", "initialisation",
        "multi_token_prediction", "float32_in_the_scan", "optimizer",
        "share"}
    # the floors of a cut: a whole period of four layers (no leading dense
    # layer exists), 8 routed experts, an eighth of the vocabulary
    assert cfg["layers"] >= 4 and cfg["layers"] % \
        cfg["full_attention_interval"] == 0 and cfg["experts"] >= 8
    assert cfg["vocab"] * 8 >= cfg["published"]["vocab"]
    # the derived keys say what the source's own say
    assert cfg["layer_types"] == [
        "full_attention" if (i + 1) % cfg["full_attention_interval"] == 0
        else "linear_attention" for i in range(cfg["num_hidden_layers"])]
    assert set(cfg["mlp_layer_types"]) == {"sparse"} and \
        cfg["decoder_sparse_step"] == 1 and cfg["mlp_only_layers"] == []
    assert set(cfg["num_attention_heads_per_layer"]) == {
        cfg["num_attention_heads"]}
    # every leaf the check compares exists in the tree the family inits
    family = manifest.load_family("qwen3_next").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    for path in cfg["tolerances"]["leaf_cosine_min"]:
        harness._leaf(shapes, path)
    assert set(cfg["tolerances"]["leaf_cosine_min"]) >= {
        "embed", "head", "period/0/w_qkvz", "period/0/w_ba",
        "period/0/conv", "period/0/a_log", "period/0/dt_bias",
        "period/0/w_out", "period/1/wq", "period/1/q_norm",
        "period/1/k_norm", "period/0/w_router", "period/0/w_gate",
        "period/0/ws_sg"}
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == 625_667_136             # 625.7M; 10.0 GB at 16 B
    assert round(total * 16 / 1e9, 1) == 10.0


def test_every_number_of_the_catalog_entry_is_in_the_file_under_its_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    # what is cut is depth and the chip's share, never a width
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "num_key_value_heads",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_conv_kernel_dim")
    assert not set(cfg["reduced"]) & set(widths)


# ---------------------------------------------------------------------------
# Counts.
# ---------------------------------------------------------------------------


def test_flops_per_token_by_layer_against_the_issues_arithmetic():
    """Forward multiply-adds a token at seq 16384, in millions: a linear
    layer's projections 33.7 and scan 2.67 (2.93 were the pair products
    counted a value head, ISSUE 33's count), the full layer's projections
    27.3 and scores 67.1, a feed-forward 6.2 (a token lands on held experts
    0.625 times), the head 38.9; 1.60 GFLOP a token forward + backward."""
    cfg = config()
    m = lambda i: {k: v / 1e6  # noqa: E731
                   for k, v in qwen3_next.layer_macs(cfg, i, 16384).items()}
    assert m(0) == m(1) == m(2)
    assert m(0)["projections"] == pytest.approx(33.72, abs=0.01)
    assert m(0)["scan"] == pytest.approx(2.665, abs=0.005)
    assert m(3)["projections"] == pytest.approx(27.26, abs=0.01)
    assert m(3)["scores"] == pytest.approx(67.1, abs=0.1)
    assert qwen3_next.layer_macs(cfg, 3, 16384)["scores"] == \
        2 * 16 * 256 * laguna.visible_pairs(16384) / 16384
    # router 2048 x 512, 0.625 routed experts in expectation and the shared
    # one, 3 x 2048 x 512 each, and the shared expert's gate
    assert qwen3_next.layer_macs(cfg, 0, 16384)["feed_forward"] == \
        2048 * 512 + 1.625 * 3 * 2048 * 512 + 2048
    total = qwen3_next.flops_per_token(cfg, 16384)
    hand = 3 * 2 * (sum(sum(qwen3_next.layer_macs(cfg, i, 16384).values())
                        for i in range(4)) + 2048 * 18992)
    assert total == hand
    assert total / 1e9 == pytest.approx(1.60, abs=0.01)
    assert total * 16384 / 1e12 == pytest.approx(26.3, abs=0.1)


def test_scan_cost_at_the_cells_shapes():
    cfg = config()
    macs = qwen3_next.scan_macs(cfg)
    from horovod_tpu.ops.gated_delta import CHUNK, scan_macs_per_token

    assert qwen3_next.CHUNK == CHUNK
    assert macs == scan_macs_per_token(key_heads=16, value_heads=32,
                                       key_dim=128, value_dim=128)
    ops, nbytes = qwen3_next.scan_cost(cfg, tokens=16384)
    assert ops == 2 * 4 * macs * 16384      # forward, recompute, 2 backward
    a_pass = 16384 * (2 * 2048 * 2 + 4096 * 2 + 2 * 32 * 4 + 4096 * 4)
    states = 256 * 32 * 128 * 128 * 4       # 537 MB of chunk states
    assert nbytes == 4 * a_pass + 3 * states
    least, bound = roofline(ops, nbytes, manifest.load_peaks("TPU v5 lite"))
    assert bound == "hbm" and 1e3 * least == pytest.approx(4.61, abs=0.02)


def test_the_flash_calls_and_the_grouped_products_at_this_cells_widths():
    """What the accepted roofline readers compute here: the full-causal
    calls at 16 heads over 2 of head_dim 256, one sequence of 16,384; the
    grouped products on 16,384 x 10 x 32 / 512 rows."""
    peaks = manifest.load_peaks("TPU v5 lite")
    shape = dict(batch=1, seq=16384, heads=16, kv_heads=2, head_dim=256)
    ops, nbytes = laguna.flash_call_cost(**shape)
    assert ops == 2 * 2 * 16 * (16384 * 16385 // 2) * 256
    least, bound = roofline(ops, nbytes, peaks)
    assert bound == "compute" and 1e3 * least == pytest.approx(11.2, abs=0.1)
    least_b, _ = roofline(*laguna.flash_call_cost(backward=True, **shape),
                          peaks)
    assert least_b == pytest.approx(2.5 * least)
    least_e, bound_e = roofline(*laguna.expert_products_cost(
        rows=16384 * 10 * 32 / 512, d_model=2048, d_ff=512, experts=32),
        peaks)
    assert bound_e == "compute" and 1e3 * least_e == pytest.approx(
        1.31, abs=0.01)


def test_the_state_loops_trips_are_read_from_either_form_of_hlo():
    """``known_trip_count`` where the compiler wrote one (the CPU's HLO);
    else the constant the loop's condition compares with (the TPU's)."""
    tpu = """
%cond.7 (arg: (s32[], f32[1,2])) -> pred[] {
  %constant.1 = s32[]{:T(128)} constant(256), metadata={op_name="x"}
  %gte = s32[] get-tuple-element(%arg), index=0
  ROOT %lt = pred[] compare(%gte, %constant.1), direction=LT
}

%other (arg: (s32[])) -> pred[] {
  %constant.2 = s32[] constant(3)
}

ENTRY %main () -> f32[] {
  %while.1 = (s32[], f32[1,2]) while(%t), condition=%cond.7, body=%body.7, metadata={op_name="jit(step)/jvp()/hvdt.gdn/hvdt.gdn.scan/while"}
  %while.2 = (s32[]) while(%t), condition=%other, body=%b, metadata={op_name="jit(step)/jvp()/while"}
}
"""
    assert scan_loop_trips(tpu) == [256]
    cpu = ('  %while.3 = (s32[]) while(%t), condition=%c, body=%b, '
           'metadata={op_name="a/hvdt.gdn.scan/while"}, '
           'backend_config={"known_trip_count":{"n":"64"}}\n')
    assert scan_loop_trips(cpu) == [64]
    assert scan_loop_trips("ENTRY %main () -> f32[] {\n}") == []


# ---------------------------------------------------------------------------
# The family against its reference, and one run of the cell.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mosaic", [False, True], ids=["xla", "kernels"])
def test_family_and_reference_agree_in_float32(mosaic):
    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    got = harness.reference_check(family, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=mosaic)
    assert got["loss_rel"] < 1e-5
    assert got["grad_norm_rel"] < 1e-4
    assert min(got["leaf_cosine"].values()) > 0.9999
    assert set(got["leaf_cosine"]) == set(
        config()["tolerances"]["leaf_cosine_min"])


@pytest.mark.parametrize("wrong", [dict(partial_rotary_factor=0.5),
                                   dict(norm_topk_prob=False)],
                         ids=["half_the_head_rotates",
                              "picks_not_normalised"])
def test_reference_check_fails_a_wrong_model(wrong):
    """Held to what float32 allows (the agreement test above reads 1e-5
    and 0.9999), a reference that rotates another share of a head or leaves
    the picks' weights unnormalised fails the harness's own check."""
    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    tight = dict(loss_rel=1e-4, grad_norm_rel=1e-3, leaf_cosine_min={
        p: 0.999 for p in family.tolerances["leaf_cosine_min"]})
    other = toy_family(compute_dtype="float32", **wrong)
    got = harness.reference_check(
        dataclasses.replace(family, tolerances=tight,
                            reference_loss=other.reference_loss),
        params, jax.random.PRNGKey(1), jax.devices()[0], mosaic=False)
    assert not got["ok"], got


def test_the_optimizer_is_lagunas_warm_up():
    assert config()["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                     "warmup_steps": 2000}
    assert qwen3_next.optimizer_of is laguna.optimizer_of


def test_tokens_are_drawn_from_the_held_slice_of_the_vocabulary():
    family = toy_family()
    (tokens,) = family.make_batch(jax.random.PRNGKey(0), 4)
    assert tokens.shape == (4, 128) and int(tokens.max()) < 256
    assert int((tokens == 0).sum()) > 0.05 * tokens.size    # skew 4


def test_run_cell_at_toy_size(hvd, devices, v5e_peaks, tmp_path):
    cell = toy_cell()
    assert cell["end_to_end"] == ["tokens_per_s_chip", "peak_hbm_gib",
                                  "setup_s"]
    result = harness.run_cell(
        cell, devices, seed=2_147_483_659, seconds=4.0, trace=False,
        started_at=time.perf_counter(), trace_dir=str(tmp_path))
    assert result["failed"] == 0 and result["attempted"] >= 8
    checks = result["checks"]
    assert checks["window"]["loss_falls"] and \
        checks["window"]["compiles_in_window"] == 0
    assert set(result["metrics"]) == set(cell["end_to_end"])
    assert result["metrics"]["tokens_per_s_chip"]["value"] > 0
    # bf16 at toy size on the CPU is not the chip's reading: the check ran
    # and read every leaf it names.
    assert set(checks["reference"]["leaf_cosine"]) == set(
        cell["config_data"]["tolerances"]["leaf_cosine_min"])


# ---------------------------------------------------------------------------
# The readers.
# ---------------------------------------------------------------------------

NEW_READERS = ["gdn_ms", "gdn_conv_ms", "gdn_scan_ms", "gdn_scan_roofline",
               "gdn_scan_steps"]


def test_the_manifest_gives_the_cell_its_readers_and_no_old_cell_the_new():
    cell = manifest.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["name"] == "s16384"
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    for generic in ("host_gap_ms", "mfu_pct", "step_device_ms",
                    "device_idle_pct", "fwd_ms", "remat_ms", "bwd_ms",
                    "attention_ms", "loss_ms", "optimizer_ms", "unscoped_ms",
                    "moe_ms", "moe_dispatch_ms", "moe_experts_ms",
                    "moe_experts_roofline", "flash_fwd_ms", "flash_bwd_ms",
                    "flash_full_fwd_roofline", "flash_full_bwd_roofline",
                    "compile_s", "hbm_temp_gib"):
        assert generic in cell["layer_metrics"], generic
    # no windowed layer here; flash_fwd_roofline's own reader divides
    # d_model by heads
    for other in ("flash_win_fwd_ms", "flash_win_bwd_roofline",
                  "flash_fwd_roofline"):
        assert other not in cell["layer_metrics"]
    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NEW_READERS:
        assert per_layer[name]["layer"] == "linear mixer"
        assert per_layer[name]["moves"] == "tokens_per_s_chip"
        assert per_layer[name]["workloads"] == [CELL]
    for old in ("lm24x1024_s4096_b8", "resnet50_train", "laguna_xs2_s8192"):
        assert not set(NEW_READERS) & set(
            manifest.load_cell(old)["layer_metrics"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name):
    """On a program from before this PR (no ``hvdt.gdn``) and without a
    trace, a reader returns None and does not raise."""
    cell = manifest.load_cell(CELL)
    ctx = harness.Context(
        config=cell["config_data"], traffic=cell["traffic"], family=None,
        chips=1, peaks=manifest.load_peaks("TPU v5 lite"),
        hlo_text="ENTRY %main () -> f32[] {\n}", memory=None,
        setup_compile_s=0.0, throughput=1.0, trace=None)
    assert manifest.load_layer_metric(name)(ctx) is None
    other = manifest.load_cell("laguna_xs2_s8192")
    ctx = dataclasses.replace(ctx, config=other["config_data"],
                              traffic=other["traffic"])
    assert manifest.load_layer_metric(name)(ctx) is None
