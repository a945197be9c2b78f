"""The ``laguna`` family and its cell ``laguna_xs2_s8192``: the
configuration file against the contract and the catalog, the operation and
byte counts against hand-worked numbers, the family against its plain
reference through the harness's own check, one toy run of the cell
through ``harness.run_cell``, and the new readers on a step that lacks
their scopes.  CPU only."""

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
from benchmark.families import laguna  # noqa: E402
from benchmark.layer_metrics import roofline  # noqa: E402

CELL = "laguna_xs2_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# The published pattern at a size the CPU takes (tests/test_models_pattern.py
# holds the same): every kind of layer, 16 experts of which 4 are held.
TOY_CONFIG = dict(
    hidden_size=64, head_dim=32, num_key_value_heads=2, sliding_window=16,
    intermediate_size=128, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts=16,
    num_experts_per_tok=2, vocab_size=512, experts=4, experts_first=4,
    vocab=256, loss_chunk=96)
TOY_TRAFFIC = dict(seq=64, per_chip_batch=2)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_xs2.json")) as f:
        return json.load(f)


def toy_cell(**config_changes):
    cell = manifest.load_cell(CELL)
    heads = [6 if h == 48 else 8 for h in
             cell["config_data"]["num_attention_heads_per_layer"]]
    cell["config_data"] = {**cell["config_data"], **TOY_CONFIG,
                           "num_attention_heads_per_layer": heads,
                           **config_changes}
    cell["traffic"] = dict(cell["traffic"], **TOY_TRAFFIC)
    return cell


def toy_family(**config_changes):
    cell = toy_cell(**config_changes)
    return manifest.load_family("laguna").build(cell["config_data"],
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
             if c["name"] == "laguna_xs2"][0]
    manifest.check_config(entry, cfg)
    assert cfg["reduced"] == ["layers", "experts", "vocab"]
    assert cfg["published"] == {"layers": 40, "experts": 256,
                                "vocab": 100352}
    assert (cfg["layers"], cfg["experts"], cfg["vocab"],
            cfg["experts_first"]) == (5, 32, 12544, 0)
    assert "8 chips" in cfg["deployment"] and len(cfg["deployment"]) <= 200
    assert set(cfg["assumed"]) >= {"router_score", "gating", "qk_norm",
                                   "sliding_window", "optimizer"}
    # the floors of a cut: a whole period and four layers after the
    # leading dense one, 8 routed experts, an eighth of the vocabulary
    assert cfg["layers"] - 1 >= 4 and cfg["experts"] >= 8
    assert cfg["vocab"] * 8 >= cfg["published"]["vocab"]
    # every leaf the check compares exists in the tree the family inits
    family = manifest.load_family("laguna").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    for path in cfg["tolerances"]["leaf_cosine_min"]:
        harness._leaf(shapes, path)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 691_623_936


def test_every_number_of_the_catalog_entry_is_in_the_file_under_its_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "Laguna-XS.2"][0]
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key       # widths, patterns, rope groups
    # what is cut is depth and the chip's share, never a width
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "num_key_value_heads", "sliding_window")
    assert not set(cfg["reduced"]) & set(widths)


# ---------------------------------------------------------------------------
# Counts.
# ---------------------------------------------------------------------------


def test_visible_pairs_of_a_window():
    assert laguna.visible_pairs(8) == 36
    assert laguna.visible_pairs(8, 3) == sum(min(i + 1, 3) for i in range(8))
    assert laguna.visible_pairs(8192, 512) == sum(
        min(i + 1, 512) for i in range(8192)) == 4_063_488
    assert laguna.visible_pairs(8, 100) == 36       # the window never bites
    # 12% of the causal half, 6% of the square
    assert laguna.visible_pairs(8192, 512) / laguna.visible_pairs(8192) == \
        pytest.approx(0.121, abs=1e-3)


def test_flops_per_token_by_layer_against_the_issues_arithmetic():
    """Forward matmul operations a token at seq 8192, in millions (2 a
    multiply-add): layer 0 projections 59, scores 101, dense MLP 101; a
    sliding sparse layer 76 / 16 / 14; the full sparse layer 173 in all;
    the head over 12,544 rows 51; 802 together, 2.4 GFLOP forward +
    backward."""
    cfg = config()
    m = lambda i: {k: 2 * v / 1e6  # noqa: E731
                   for k, v in laguna.layer_macs(cfg, i, 8192).items()}
    assert m(0)["projections"] == pytest.approx(58.9, abs=0.1)
    assert m(0)["scores"] == pytest.approx(100.7, abs=0.1)
    assert m(0)["feed_forward"] == pytest.approx(100.7, abs=0.1)
    assert m(1) == m(2) == m(3)
    assert m(1)["projections"] == pytest.approx(75.8, abs=0.1)
    assert m(1)["scores"] == pytest.approx(16.3, abs=0.1)
    # router 2048 x 256, one routed expert in expectation (8 x 32 / 256)
    # and the shared one, 3 x 2048 x 512 each
    assert laguna.layer_macs(cfg, 1, 8192)["feed_forward"] == \
        2048 * 256 + 2 * 3 * 2048 * 512
    assert sum(m(4).values()) == pytest.approx(173.2, abs=0.2)
    total = laguna.flops_per_token(cfg, 8192)
    hand = 3 * 2 * (sum(sum(laguna.layer_macs(cfg, i, 8192).values())
                        for i in range(5)) + 2048 * 12544)
    assert total == hand
    assert total / 3 / 1e6 == pytest.approx(802, abs=1.5)
    scores = 3 * 2 * sum(laguna.layer_macs(cfg, i, 8192)["scores"]
                         for i in range(5))
    assert scores / total == pytest.approx(0.31, abs=0.01)


def test_flash_call_costs_at_the_cells_shapes():
    shape = dict(batch=2, seq=8192, kv_heads=8, head_dim=128)
    tensor = 2 * 8192 * 128 * 2                     # bytes a head
    ops, nbytes = laguna.flash_call_cost(heads=64, window=512, **shape)
    assert ops == 2 * 2 * 2 * 64 * 4_063_488 * 128
    assert nbytes == tensor * (2 * 64 + 2 * 8)      # q, o; k, v
    ops_b, bytes_b = laguna.flash_call_cost(heads=64, window=512,
                                            backward=True, **shape)
    assert ops_b == 2.5 * ops
    assert bytes_b == tensor * (5 * 64 + 2 * 8)     # q, dO, dq, dk, dv; k, v
    full, _ = laguna.flash_call_cost(heads=48, **shape)
    assert full == 2 * 2 * 2 * 48 * (8192 * 8193 // 2) * 128
    # both compute-bound on the v5e; a windowed forward's least time is a
    # sixth of the full one's
    peaks = manifest.load_peaks("TPU v5 lite")
    least_w, bound_w = roofline(ops, nbytes, peaks)
    least_f, bound_f = roofline(*laguna.flash_call_cost(heads=48, **shape),
                                peaks)
    assert bound_w == bound_f == "compute"
    assert 1e3 * least_f == pytest.approx(8.37, abs=0.02)
    assert 1e3 * least_w == pytest.approx(1.35, abs=0.02)


def test_expert_products_cost_at_the_cells_load():
    ops, nbytes = laguna.expert_products_cost(rows=16384, d_model=2048,
                                              d_ff=512, experts=32)
    assert ops == 12 * 2 * 16384 * 2048 * 512       # 3 + 3 + 6 products
    assert nbytes == 4 * 32 * 3 * 2048 * 512 * 2    # 3 reads, 1 write
    least, bound = roofline(ops, nbytes, manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * least == pytest.approx(2.09, abs=0.01)


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


@pytest.mark.parametrize("wrong", [dict(sliding_window=17),
                                   dict(moe_routed_scaling_factor=1.0)],
                         ids=["window+1", "no_scaling_factor"])
def test_reference_check_fails_a_wrong_model(wrong):
    """Held to what float32 allows (the agreement test above reads 1e-5
    and 0.9999), a reference one window position or the scaling factor
    away from the system fails the harness's own check."""
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


def test_the_optimizer_warms_up_to_the_configurations_rate():
    """``warmup_steps`` (this family's key): the rate climbs linearly from
    0 and is the configuration's 3e-4 from step 2000 on; the update is
    AdamW's with that rate."""
    import jax.numpy as jnp
    import optax

    spec = config()["optimizer"]
    assert spec == {"name": "adamw", "learning_rate": 3e-4,
                    "warmup_steps": 2000}
    opt = laguna.optimizer_of(spec)
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 0.5)}
    state = opt.init(params)
    sizes = []
    for _ in range(3):
        updates, state = opt.update(grads, state, params)
        sizes.append(float(-updates["w"][0]))
    # step n moves a weight by about rate_n (Adam's unit step) + decay
    assert sizes[0] == 0.0
    assert sizes[1] == pytest.approx(3e-4 / 2000 * (1 + 1e-4), rel=1e-3)
    assert sizes[2] == pytest.approx(2 * sizes[1], rel=1e-3)
    plain = laguna.optimizer_of({"name": "adamw", "learning_rate": 3e-4})
    updates, _ = plain.update(grads, plain.init(params), params)
    assert float(-updates["w"][0]) == pytest.approx(3e-4 * (1 + 1e-4),
                                                    rel=1e-3)
    assert isinstance(opt, optax.GradientTransformation)


def test_tokens_are_drawn_from_the_held_slice_of_the_vocabulary():
    family = toy_family()
    (tokens,) = family.make_batch(jax.random.PRNGKey(0), 4)
    assert tokens.shape == (4, 64) and int(tokens.max()) < 256
    assert int((tokens == 0).sum()) > 0.05 * tokens.size    # skew 4


def test_run_cell_at_toy_size(hvd, devices, v5e_peaks, tmp_path):
    cell = toy_cell()
    assert cell["end_to_end"] == ["tokens_per_s_chip", "peak_hbm_gib",
                                  "setup_s"]
    result = harness.run_cell(
        cell, devices, seed=2_147_483_659, seconds=1.0, trace=False,
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

NEW_READERS = ["moe_ms", "moe_dispatch_ms", "moe_experts_ms",
               "moe_experts_roofline", "flash_win_fwd_ms",
               "flash_win_fwd_roofline", "flash_win_bwd_ms",
               "flash_win_bwd_roofline", "flash_full_fwd_roofline",
               "flash_full_bwd_roofline"]


def test_the_manifest_gives_the_cell_its_readers_and_no_old_cell_the_new():
    cell = manifest.load_cell(CELL)
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    for generic in ("host_gap_ms", "mfu_pct", "step_device_ms",
                    "device_idle_pct", "fwd_ms", "remat_ms", "bwd_ms",
                    "attention_ms", "loss_ms", "optimizer_ms", "unscoped_ms",
                    "flash_fwd_ms", "flash_bwd_ms", "compile_s",
                    "hbm_temp_gib"):
        assert generic in cell["layer_metrics"], generic
    # flash_fwd_roofline's own reader divides d_model by heads: not here
    assert "flash_fwd_roofline" not in cell["layer_metrics"]
    for old in ("lm24x1024_s4096_b8", "resnet50_train"):
        assert not set(NEW_READERS) & set(
            manifest.load_cell(old)["layer_metrics"])


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name):
    """On a program from before this PR (no ``hvdt.moe``, no windowed
    kernel) and without a trace, a reader returns None and does not
    raise."""
    cell = manifest.load_cell(CELL)
    ctx = harness.Context(
        config=cell["config_data"], traffic=cell["traffic"], family=None,
        chips=1, peaks=manifest.load_peaks("TPU v5 lite"),
        hlo_text="ENTRY %main () -> f32[] {\n}", memory=None,
        setup_compile_s=0.0, throughput=1.0, trace=None)
    assert manifest.load_layer_metric(name)(ctx) is None
    other = manifest.load_cell("lm24x1024_s4096_b8")
    ctx = dataclasses.replace(ctx, config=other["config_data"],
                              traffic=other["traffic"])
    assert manifest.load_layer_metric(name)(ctx) is None
