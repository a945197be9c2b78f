"""The ``sdar`` family and its cell ``sdar_30b_s8192``: the configuration
file against the contract and the catalog, the pair, operation and byte
counts against a brute-force count and hand-worked numbers, the family
against its plain reference through the harness's own check, one toy run of
the cell through ``harness.run_cell``, and the six new readers on a small
recorded step (``data/bd_trace.json``, ``data/bd_step.hlo.txt``) and on a
step that lacks their scopes.  CPU only."""

import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, manifest  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.families import laguna, sdar  # noqa: E402
from benchmark.layer_metrics import roofline  # noqa: E402
from benchmark.reference import sdar as reference  # noqa: E402

CELL = "sdar_30b_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(__file__), "data")

# The published layer at a size the CPU takes (tests/test_models_sdar.py
# holds the same): two layers, 16 experts of which 4 are held, 4 picks.
TOY_CONFIG = dict(
    hidden_size=64, head_dim=16, num_attention_heads=8,
    num_key_value_heads=2, num_attention_heads_per_layer=[8] * 48,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
    vocab_size=512, layers=2, experts=4, experts_first=4, vocab=256,
    loss_chunk=96)
TOY_TRAFFIC = dict(seq=128, per_chip_batch=2)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar_30b_a3b.json")) as f:
        return json.load(f)


def toy_cell(**config_changes):
    cell = manifest.load_cell(CELL)
    cell["config_data"] = {**cell["config_data"], **TOY_CONFIG,
                           **config_changes}
    cell["traffic"] = dict(cell["traffic"], **TOY_TRAFFIC)
    return cell


def toy_family(**config_changes):
    cell = toy_cell(**config_changes)
    return manifest.load_family("sdar").build(cell["config_data"],
                                              cell["traffic"])


@pytest.fixture()
def v5e_peaks(monkeypatch):
    real = manifest.load_peaks
    monkeypatch.setattr(manifest, "load_peaks",
                        lambda kind: real("TPU v5 lite"))


# ---------------------------------------------------------------------------
# The configuration file and the cell.
# ---------------------------------------------------------------------------


def test_the_configuration_keeps_the_contract_and_the_cut_the_issue_states():
    cfg = config()
    entry = [c for c in manifest.load_manifest()["configs"]
             if c["name"] == "sdar_30b_a3b"][0]
    manifest.check_config(entry, cfg)
    assert cfg["reduced"] == ["layers", "experts", "vocab"]
    assert cfg["published"] == {"layers": 48, "experts": 128,
                                "vocab": 151936}
    assert (cfg["layers"], cfg["experts"], cfg["vocab"],
            cfg["experts_first"]) == (6, 16, 18992, 0)
    assert "8 chips" in cfg["deployment"] and len(cfg["deployment"]) <= 200
    assert set(cfg["assumed"]) >= {
        "block_length", "noise_schedule", "stratified_t", "no_logit_shift",
        "mask_token", "qk_norm", "loss_normalisation", "router",
        "initialisation", "optimizer", "absent_experts"}
    # no key of its own beside the source's, the share and the repo's
    assert "qk_norm_gain_init" not in cfg
    # the floors of a cut: four layers (period 1, no leading dense layer),
    # 8 routed experts, an eighth of the vocabulary
    assert cfg["layers"] >= 4 and cfg["experts"] >= 8
    assert cfg["vocab"] * 8 >= cfg["published"]["vocab"]
    # the derived keys say what the source's own say
    assert set(cfg["layer_types"]) == {"full_attention"} and \
        not cfg["use_sliding_window"] and cfg["sliding_window"] is None
    assert set(cfg["mlp_layer_types"]) == {"sparse"} and \
        cfg["decoder_sparse_step"] == 1 and cfg["mlp_only_layers"] == []
    assert set(cfg["num_attention_heads_per_layer"]) == {
        cfg["num_attention_heads"]}
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 48
    # every leaf the check compares exists in the tree the family inits
    family = manifest.load_family("sdar").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    for path in cfg["tolerances"]["leaf_cosine_min"]:
        harness._leaf(shapes, path)
    assert set(cfg["tolerances"]["leaf_cosine_min"]) >= {
        "embed", "head", "period/0/wq", "period/0/wk", "period/0/q_norm",
        "period/0/k_norm", "period/0/wo", "period/0/w_router",
        "period/0/w_gate", "period/0/w_up", "period/0/w_down"}
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == 645_623_296             # 645.6M; 10.33 GB at 16 B
    assert round(total * 16 / 1e9, 2) == 10.33


def test_every_number_of_the_catalog_entry_is_in_the_file_under_its_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"][0]
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    # what is cut is depth and the chip's share, never a width
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "moe_intermediate_size", "num_experts_per_tok",
              "num_attention_heads", "num_key_value_heads", "num_experts")
    assert not set(cfg["reduced"]) & set(widths)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_experts"]) == (
                2048, 128, 32, 4, 768, 8, 128)


def test_the_cell_is_the_issues_traffic():
    cell = manifest.load_cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "sdar_30b_a3b"
    t = cell["traffic"]
    assert t == {"name": "s8192_bd4", "seq": 8192, "per_chip_batch": 1,
                 "rows_per_token": 2, "block_length": 4, "pool_batches": 8,
                 "sample_sequences": 1, "token_skew": 4}
    assert (cell["warmup_steps"], cell["trace_steps"]) == (1, 3)
    assert t["seq"] * t["per_chip_batch"] * t["rows_per_token"] == 16384
    entry = [w for w in manifest.load_manifest()["workloads"]
             if w["name"] == CELL][0]
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    with pytest.raises(ValueError, match="blocks of 32"):
        manifest.load_family("sdar").build(
            cell["config_data"], dict(t, block_length=32))


# ---------------------------------------------------------------------------
# Counts.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seq, block", [(64, 4), (96, 32), (128, 1),
                                        (32, 32)])
def test_visible_pairs_against_a_brute_force_count_of_the_dense_mask(
        seq, block):
    mask = np.asarray(reference.visible(seq, block))
    assert mask.shape == (2 * seq, 2 * seq)
    assert int(mask.sum()) == sdar.visible_pairs_bd(seq, block) == \
        seq * seq + seq * block
    # by term: block-diagonal, strictly earlier, block-causal, nothing
    assert int(mask[:seq, :seq].sum()) == seq * block
    assert int(mask[:seq, seq:].sum()) == seq * (seq - block) // 2
    assert int(mask[seq:, seq:].sum()) == seq * (seq + block) // 2
    assert not mask[seq:, :seq].any()


def test_flops_per_token_against_a_hand_count():
    """Forward multiply-adds a DATA token of a layer at seq 8192, in
    millions: projections 37.7 (wq and wo 8.39 each, wk and wv 1.05 each,
    on two rows), scores 67.1 (2 x 32 x 128 x (8192 + 4)), feed-forward
    9.96 (the router 0.26 and one held expert of 3 x 2048 x 768 in
    expectation, on two rows); the head 38.9 on the noisy row alone; 4.37
    GFLOP a token forward + backward, 35.8 TFLOP a step."""
    cfg = config()
    m = sdar.layer_macs(cfg, 8192)
    assert m["projections"] == 2 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    assert m["scores"] == 2 * 32 * 128 * (8192 + 4)
    assert m["feed_forward"] == 2 * (2048 * 128 + 1.0 * 3 * 2048 * 768)
    assert {k: round(v / 1e6, 2) for k, v in m.items()} == {
        "projections": 37.75, "scores": 67.14, "feed_forward": 9.96}
    total = sdar.flops_per_token(cfg, 8192)
    assert total == 3 * 2 * (6 * sum(m.values()) + 2048 * 18992)
    assert total / 1e9 == pytest.approx(4.37, abs=0.01)
    assert total * 8192 / 1e12 == pytest.approx(35.8, abs=0.1)
    family = manifest.load_family("sdar").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    assert family.flops_per_unit == total and family.units_per_sample == 8192


def test_the_block_mask_calls_and_the_grouped_products_at_the_cells_widths():
    """One call over the 16,384 rows of a sequence at 32 heads over 4 of
    128: 67,141,632 pairs a head, 1.10 TFLOP and 5.6 ms forward, 2.75 TFLOP
    and 14.0 ms backward, compute-bound; the grouped products on 2 x 8192
    x 8 x 16 / 128 = 16,384 rows of width 768."""
    peaks = manifest.load_peaks("TPU v5 lite")
    shape = dict(batch=1, seq=8192, heads=32, kv_heads=4, head_dim=128,
                 block=4)
    assert sdar.visible_pairs_bd(8192, 4) == 67_141_632
    ops, nbytes = sdar.flash_bd_call_cost(**shape)
    assert ops == 2 * 2 * 32 * 67_141_632 * 128
    assert nbytes == 16384 * 128 * 2 * (2 * 32 + 2 * 4)
    least, bound = roofline(ops, nbytes, peaks)
    assert bound == "compute" and 1e3 * least == pytest.approx(5.58,
                                                               abs=0.02)
    ops_b, nbytes_b = sdar.flash_bd_call_cost(backward=True, **shape)
    assert ops_b == 2.5 * ops
    assert nbytes_b == 16384 * 128 * 2 * (5 * 32 + 2 * 4)
    assert 1e3 * roofline(ops_b, nbytes_b, peaks)[0] == pytest.approx(
        13.96, abs=0.05)
    # twice a causal call's pairs on L rows, a quarter of the 2 L square
    assert sdar.visible_pairs_bd(8192, 4) / laguna.visible_pairs(8192) == \
        pytest.approx(2.0, abs=0.001)
    least_e, bound_e = roofline(*laguna.expert_products_cost(
        rows=2 * 8192 * 8 * 16 / 128, d_model=2048, d_ff=768, experts=16),
        peaks)
    assert bound_e == "compute" and 1e3 * least_e == pytest.approx(
        3.14, abs=0.01)


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


_VISIBLE = reference.visible


def _leaky(length, block):
    """The reference's mask with the noisy stream let see the clean copy
    of its own block: <= for < in the strictly-earlier term."""
    real = _VISIBLE(length, block)
    row = np.arange(2 * length)
    blk = (row % length) // block
    own = (row[:, None] < length) & (row[None, :] >= length) & \
        (blk[:, None] == blk[None, :])
    return real | own


@pytest.mark.parametrize("wrong", ["own_block_leak", "no_1_over_t",
                                   "logits_shifted_by_one"])
def test_reference_check_fails_a_wrong_objective(wrong, monkeypatch):
    """Held to what float32 allows (the agreement test above reads 1e-5
    and 0.9999), a reference whose noisy rows see their own clean block,
    one without the 1 / t weight and one that predicts the next token fail
    the harness's own check."""
    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    tight = dict(loss_rel=1e-4, grad_norm_rel=1e-3, leaf_cosine_min={
        p: 0.999 for p in family.tolerances["leaf_cosine_min"]})
    other = family.reference_loss
    if wrong == "own_block_leak":
        monkeypatch.setattr(reference, "visible", _leaky)
    elif wrong == "no_1_over_t":
        other = lambda p, tokens, t, masked: family.reference_loss(  # noqa: E731
            p, tokens, jax.numpy.ones_like(t), masked)
    else:
        other = lambda p, tokens, t, masked: family.reference_loss(  # noqa: E731
            p, jax.numpy.roll(tokens, -1, 1), t, masked)
    got = harness.reference_check(
        dataclasses.replace(family, tolerances=tight, reference_loss=other),
        params, jax.random.PRNGKey(1), jax.devices()[0], mosaic=False)
    assert not got["ok"], got
    if wrong == "no_1_over_t":
        assert got["loss_rel"] > 0.1


def test_the_optimizer_is_lagunas_warm_up():
    assert config()["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                     "warmup_steps": 2000}
    assert sdar.optimizer_of is laguna.optimizer_of


def test_a_batch_is_tokens_of_the_held_slice_with_noise_of_its_own_key():
    family = toy_family()
    tokens, t, masked = family.make_batch(jax.random.PRNGKey(0), 4)
    assert tokens.shape == masked.shape == (4, 128) and t.shape == (4, 32)
    # the mask token (the last held row) is never data
    assert int(tokens.max()) < 255
    assert int((tokens == 0).sum()) > 0.05 * tokens.size    # skew 4
    assert float(t.min()) >= 1e-3 and float(t.max()) < 1.0
    # stratified: a sequence's 32 blocks hold every 32nd of [eps, 1) once
    strata = np.sort(np.floor((np.asarray(t) - 1e-3) / (1 - 1e-3) * 32), 1)
    np.testing.assert_array_equal(strata, np.tile(np.arange(32.0), (4, 1)))
    # the same key gives the same batch; another slot's key other tokens
    # AND another realisation of the noise (both come from --seed)
    again = family.make_batch(jax.random.PRNGKey(0), 4)
    for a, b in zip((tokens, t, masked), again):
        np.testing.assert_array_equal(a, b)
    tokens2, t2, masked2 = family.make_batch(jax.random.PRNGKey(1), 4)
    assert not np.array_equal(tokens, tokens2)
    assert not np.array_equal(t, t2) and not np.array_equal(masked, masked2)


def _toy_family_and_its_model(monkeypatch):
    """The toy family and the ``TransformerConfig`` it hands
    ``transformer_init``."""
    import horovod_tpu.models as models

    seen, real = [], models.transformer_init
    monkeypatch.setattr(
        models, "transformer_init",
        lambda key, cfg: seen.append(cfg) or real(key, cfg))
    family = toy_family()
    jax.eval_shape(family.init, jax.random.PRNGKey(0))
    return family, real, seen[0]


@pytest.mark.parametrize("rows", ["alike", "apart"])
def test_the_router_starts_with_every_rank_its_share_of_a_rows_picks(
        rows, monkeypatch):
    """What makes a seed's work the next seed's: under the family's
    initialisation every row sends picks / ranks of its picks to each
    rank, whether the rows are one vector (a random layer's attention
    leaves them nearly so) or nothing alike; under ``transformer_init``'s
    own router rows that are alike send this rank what the seed gives."""
    from horovod_tpu.parallel.moe import moe_route

    family, transformer_init, model = _toy_family_and_its_model(monkeypatch)
    cfg = toy_cell()["config_data"]
    held, routed, picks = (cfg["experts"], cfg["num_experts"],
                           cfg["num_experts_per_tok"])
    ranks = routed // held
    x = jax.random.normal(jax.random.PRNGKey(7), (512, cfg["hidden_size"]))
    if rows == "alike":
        x = x[:1] + 0.05 * x
    landed = []
    for seed in range(6):
        params = jax.jit(family.init)(jax.random.PRNGKey(seed))
        for w_router in params["period"]["0"]["w_router"][:, 0]:
            _, experts, weights = moe_route(
                x, w_router, top_k=picks, score="softmax", normalize=True)
            on_rank = np.stack([(np.asarray(experts) // held == r).sum(1)
                                for r in range(ranks)], 1)
            np.testing.assert_array_equal(on_rank, picks // ranks)
            np.testing.assert_allclose(np.asarray(weights), 1 / picks,
                                       rtol=1e-6)
            landed.append(int(on_rank[:, cfg["experts_first"] // held].sum()))
    assert set(landed) == {512 * picks // ranks}
    if rows == "alike":
        spread = set()
        for seed in range(6):
            params = transformer_init(jax.random.PRNGKey(seed), model)
            _, experts, _ = moe_route(
                x, params["period"]["0"]["w_router"][0, 0], top_k=picks,
                score="softmax", normalize=True)
            spread.add(int((np.asarray(experts) // held
                            == cfg["experts_first"] // held).sum()))
        assert max(spread) > 2 * min(spread)


def test_the_family_changes_the_routers_columns_and_no_other_weight(
        monkeypatch):
    family, transformer_init, model = _toy_family_and_its_model(monkeypatch)
    held = toy_cell()["config_data"]["experts"]
    ours = jax.jit(family.init)(jax.random.PRNGKey(3))
    theirs = jax.jit(lambda key: transformer_init(key, model))(
        jax.random.PRNGKey(3))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree.leaves(theirs)):
        if path[-1].key == "w_router":
            a, b = np.asarray(a), np.asarray(b)
            for rank in range(a.shape[-1] // held):
                np.testing.assert_array_equal(
                    a[..., rank * held:(rank + 1) * held], b[..., :held])
        else:
            np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="as many on one rank"):
        toy_family(num_experts_per_tok=3)


def test_run_cell_at_toy_size(hvd, devices, v5e_peaks, tmp_path):
    # At the full rate from step 0: a toy window of a few dozen steps under
    # the cell's 2000-step warm-up falls by less than two slots of 128
    # tokens differ (PERF.md section 7, B0 (l)).
    cell = toy_cell(optimizer={"name": "adamw", "learning_rate": 1e-3})
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
    assert set(checks["reference"]["leaf_cosine"]) == set(
        cell["config_data"]["tolerances"]["leaf_cosine_min"])


# ---------------------------------------------------------------------------
# The readers.
# ---------------------------------------------------------------------------

NEW_READERS = ["flash_bd_fwd_ms", "flash_bd_fwd_roofline", "flash_bd_bwd_ms",
               "flash_bd_bwd_roofline", "flash_bd_calls",
               "moe_experts_bd_roofline"]


def test_the_manifest_gives_the_cell_its_readers_and_no_old_cell_the_new():
    cell = manifest.load_cell(CELL)
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    for generic in ("host_gap_ms", "mfu_pct", "step_device_ms",
                    "device_idle_pct", "fwd_ms", "remat_ms", "bwd_ms",
                    "attention_ms", "loss_ms", "optimizer_ms", "unscoped_ms",
                    "moe_ms", "moe_dispatch_ms", "moe_experts_ms",
                    "compile_s", "hbm_temp_gib"):
        assert generic in cell["layer_metrics"], generic
    # nothing to read: no causal or windowed call, no gate, no linear
    # mixer; moe_experts_roofline's reader takes a token for one row
    for other in ("flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
                  "flash_full_fwd_roofline", "flash_win_fwd_ms",
                  "attn_gate_ms", "gdn_ms", "moe_experts_roofline"):
        assert other not in cell["layer_metrics"], other
    # something to read, and not listed: an accepted test pins these
    # eight entries' lists of cells (PERF.md section 7, B0 (m))
    for pinned in ("attn_proj_ms", "attn_rope_ms", "attn_core_ms",
                   "attn_surround_ms", "attn_copies", "embed_ms",
                   "moe_rows_ms", "moe_tokens_ms"):
        assert pinned not in cell["layer_metrics"], pinned
    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NEW_READERS:
        assert per_layer[name]["layer"] == (
            "expert layer" if name.startswith("moe") else "kernels")
        assert per_layer[name]["moves"] == "tokens_per_s_chip"
        assert per_layer[name]["workloads"] == [CELL]
    for old in ("lm24x1024_s4096_b8", "resnet50_train", "laguna_xs2_s8192",
                "qwen3_next_s16384"):
        assert not set(NEW_READERS) & set(
            manifest.load_cell(old)["layer_metrics"])


def _ctx(trace=None, hlo_text="ENTRY %main () -> f32[] {\n}", cell=CELL):
    cell = manifest.load_cell(cell)
    return harness.Context(
        config=cell["config_data"], traffic=cell["traffic"], family=None,
        chips=1, peaks=manifest.load_peaks("TPU v5 lite"),
        hlo_text=hlo_text, memory=None, setup_compile_s=0.0,
        throughput=1.0, trace=trace)


def test_the_readers_on_a_recorded_step():
    """Two steps of 81 ms: the block-mask forward twice (forward 7 ms,
    recompute 7), delta 1, the backward 16; under ``hvdt.moe.experts`` 10 +
    30, and the grouped products' own calls, which XLA names
    ``ragged-dot-none`` and takes off the scope's path, 4 + 6."""
    with open(os.path.join(DATA, "bd_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "bd_step.hlo.txt")) as f:
        ctx = _ctx(trace, f.read())
    read = lambda name: manifest.load_layer_metric(name)(ctx)  # noqa: E731
    assert read("flash_bd_fwd_ms") == pytest.approx(14.0)
    assert read("flash_bd_bwd_ms") == pytest.approx(16.0)
    assert read("flash_bd_calls") == 3
    # two calls at 5.58 ms least over 14 ms; one at 13.96 over 16
    assert read("flash_bd_fwd_roofline") == pytest.approx(79.7, abs=0.3)
    assert read("flash_bd_bwd_roofline") == pytest.approx(87.3, abs=0.3)
    # six layers at 3.14 ms least over 40 ms under the scope + 10 ms of
    # calls that only their name finds
    assert read("moe_experts_bd_roofline") == pytest.approx(37.7, abs=0.2)
    assert read("unscoped_ms") == pytest.approx(10.0)
    # the accepted readers of the same step read what they read
    assert read("attn_surround_ms") == pytest.approx(1.0)
    assert read("moe_experts_ms") == pytest.approx(40.0)
    assert read("flash_fwd_ms") is None and read("flash_win_fwd_ms") is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name):
    """On a program from before this PR (no ``hvdt.kernel.flash_bd_*``) and
    without a trace, a reader returns None and does not raise; nor on
    another configuration's step that has the grouped products."""
    assert manifest.load_layer_metric(name)(_ctx()) is None
    assert manifest.load_layer_metric(name)(
        _ctx(cell="laguna_xs2_s8192")) is None
    with open(os.path.join(DATA, "scopes_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "scopes_step.hlo.txt")) as f:
        ctx = _ctx(trace, f.read(), cell="laguna_xs2_s8192")
    assert manifest.load_layer_metric(name)(ctx) is None
