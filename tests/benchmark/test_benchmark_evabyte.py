"""The ``evabyte`` family and its cell ``evabyte_s32768``: the configuration
file against the contract and the catalog, the pair, operation and byte
counts against a brute-force count of the dense masks and hand-worked
numbers, the family against its plain reference through the harness's own
check, controls that the check has to fail, one toy run of the cell through
``harness.run_cell``, and the five new readers on a small recorded step
(``data/eva_trace.json``, ``data/eva_step.hlo.txt``) and on a step that
lacks their scopes.  CPU only."""

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, manifest  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.families import evabyte  # noqa: E402
from benchmark.layer_metrics import roofline  # noqa: E402
from benchmark.reference import evabyte as reference  # noqa: E402

CELL = "evabyte_s32768"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(__file__), "data")

# The published layer at a size the CPU takes (tests/test_models_evabyte.py
# holds the like): two layers, heads 4..7 of 8, windows of 8 in chunks of
# 2, 3 prediction heads over 40 ids.
TOY_CONFIG = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=8,
    intermediate_size=96, window_size=8, chunk_size=2, num_pred_heads=3,
    vocab_size=40, layers=2, heads=4, heads_first=4, loss_chunk=16)
TOY_TRAFFIC = dict(seq=32, per_chip_batch=2, pred_heads=3, window=8, chunk=2)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "evabyte.json")) as f:
        return json.load(f)


def toy_cell(**config_changes):
    cell = manifest.load_cell(CELL)
    cell["config_data"] = {**cell["config_data"], **TOY_CONFIG,
                           **config_changes}
    cell["traffic"] = dict(cell["traffic"], **TOY_TRAFFIC)
    return cell


def toy_family(**config_changes):
    cell = toy_cell(**config_changes)
    return manifest.load_family("evabyte").build(cell["config_data"],
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
             if c["name"] == "evabyte"][0]
    manifest.check_config(entry, cfg)
    assert cfg["family"] == "evabyte"
    assert cfg["reduced"] == ["layers", "heads"]
    assert cfg["published"] == {"layers": 32, "heads": 32}
    assert (cfg["layers"], cfg["heads"], cfg["heads_first"]) == (4, 8, 0)
    assert "4 chips" in cfg["deployment"] and len(cfg["deployment"]) <= 200
    assert "feed-forward and norms on every chip" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {
        "pooling", "aggregation", "phi_mu_init", "head", "initialisation",
        "optimizer", "data", "share"}
    # the floor of a cut: four layers (period 1, no leading dense layer)
    assert cfg["layers"] >= 4
    assert (cfg["param_dtype"], cfg["compute_dtype"], cfg["remat"],
            cfg["loss_chunk"]) == ("float32", "bfloat16", "full", 8192)
    assert cfg["optimizer"] == {"name": "adamw", "learning_rate": 3e-4}
    # every leaf the check compares exists in the tree the family inits,
    # and every leaf of the tree is compared
    family = manifest.load_family("evabyte").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    for path in cfg["tolerances"]["leaf_cosine_min"]:
        harness._leaf(shapes, path)
    assert set(cfg["tolerances"]["leaf_cosine_min"]) == {
        "embed", "head", "ln_f"} | {"period/0/" + n for n in shapes[
            "period"]["0"]}
    assert {"phi", "mu"} <= set(shapes["period"]["0"])
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == 620_015_616             # 620.0M; 9.92 GB at 16 B
    assert round(total * 16 / 1e9, 2) == 9.92
    layer = sum(x.size for x in jax.tree.leaves(shapes["period"]["0"])) // 4
    assert layer == 152_053_760
    assert shapes["head"].shape == (8 * 320, 4096)


def test_every_number_of_the_catalog_entry_is_in_the_file_under_its_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "EvaByte"][0]
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    # what is cut is depth and the chip's share of the heads, never a width
    widths = ("hidden_size", "intermediate_size", "window_size",
              "chunk_size", "num_pred_heads", "vocab_size",
              "num_attention_heads", "num_key_value_heads")
    assert not set(cfg["reduced"]) & set(widths)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["hidden_size"] // cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["window_size"], cfg["chunk_size"],
            cfg["num_pred_heads"], cfg["vocab_size"]) == (
                4096, 32, 128, 11008, 2048, 16, 8, 320)


def test_the_cell_is_the_issues_traffic():
    cell = manifest.load_cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "evabyte"
    t = cell["traffic"]
    assert t == {"name": "s32768_mb8", "seq": 32768, "per_chip_batch": 1,
                 "pred_heads": 8, "window": 2048, "chunk": 16,
                 "pool_batches": 8, "sample_sequences": 1, "token_skew": 4}
    assert (cell["warmup_steps"], cell["trace_steps"]) == (1, 3)
    entry = [w for w in manifest.load_manifest()["workloads"]
             if w["name"] == CELL][0]
    assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
    assert cell["end_to_end"] == ["tokens_per_s_chip", "peak_hbm_gib",
                                  "setup_s"]
    for key in ("pred_heads", "window", "chunk"):
        with pytest.raises(ValueError, match=key):
            manifest.load_family("evabyte").build(
                cell["config_data"], dict(t, **{key: 4}))


# ---------------------------------------------------------------------------
# Counts.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seq, window, chunk", [(24, 8, 2), (8, 8, 2),
                                                (64, 16, 4), (48, 16, 16)])
def test_visible_pairs_against_a_count_of_the_dense_masks(seq, window,
                                                          chunk):
    seen = np.asarray(reference.visible(jnp.arange(seq), seq, window, chunk))
    assert seen.shape == (seq, seq + seq // chunk)
    exact, summary = evabyte.eva_visible_pairs(seq, window, chunk)
    assert (exact, summary) == (int(seen[:, :seq].sum()),
                                int(seen[:, seq:].sum()))
    windows = seq // window
    assert exact == windows * window * (window + 1) // 2
    # the first window's rows see no summary, nor a chunk of their own
    # window; the last window's rows see every chunk before it
    assert not seen[:window, seq:].any()
    assert int(seen[-1, seq:].sum()) == (windows - 1) * (window // chunk)


def test_the_pairs_and_the_cores_cost_at_the_cells_widths():
    """65,028,096 pairs a head, 48% of them on summaries; a forward pass
    of the aggregation at 8 heads of 128 is 266 GFLOP and 1.35 ms at the
    chip's peak, a backward 666 GFLOP and 3.38 ms, both compute-bound: 24.3
    ms a step over four layers (forward, recompute, backward)."""
    peaks = manifest.load_peaks("TPU v5 lite")
    exact, summary = evabyte.eva_visible_pairs(32768, 2048, 16)
    assert (exact, summary) == (33_570_816, 31_457_280)
    assert exact + summary == 65_028_096
    assert summary / (exact + summary) == pytest.approx(0.48, abs=0.005)
    assert sum(evabyte.eva_visible_pairs(16384, 2048, 16)) == \
        8 * 2048 * 2049 // 2 + 2048 * 128 * 28
    shape = dict(batch=1, seq=32768, heads=8, head_dim=128, window=2048,
                 chunk=16)
    ops, nbytes = evabyte.eva_core_cost(**shape)
    assert ops == 2 * 2 * 8 * 65_028_096 * 128
    tensor = 8 * 32768 * 128 * 2
    assert nbytes == 4 * tensor + 2 * tensor / 16
    least, bound = roofline(ops, nbytes, peaks)
    assert bound == "compute" and 1e3 * least == pytest.approx(1.35,
                                                               abs=0.01)
    ops_b, nbytes_b = evabyte.eva_core_cost(backward=True, **shape)
    assert ops_b == 2.5 * ops and nbytes_b == 7 * tensor + 4 * tensor / 16
    least_b, bound_b = roofline(ops_b, nbytes_b, peaks)
    assert bound_b == "compute" and 1e3 * least_b == pytest.approx(
        3.38, abs=0.01)
    assert 1e3 * 4 * (2 * least + least_b) == pytest.approx(24.3, abs=0.1)


def test_flops_per_token_against_a_hand_count():
    """Forward multiply-adds a byte of a layer at seq 32,768, in millions:
    feed-forward 135.3 (3 x 4096 x 11008), projections at 8 heads 16.8,
    scores 4.06 (2 x 1024 x 1984.5), pooling 0.003; the head 10.5 once;
    3.81 GFLOP a byte forward + backward, 124.8 TFLOP a step."""
    cfg = config()
    m = evabyte.layer_macs(cfg, 32768)
    assert m["feed_forward"] == 3 * 4096 * 11008
    assert m["projections"] == 4 * 4096 * 1024
    assert m["scores"] == 2 * 1024 * 65_028_096 / 32768
    assert m["pooling"] == 3 * 1024
    assert {k: round(v / 1e6, 2) for k, v in m.items()} == {
        "projections": 16.78, "pooling": 0.0, "scores": 4.06,
        "feed_forward": 135.27}
    total = evabyte.flops_per_token(cfg, 32768)
    assert total == 3 * 2 * (4 * sum(m.values()) + 4096 * 320 * 8)
    assert total * 32768 / 1e12 == pytest.approx(124.8, abs=0.1)
    family = manifest.load_family("evabyte").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    assert family.flops_per_unit == total
    assert family.units_per_sample == 32768 and family.unit == "tokens"


# ---------------------------------------------------------------------------
# The family against its reference, and one run of the cell.
# ---------------------------------------------------------------------------


def sharpened(params, by=40.0):
    """phi and mu at a size at which the pooling is far from a mean (at
    their start, 0.01 of a unit, a chunk's softmax is nearly flat)."""
    layer = dict(params["period"]["0"])
    layer.update({n: by * layer[n] for n in ("phi", "mu")})
    return dict(params, period={"0": layer})


@pytest.mark.parametrize("mosaic", [False, True], ids=["xla", "kernels"])
def test_family_and_reference_agree_in_float32(mosaic):
    family = toy_family(compute_dtype="float32")
    params = sharpened(jax.jit(family.init)(jax.random.PRNGKey(0)))
    got = harness.reference_check(family, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=mosaic)
    assert got["loss_rel"] < 1e-5
    assert got["grad_norm_rel"] < 1e-4
    assert min(got["leaf_cosine"].values()) > 0.9999
    assert set(got["leaf_cosine"]) == set(
        config()["tolerances"]["leaf_cosine_min"])


_VISIBLE = reference.visible


def _no_summaries(rows, length, window, chunk):
    """Window-only attention: the summaries left out of the softmax."""
    seen = _VISIBLE(rows, length, window, chunk)
    return seen.at[:, length:].set(False)


def _own_window_too(rows, length, window, chunk):
    """The summaries' window mask off by one: <= for <, so a row sees its
    own window's chunks twice, as keys and as summaries."""
    seen = _VISIBLE(rows, length, window, chunk)
    c = jnp.arange(length // chunk)[None, :]
    return seen.at[:, length:].set(
        (chunk * c) // window <= rows[:, None] // window)


@pytest.mark.parametrize("wrong", ["no_summaries", "own_window_too",
                                   "first_head_only", "no_mu"])
def test_reference_check_fails_a_wrong_model(wrong, monkeypatch):
    """Held to what float32 allows (the agreement test above reads 1e-5
    and 0.9999), a reference without the summaries, one whose rows see
    their own window's chunks, one that scores the first prediction head
    alone and one whose pooled key lacks mu fail the harness's own check."""
    family = toy_family(compute_dtype="float32")
    params = sharpened(jax.jit(family.init)(jax.random.PRNGKey(0)))
    tight = dict(loss_rel=1e-4, grad_norm_rel=1e-3, leaf_cosine_min={
        p: 0.999 for p in family.tolerances["leaf_cosine_min"]})
    other = family.reference_loss
    if wrong == "no_summaries":
        monkeypatch.setattr(reference, "visible", _no_summaries)
    elif wrong == "own_window_too":
        monkeypatch.setattr(reference, "visible", _own_window_too)
    elif wrong == "first_head_only":
        one = dict(toy_cell()["config_data"], num_pred_heads=1)
        other = lambda p, tokens: reference.loss(  # noqa: E731
            dict(p, head=p["head"][:one["vocab_size"]]), tokens, config=one)
    else:
        real = reference.summaries
        monkeypatch.setattr(
            reference, "summaries",
            lambda k, v, phi, mu, chunk: real(k, v, phi, 0.0 * mu, chunk))
    got = harness.reference_check(
        dataclasses.replace(family, tolerances=tight, reference_loss=other),
        params, jax.random.PRNGKey(1), jax.devices()[0], mosaic=False)
    assert not got["ok"], got


def test_a_batch_is_skewed_byte_ids_from_its_key():
    family = toy_family()
    (tokens,) = family.make_batch(jax.random.PRNGKey(0), 4)
    assert tokens.shape == (4, 32) and tokens.dtype == jnp.int32
    assert 0 <= int(tokens.min()) and int(tokens.max()) < 40
    assert int((tokens == 0).sum()) > 0.2 * tokens.size     # skew 4
    np.testing.assert_array_equal(
        tokens, family.make_batch(jax.random.PRNGKey(0), 4)[0])
    assert not np.array_equal(
        tokens, family.make_batch(jax.random.PRNGKey(1), 4)[0])


def test_run_cell_at_toy_size(hvd, devices, v5e_peaks, tmp_path):
    cell = toy_cell(optimizer={"name": "adamw", "learning_rate": 1e-3})
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

NEW_READERS = ["eva_ms", "eva_summary_ms", "eva_core_ms",
               "eva_core_roofline", "eva_calls"]


def test_the_manifest_gives_the_cell_its_readers_and_no_old_cell_the_new():
    cell = manifest.load_cell(CELL)
    assert set(NEW_READERS) <= set(cell["layer_metrics"])
    for generic in ("host_gap_ms", "mfu_pct", "step_device_ms",
                    "device_idle_pct", "fwd_ms", "remat_ms", "bwd_ms",
                    "attention_ms", "loss_ms", "optimizer_ms", "unscoped_ms",
                    "compile_s", "hbm_temp_gib"):
        assert generic in cell["layer_metrics"], generic
    # nothing to read (the windows' causal calls lower under eva_win_*),
    # or pinned by an accepted test (PERF.md section 7, B0 (m))
    for other in ("flash_fwd_ms", "flash_bwd_ms", "flash_fwd_roofline",
                  "flash_win_fwd_ms", "flash_bd_fwd_ms", "gdn_ms", "moe_ms",
                  "attn_proj_ms", "attn_rope_ms", "attn_core_ms",
                  "attn_surround_ms", "attn_copies", "embed_ms"):
        assert other not in cell["layer_metrics"], other
    per_layer = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NEW_READERS:
        assert per_layer[name]["layer"] == "linear mixer"
        assert per_layer[name]["moves"] == "tokens_per_s_chip"
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["source"] == "program_span"
    assert per_layer["eva_core_roofline"]["unit"] == "%"
    assert [m["name"] for m in manifest.load_manifest()["per_layer"]][
        -5:] == NEW_READERS
    for old in ("lm24x1024_s4096_b8", "resnet50_train", "laguna_xs2_s8192",
                "qwen3_next_s16384", "sdar_30b_s8192"):
        assert not set(NEW_READERS) & set(
            manifest.load_cell(old)["layer_metrics"])


def _ctx(trace=None, hlo_text="ENTRY %main () -> f32[] {\n}", cell=CELL):
    cell = manifest.load_cell(cell)
    return harness.Context(
        config=cell["config_data"], traffic=cell["traffic"], family=None,
        chips=1, peaks=manifest.load_peaks("TPU v5 lite"),
        hlo_text=hlo_text, memory=None, setup_compile_s=0.0,
        throughput=1.0, trace=trace)


def _recorded(cell=CELL):
    with open(os.path.join(DATA, "eva_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "eva_step.hlo.txt")) as f:
        return _ctx(trace, f.read(), cell)


def test_the_readers_on_a_recorded_step():
    """Two steps of 91 ms.  Under ``hvdt.eva.summary`` 1 (forward) + 1
    (recompute) + 2 (backward); under ``hvdt.eva.core`` the windows' call
    4 + 4 + 8, the summaries' 3 + 3 + (3 + 4), the merge 1 + 1 + 1; the
    projections 5 and the feed-forward 30 + 20 outside."""
    ctx = _recorded()
    read = lambda name: manifest.load_layer_metric(name)(ctx)  # noqa: E731
    assert read("eva_summary_ms") == pytest.approx(4.0)
    assert read("eva_core_ms") == pytest.approx(32.0)
    assert read("eva_ms") == pytest.approx(36.0)
    assert read("eva_calls") == 7
    assert read("attention_ms") == pytest.approx(41.0)
    assert read("eva_summary_ms") + read("eva_core_ms") <= read("eva_ms") \
        <= read("attention_ms")
    # four layers at 2 x 1.35 + 3.38 ms least over 32 ms
    assert read("eva_core_roofline") == pytest.approx(100 * 24.33 / 32,
                                                      abs=0.3)
    assert 0 < read("eva_core_roofline") < 100
    # the accepted flash readers find nothing under their names
    assert read("flash_fwd_ms") is None and read("flash_bwd_ms") is None
    assert read("fwd_ms") == pytest.approx(44.0)
    assert read("remat_ms") == pytest.approx(9.0)
    assert read("bwd_ms") == pytest.approx(38.0)


def test_eva_calls_reads_zero_where_the_xla_form_runs():
    ctx = _recorded()
    hlo = ctx.hlo_text.replace('custom_call_target="tpu_custom_call"',
                               'custom_call_target="other"')
    trace = tr.trace_from_json(open(os.path.join(
        DATA, "eva_trace.json")).read().replace("tpu_custom_call", "other"))
    ctx = _ctx(trace, hlo)
    assert manifest.load_layer_metric("eva_calls")(ctx) == 0
    assert manifest.load_layer_metric("eva_core_ms")(ctx) == pytest.approx(
        32.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name):
    """On a program from before this PR (no ``hvdt.eva``) and without a
    trace, a reader returns None and does not raise; nor on another
    configuration's recorded step."""
    assert manifest.load_layer_metric(name)(_ctx()) is None
    assert manifest.load_layer_metric(name)(
        _ctx(cell="laguna_xs2_s8192")) is None
    with open(os.path.join(DATA, "scopes_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "scopes_step.hlo.txt")) as f:
        ctx = _ctx(trace, f.read(), cell="laguna_xs2_s8192")
    assert manifest.load_layer_metric(name)(ctx) is None
