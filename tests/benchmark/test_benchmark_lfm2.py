"""The ``lfm2`` family and its cell ``lfm2_24b_s8192``: the configuration
file against the contract and the catalog, the operation and byte counts
against hand-worked numbers, the route's start, the family against its
plain reference through the harness's own check (and wrong models failing
it), one toy run of the cell through ``harness.run_cell``, and the new
readers on a recorded step and on steps that lack their scopes.  CPU only.

The readers' entries are not in ``BENCHMARK.json`` yet (an accepted test
pins the tail of ``per_layer``: PERF.md section 7, B0 (r)); they are
rehearsed here on a copy of the manifest."""

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
from benchmark.families import laguna, lfm2  # noqa: E402
from benchmark.layer_metrics import roofline  # noqa: E402

CELL = "lfm2_24b_s8192"
CONFIG = "lfm2_24b_a2b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# The held range at a size the CPU takes (tests/test_models_lfm2.py holds
# the same): layers 1-5, 4 / 2 heads of 16, 64 routed experts of 32 (16
# held), top 4.
TOY_CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=512,
    vocab=128, loss_chunk=96)
TOY_TRAFFIC = dict(seq=64, per_chip_batch=2)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def toy_cell(**config_changes):
    cell = manifest.load_cell(CELL)
    cell["config_data"] = {**cell["config_data"], **TOY_CONFIG,
                           **config_changes}
    cell["traffic"] = dict(cell["traffic"], **TOY_TRAFFIC)
    return cell


def toy_family(**config_changes):
    cell = toy_cell(**config_changes)
    return manifest.load_family("lfm2").build(cell["config_data"],
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
             if c["name"] == CONFIG][0]
    manifest.check_config(entry, cfg)
    assert cfg["family"] == "lfm2"
    assert cfg["reduced"] == entry["reduced"] == ["layers", "experts",
                                                  "vocab"]
    assert cfg["published"] == {"layers": 40, "experts": 64, "vocab": 65536}
    assert (cfg["layers"], cfg["layers_first"], cfg["experts"],
            cfg["experts_first"], cfg["vocab"]) == (5, 1, 16, 0, 8192)
    assert "four chips share each layer" in cfg["deployment"] and \
        len(cfg["deployment"]) <= 200
    assert set(cfg["assumed"]) >= {
        "tie_word_embeddings", "column_order", "short_conv", "attention",
        "router", "router_bias", "router_start", "initialisation",
        "optimizer", "share"}
    # the floors of a cut: the leading dense layers once, a whole period
    # and four layers after them, 8 experts, an eighth of the vocabulary
    held = cfg["layer_types"][1:6]
    assert held == ["conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["layer_types"][2:] == (held[1:] * 10)[:38]
    assert cfg["num_dense_layers"] == 2 and cfg["layers_first"] == 1
    assert cfg["experts"] >= 8 and cfg["vocab"] * 8 >= 65536
    assert lfm2.sparse_layers(cfg) == 4 and lfm2.conv_layers(cfg) == 4
    # every leaf the check compares exists in the tree the family inits
    family = manifest.load_family("lfm2").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    for path in cfg["tolerances"]["leaf_cosine_min"]:
        harness._leaf(shapes, path)
    assert set(cfg["tolerances"]["leaf_cosine_min"]) >= {
        "embed", "lead/0/w_in", "lead/0/conv", "lead/0/w_out",
        "period/0/wq", "period/0/wk", "period/0/w_router",
        "period/1/w_in", "period/1/conv", "period/1/w_out",
        "period/1/w_router"}
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == cfg["parameters"] == 771_275_136
    assert round(total * 16 / 1e9, 2) == cfg["training_state_gb"] == 12.34


def test_every_number_of_the_catalog_entry_is_in_the_file_under_its_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "LFM2-24B-A2B"][0]
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    # what is cut is depth, experts held and the vocabulary's rows, never
    # a width
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_rank"))
                or k in row["config"]]


def test_a_program_without_the_short_convolution_is_refused_at_once():
    """What makes the parent commit fail the cell in seconds: its
    ``config_from_published`` knows no ``conv`` entry of ``layer_types``
    (and no ``layers_first``), before anything compiles."""
    import horovod_tpu.models as models

    real = models.config_from_published

    def as_the_parent(published, *, layers_first=0, normalize_eps=0.0, **kw):
        if "conv" in published["layer_types"]:
            raise ValueError("layer_types holds ['conv']: one of (...)")
        return real(published, **kw)

    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(models, "config_from_published", as_the_parent)
        with pytest.raises(ValueError, match="layer_types holds"):
            manifest.load_family("lfm2").build(cell["config_data"],
                                               cell["traffic"])


# ---------------------------------------------------------------------------
# Counts.
# ---------------------------------------------------------------------------


def test_flops_per_token_by_layer_against_the_issues_arithmetic():
    """Forward multiply-adds a token at seq 8192: layer 1 89.1M (the conv
    mixer's four 2048^2 products and 3 x 2048 taps, dense 3 x 2048 x
    11,776), the attention layer 36.9M with the causal half of the scores
    and one landed pick, each conv-sparse layer 26.4M, the tied head
    16.8M, once: 1.33 GFLOP a token forward + backward."""
    cfg = config()
    lead = lfm2.layer_macs(cfg, 1, 8192)
    assert lead == {"projections": 4 * 2048 * 2048, "taps": 3 * 2048,
                    "feed_forward": 3 * 2048 * 11776}
    assert sum(lead.values()) == 89_135_104
    sparse = 2048 * 64 + 1 * 3 * 2048 * 1536        # one of four picks lands
    attention = lfm2.layer_macs(cfg, 2, 8192)
    assert attention == {
        "projections": 10_485_760,
        "scores": 2 * 32 * 64 * laguna.visible_pairs(8192) / 8192,
        "feed_forward": sparse}
    assert round(sum(attention.values()) / 1e6, 1) == 36.8
    conv = lfm2.layer_macs(cfg, 3, 8192)
    assert conv == {"projections": 4 * 2048 * 2048, "taps": 3 * 2048,
                    "feed_forward": sparse}
    assert round(sum(conv.values()) / 1e6, 1) == 26.4
    assert lfm2.layer_macs(cfg, 4, 8192) == lfm2.layer_macs(
        cfg, 5, 8192) == conv
    total = lfm2.flops_per_token(cfg, 8192)
    assert total == 6 * (sum(lead.values()) + sum(attention.values())
                         + 3 * sum(conv.values()) + 2048 * 8192)
    assert total / 1e9 == pytest.approx(1.331, abs=0.001)
    # the program's own count takes the full score square
    from horovod_tpu.models import (config_from_published,
                                    transformer_flops_per_token)

    ours = transformer_flops_per_token(config_from_published(
        cfg, layers=5, layers_first=1, experts=16, vocab=8192,
        qk_norm=True, max_seq=8192))
    assert 3 * ours - total == pytest.approx(
        6 * 2 * 32 * 64 * (8192 - laguna.visible_pairs(8192) / 8192))


def test_sconv_conv_cost_against_a_hand_count():
    cfg = config()
    ops, nbytes = lfm2.sconv_conv_cost(cfg, tokens=16384)
    # B, C, X read and the gated sum written, bf16: 16 KB a token a pass
    assert nbytes == 4 * 16384 * (4 * 2048 * 2) == 1_073_741_824
    assert ops == 4 * 16384 * 2048 * 10
    least, bound = roofline(ops, nbytes, manifest.load_peaks("TPU v5 lite"))
    assert bound == "hbm" and 1e3 * least == pytest.approx(1.311, abs=0.001)
    # the issue's 0.33 ms a pass
    assert 1e3 * least / 4 == pytest.approx(0.328, abs=0.001)
    # the whole mixer: 4 d^2 multiply-adds a token, four passes
    ops, nbytes = lfm2.sconv_cost(cfg, tokens=16384)
    assert ops == 2 * 4 * 4 * 2048 * 2048 * 16384
    assert nbytes == 4 * (16384 * 2 * 2048 * 2 + 4 * 2048 * 2048 * 2)
    least, bound = roofline(ops, nbytes, manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * least == pytest.approx(11.163,
                                                               abs=0.001)


# ---------------------------------------------------------------------------
# The route's start.
# ---------------------------------------------------------------------------


def test_every_token_lands_one_pick_here_and_the_bias_moves_the_winner():
    """The router's columns and the drawn bias tied over the four ranks:
    a row's four picks are the four copies of its best score-plus-bias
    column, one a rank, whatever the row; the weights start at 1/4; and
    the bias is no bystander: it changes the winner for many rows."""
    from horovod_tpu.parallel import moe

    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(3))
    for run, layers in (("0", 1), ("1", 3)):
        p = params["period"][run]
        assert p["router_bias"].shape == (1, layers, 64)
        for i in range(layers):
            w, b = p["w_router"][0, i], p["router_bias"][0, i]
            for rank in (1, 2, 3):
                np.testing.assert_array_equal(
                    w[:, :16], w[:, 16 * rank:16 * rank + 16])
                np.testing.assert_array_equal(
                    b[:16], b[16 * rank:16 * rank + 16])
            assert float(jnp.std(b)) == pytest.approx(
                config()["router_bias_std"], rel=0.5)
            x = jax.random.normal(jax.random.PRNGKey(i), (256, 64))
            scores, experts, weights = moe.moe_route(
                x, w, top_k=4, select_bias=b, normalize_eps=1e-6)
            assert (np.sort(np.asarray(experts) // 16, -1)
                    == np.arange(4)).all()
            assert len({tuple(r) for r in np.asarray(experts) % 16
                        if len(set(r)) == 1}) > 1
            np.testing.assert_allclose(weights, 0.25, atol=1e-5)
            moved = (jnp.argmax(scores + b, -1) % 16
                     != jnp.argmax(scores, -1) % 16).mean()
            assert float(moved) > 0.2
    # the two runs' biases are different draws
    assert not np.array_equal(params["period"]["0"]["router_bias"][0, 0],
                              params["period"]["1"]["router_bias"][0, 0])


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


def _cut_to_the_last_tap(x, w, bias=None, **gates):
    from horovod_tpu.ops.gated_delta import causal_conv

    return causal_conv(x, w.at[:-1].set(0.0), bias, **gates)


def _without(gate):
    def conv(x, w, bias=None, **gates):
        from horovod_tpu.ops.gated_delta import causal_conv

        return causal_conv(x, w, bias, **{k: v for k, v in gates.items()
                                          if k != gate})
    return conv


def _reading_the_future(x, w, bias=None, *, times, gate):
    from horovod_tpu.ops.gated_delta import causal_conv

    return causal_conv(x[:, ::-1], w, bias, times=times[:, ::-1]
                       )[:, ::-1] * gate


WRONG = {
    "the_conv_cut_to_its_last_tap": ("conv", _cut_to_the_last_tap),
    "the_c_gate_left_out": ("conv", _without("gate")),
    "the_b_gate_left_out": ("conv", _without("times")),
    "the_convolution_acausal": ("conv", _reading_the_future),
    "picks_by_the_score_alone": ("bias", None),
    "weights_taken_from_score_plus_bias": ("weights", None),
    "qk_norm_left_out": ("qk_norm", None),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_reference_check_fails_a_wrong_model(wrong, monkeypatch):
    """Held to what float32 allows (the agreement test above reads 1e-5
    and 0.9999), a system that leaves out a gate, the convolution's past,
    its causality, the bias in the picks or q / k norm, or that weighs by
    score plus bias, fails the harness's own check (the chip's controls of
    the same names: the configuration file's ``tolerances.why``)."""
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops import short_conv
    from horovod_tpu.parallel import moe

    what, stand_in = WRONG[wrong]
    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    tight = dict(loss_rel=1e-4, grad_norm_rel=1e-3, leaf_cosine_min={
        p: 0.999 for p in family.tolerances["leaf_cosine_min"]})
    family = dataclasses.replace(family, tolerances=tight)
    if what == "conv":
        monkeypatch.setattr(short_conv, "causal_conv", stand_in)
    elif what == "bias":
        real = tfm.moe_held_experts
        monkeypatch.setattr(
            tfm, "moe_held_experts",
            lambda *a, select_bias=None, **kw: real(*a, **kw))
    elif what == "weights":
        monkeypatch.setattr(
            moe, "_picked_and_experts",
            lambda scores, bias, k: jax.lax.top_k(scores + bias, k))
    else:
        # the same tree (q_norm and k_norm stay, unread) under a
        # configuration without the norm
        from horovod_tpu.models import (config_from_published,
                                        transformer_loss)

        c = toy_cell(compute_dtype="float32")["config_data"]
        cfg = config_from_published(
            c, layers=5, layers_first=1, experts=16, vocab=c["vocab"],
            qk_norm=False, normalize_eps=1e-6, max_seq=64,
            dtype=jnp.float32, remat=True, loss_chunk=96)
        family = dataclasses.replace(
            family, loss_fn=lambda p, t: transformer_loss(p, t, cfg))
    got = harness.reference_check(family, params, jax.random.PRNGKey(1),
                                  jax.devices()[0], mosaic=False)
    assert not got["ok"], got
    if what == "weights":
        # the tied start gives both forms the weights 1/4: only the
        # router's own gradient tells them apart
        assert got["loss_rel"] < 1e-5
        assert min(got["leaf_cosine"]["period/0/w_router"],
                   got["leaf_cosine"]["period/1/w_router"]) < 0.999


def test_the_optimizer_is_the_catalog_cells_warm_up():
    assert config()["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                     "warmup_steps": 2000}
    assert lfm2.optimizer_of is laguna.optimizer_of


def test_tokens_are_drawn_from_the_held_slice_of_the_vocabulary():
    family = toy_family()
    (tokens,) = family.make_batch(jax.random.PRNGKey(0), 4)
    assert tokens.shape == (4, 64) and int(tokens.max()) < 128
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

NEW_READERS = ["sconv_ms", "sconv_proj_ms", "sconv_conv_ms",
               "sconv_roofline", "moe_experts_all_roofline",
               "moe_route_select"]
UNITS = {"sconv_roofline": "%", "moe_experts_all_roofline": "%",
         "moe_route_select": "routes"}


def entries():
    """The entries a benchmark PR adds for the readers (B0 (r))."""
    return [{"name": name, "unit": UNITS.get(name, "ms"),
             "better": "higher" if name in UNITS else "lower",
             "source": "program_counter" if name == "moe_route_select"
             else "program_span",
             "layer": "expert layer" if name.startswith("moe")
             else "linear mixer",
             "moves": "tokens_per_s_chip", "workloads": [CELL]}
            for name in NEW_READERS]


def test_the_manifest_gives_the_cell_its_readers_and_no_old_cell_the_new(
        tmp_path):
    """Held by containment: no tail and no whole list is pinned."""
    cell = manifest.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["name"] == "s8192"
    assert cell["config"] == CONFIG
    assert cell["traffic"]["per_chip_batch"] == 2
    for listed in ("host_gap_ms", "mfu_pct", "step_device_ms",
                   "device_idle_pct", "fwd_ms", "remat_ms", "bwd_ms",
                   "attention_ms", "loss_ms", "optimizer_ms", "unscoped_ms",
                   "flash_fwd_ms", "flash_bwd_ms", "moe_ms",
                   "moe_dispatch_ms", "moe_experts_ms", "compile_s",
                   "hbm_temp_gib"):
        assert listed in cell["layer_metrics"], listed
    # nothing to read (no window, no delta rule, no state-space scan), a
    # reader that reads ``mlp_layer_types``, or a list an accepted test
    # pins (B0 (q))
    for other in ("flash_win_fwd_ms", "flash_fwd_roofline", "gdn_ms",
                  "eva_ms", "moe_experts_roofline", "moe_rows_ms",
                  "moe_sum_rows_calls", "attn_proj_ms", "attn_rope_ms",
                  "attn_core_ms",
                  "attn_surround_ms", "attn_copies", "embed_ms"):
        assert other not in cell["layer_metrics"], other
    assert "tokens_per_s_chip" in cell["end_to_end"]
    entry = [w for w in manifest.load_manifest()["workloads"]
             if w["name"] == CELL][0]
    assert len(entry["why"]) <= 200 and "seq 8192 x 2" in entry["why"]
    # the readers' entries, rehearsed on a copy of the manifest
    for path in ("benchmark/workloads", "benchmark/configs"):
        os.makedirs(tmp_path / path)
    for path in (f"benchmark/workloads/{CELL}.json",
                 f"benchmark/configs/{CONFIG}.json"):
        with open(os.path.join(REPO, path)) as src, \
                open(tmp_path / path, "w") as dst:
            dst.write(src.read())
    copy = manifest.load_manifest()
    copy["per_layer"] = copy["per_layer"] + entries()
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(copy, f)
    rehearsed = manifest.load_cell(CELL, root=str(tmp_path))
    assert rehearsed["layer_metrics"][-len(NEW_READERS):] == NEW_READERS
    assert rehearsed["units"]["sconv_roofline"] == "%"
    for name in NEW_READERS:
        manifest.load_layer_metric(name)
    for old in ("lm24x1024_s4096_b8", "resnet50_train", "laguna_xs2_s8192",
                "qwen3_next_s16384", "sdar_30b_s8192", "evabyte_s32768",
                "granite_h_micro_s8192"):
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
    with open(os.path.join(DATA, "sconv_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "sconv_step.hlo.txt")) as f:
        return _ctx(trace, f.read(), cell)


def test_the_readers_on_a_recorded_step():
    """Two steps of 105 ms.  Under ``hvdt.sconv``: the pre-norm 1, the
    input projection 5 + 1 (the leading layer's, outside the scan) + 6 +
    12, the gates and taps 2 + 2 + 4, the output projection 2 + 2 + 4.
    The route 0.5 + its top-k under ``hvdt.moe.route.select_bias`` 1 + two
    sorts of 0.25, the two moves 1 + 1 + 1, around the grouped products 2 +
    4 and XLA's own ``ragged-dot-none`` calls 10 + 20 (no scope of the
    program); the flash calls 3 + 5 and the dense feed-forward 15."""
    ctx = _recorded()
    read = lambda name: manifest.load_layer_metric(name)(ctx)  # noqa: E731
    assert read("sconv_ms") == pytest.approx(41.0)
    assert read("sconv_proj_ms") == pytest.approx(32.0)
    assert read("sconv_conv_ms") == pytest.approx(8.0)
    # four layers at 11.163 ms least (2.2 TFLOP at 197 TFLOP/s) over 41 ms
    # is over 100%: the recorded step's times are made up.  At the chip's
    # 63.4 ms (PERF.md, PR 48) it reads 70%.
    assert read("sconv_roofline") == pytest.approx(
        100 * 4 * 11.163 / 41, abs=0.05)
    assert 100 * 4 * 11.163 / 63.4151 == pytest.approx(70.4, abs=0.1)
    # 12 products of 16,384 rows x 2048 x 1536 at 197 TFLOP/s: 6.28 ms a
    # layer, four layers, over 6 + 30 ms
    assert read("moe_experts_all_roofline") == pytest.approx(
        100 * 4 * 6.279 / 36, abs=0.05)
    assert 0 < read("moe_experts_all_roofline") < 100
    assert read("moe_route_select") == 1
    # the accepted readers beside them
    assert read("moe_route_sorts") == 3
    assert read("moe_ms") == pytest.approx(11.0)
    assert read("moe_dispatch_ms") == pytest.approx(5.0)
    assert read("moe_experts_ms") == pytest.approx(6.0)
    assert read("moe_sum_rows_calls") == 2
    assert read("flash_fwd_ms") == pytest.approx(3.0)
    assert read("flash_bwd_ms") == pytest.approx(5.0)
    assert read("attention_ms") == pytest.approx(8.0)
    assert read("fwd_ms") + read("remat_ms") + read("bwd_ms") + read(
        "unscoped_ms") == pytest.approx(read("step_device_ms"))
    assert read("ssd_ms") is None and read("gdn_ms") is None
    assert read("moe_experts_roofline") is None
    assert read("moe_experts_bd_roofline") is None


def test_a_route_without_a_bias_reads_zero_and_no_route_reads_nothing():
    with open(os.path.join(DATA, "moe_route_step.hlo.txt")) as f:
        assert manifest.load_layer_metric("moe_route_select")(
            _ctx(hlo_text=f.read(), cell="laguna_xs2_s8192")) == 0
    with open(os.path.join(DATA, "ssd_step.hlo.txt")) as f:
        assert manifest.load_layer_metric("moe_route_select")(
            _ctx(hlo_text=f.read(), cell="granite_h_micro_s8192")) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name):
    """On a program from before this PR (no ``hvdt.sconv``, no
    ``hvdt.moe.route.select_bias``) and without a trace, a reader returns
    None and does not raise; nor on another configuration's recorded
    step (but for the count of biased routes, which is 0 there)."""
    assert manifest.load_layer_metric(name)(_ctx()) is None
    assert manifest.load_layer_metric(name)(
        _ctx(cell="qwen3_next_s16384")) is None
    with open(os.path.join(DATA, "scopes_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "scopes_step.hlo.txt")) as f:
        ctx = _ctx(trace, f.read(), cell="laguna_xs2_s8192")
    got = manifest.load_layer_metric(name)(ctx)
    assert got is None or (name == "moe_route_select" and got == 0)
    with open(os.path.join(DATA, "ssd_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "ssd_step.hlo.txt")) as f:
        ctx = _ctx(trace, f.read(), cell="granite_h_micro_s8192")
    assert manifest.load_layer_metric(name)(ctx) is None
