"""The ``granite_hybrid`` family and its cell ``granite_h_micro_s8192``: the
configuration file against the contract and the catalog, the operation and
byte counts against hand-worked numbers, the family against its plain
reference through the harness's own check, one toy run of the cell through
``harness.run_cell``, and the ``ssd_*`` readers on a recorded step and on
steps that lack their scopes.  CPU only.

The readers' entries are not in ``BENCHMARK.json`` yet (an accepted test
pins the tail of ``per_layer``: PERF.md section 7, B0 (r)); they are
rehearsed here on a copy of the manifest."""

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
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.families import granite_hybrid, laguna  # noqa: E402
from benchmark.layer_metrics import roofline  # noqa: E402
from benchmark.layer_metrics.ssd_scan_steps import scope_loop_trips  # noqa: E402
from horovod_tpu.ops.ssd import ssd_scan as _SSD_SCAN  # noqa: E402

CELL = "granite_h_micro_s8192"
CONFIG = "granite_4_0_h_micro"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# The published period at a size the CPU takes (tests/
# test_models_granite.py holds the same): ten layers, 8 heads of 8 over a
# state of 16 in chunks of 16.
TOY_CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=96, intermediate_size=96, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16, vocab_size=512,
    vocab=128, attention_multiplier=0.0625, loss_chunk=96)
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
    return manifest.load_family("granite_hybrid").build(cell["config_data"],
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
    assert cfg["family"] == "granite_hybrid"
    assert cfg["reduced"] == entry["reduced"] == ["layers", "vocab"]
    assert cfg["published"] == {"layers": 40, "vocab": 100352}
    assert (cfg["layers"], cfg["vocab"]) == (10, 12544)
    assert "no layer divided" in cfg["deployment"] and \
        len(cfg["deployment"]) <= 200
    assert set(cfg["assumed"]) >= {
        "column_order", "gated_norm", "time_step", "initialisation",
        "float32_in_the_scan", "multipliers", "optimizer", "share"}
    # the floors of a cut: a whole period (no leading dense layer exists),
    # at least four layers, an eighth of the vocabulary
    period = cfg["layer_types"][:10]
    assert period == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"] == period * 4
    assert cfg["vocab"] * 8 >= cfg["published"]["vocab"]
    # every leaf the check compares exists in the tree the family inits
    family = manifest.load_family("granite_hybrid").build(
        cfg, manifest.load_cell(CELL)["traffic"])
    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    for path in cfg["tolerances"]["leaf_cosine_min"]:
        harness._leaf(shapes, path)
    assert set(cfg["tolerances"]["leaf_cosine_min"]) >= {
        "embed", "period/0/w_in", "period/0/conv", "period/0/conv_bias",
        "period/0/a_log", "period/0/dt_bias", "period/0/d_skip",
        "period/0/ssd_norm", "period/0/w_out", "period/1/wq", "period/1/wk",
        "period/2/w_in"}
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == cfg["parameters"] == 772_160_448
    assert round(total * 16 / 1e9, 2) == cfg["training_state_gb"] == 12.35


def test_every_number_of_the_catalog_entry_is_in_the_file_under_its_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "granite-4.0-h-micro"][0]
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    # what is cut is depth and the vocabulary's rows, never a width
    assert not [k for k in cfg["reduced"] if k.endswith(("_dim", "_rank"))
                or k in row["config"]]


def test_a_program_without_the_state_space_kind_is_refused_at_once():
    """What makes the parent commit fail the cell in seconds: the family
    counts the layers that became state-space layers before anything
    compiles."""
    import horovod_tpu.models as models

    real = models.config_from_published

    def as_attention(published, **kw):
        return real(dict(published, layer_types=["attention"] * 40), **kw)

    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(models, "config_from_published", as_attention)
        with pytest.raises(ValueError, match="state-space"):
            manifest.load_family("granite_hybrid").build(
                cell["config_data"], cell["traffic"])


# ---------------------------------------------------------------------------
# Counts.
# ---------------------------------------------------------------------------


def test_flops_per_token_by_layer_against_the_issues_arithmetic():
    """Forward multiply-adds a token at seq 8192: a Mamba-2 layer's two
    projections 25,821,184 and its convolution's 17,408 taps, its scan
    2,129,920; the attention layer's projections 10,485,760 and scores
    over the causal half; a feed-forward 50,331,648; the tied head
    25,690,112, once."""
    cfg = config()
    mamba = granite_hybrid.layer_macs(cfg, 0, 8192)
    assert mamba == {"projections": 2048 * 8512 + 4096 * 2048 + 4 * 4352,
                     "scan": 2_129_920, "feed_forward": 3 * 2048 * 8192}
    assert mamba["projections"] - 4 * 4352 == 25_821_184
    assert all(granite_hybrid.layer_macs(cfg, i, 8192) == mamba
               for i in (1, 4, 6, 9))
    attention = granite_hybrid.layer_macs(cfg, 5, 8192)
    assert attention["projections"] == 10_485_760
    assert attention["scores"] == 2 * 32 * 64 * laguna.visible_pairs(
        8192) / 8192
    total = granite_hybrid.flops_per_token(cfg, 8192)
    assert total == 6 * (9 * sum(mamba.values()) + sum(attention.values())
                         + 2048 * 12544)
    assert total * 8192 / 1e12 == pytest.approx(39.71, abs=0.01)


def test_ssd_scan_cost_against_a_hand_count():
    cfg = config()
    from horovod_tpu.ops.ssd import scan_macs_per_token

    macs = granite_hybrid.ssd_scan_macs(cfg)
    # the scores once for all 64 heads; a head's masked product; its state
    assert macs == 256 * 128 + 64 * 256 * 64 + 2 * 64 * 64 * 128 \
        == 2_129_920
    assert macs == scan_macs_per_token(heads=64, head_dim=64, state=128,
                                       groups=1, chunk=256)
    ops, nbytes = granite_hybrid.ssd_scan_cost(cfg, tokens=8192)
    assert ops == 2 * 4 * macs * 8192       # forward, recompute, 2 backward
    # x read and y written in bf16, B and C in bf16, delta in float32
    a_pass = 8192 * (2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4)
    states = 32 * 64 * 64 * 128 * 4         # 67 MB of chunk states
    assert nbytes == 4 * a_pass + 3 * states
    least, bound = roofline(ops, nbytes, manifest.load_peaks("TPU v5 lite"))
    assert bound == "hbm" and 1e3 * least == pytest.approx(0.932, abs=0.001)


def test_the_state_loops_trips_are_read_from_either_form_of_hlo():
    tpu = """
%cond.7 (arg: (s32[], f32[1,2])) -> pred[] {
  %constant.1 = s32[]{:T(128)} constant(32), metadata={op_name="x"}
  %gte = s32[] get-tuple-element(%arg), index=0
  ROOT %lt = pred[] compare(%gte, %constant.1), direction=LT
}

ENTRY %main () -> f32[] {
  %while.1 = (s32[], f32[1,2]) while(%t), condition=%cond.7, body=%body.7, metadata={op_name="jit(step)/jvp()/hvdt.ssd/hvdt.ssd.scan/hvdt.ssd.scan.state/while"}
  %while.2 = (s32[]) while(%t), condition=%other, body=%b, metadata={op_name="jit(step)/jvp()/hvdt.gdn/hvdt.gdn.scan/while"}
}
"""
    assert scope_loop_trips(tpu, "hvdt.ssd.scan") == [32]
    cpu = ('  %while.3 = (s32[]) while(%t), condition=%c, body=%b, '
           'metadata={op_name="a/hvdt.ssd.scan/while"}, '
           'backend_config={"known_trip_count":{"n":"8"}}\n')
    assert scope_loop_trips(cpu, "hvdt.ssd.scan") == [8]
    assert scope_loop_trips("ENTRY %main () -> f32[] {\n}",
                            "hvdt.ssd.scan") == []


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


@pytest.mark.parametrize("wrong", [
    dict(residual_multiplier=1.0), dict(attention_multiplier=0.25),
    dict(mamba_n_groups=2)],
    ids=["residual_multiplier_left_at_1", "score_scale_four_times",
         "the_norm_over_half_the_channels"])
def test_reference_check_fails_a_wrong_model(wrong):
    """Held to what float32 allows (the agreement test above reads 1e-5
    and 0.9999), a reference with another constant, or one whose gated norm
    and B / C take the channels in two groups, fails the harness's own
    check."""
    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    tight = dict(loss_rel=1e-4, grad_norm_rel=1e-3, leaf_cosine_min={
        p: 0.999 for p in family.tolerances["leaf_cosine_min"]})
    if "mamba_n_groups" in wrong:
        # the same leaves read as two groups: B and C of 8 in place of 16
        wrong = dict(wrong, mamba_d_state=8)
    other = toy_family(compute_dtype="float32", **wrong)
    got = harness.reference_check(
        dataclasses.replace(family, tolerances=tight,
                            reference_loss=other.reference_loss),
        params, jax.random.PRNGKey(1), jax.devices()[0], mosaic=False)
    assert not got["ok"], got


def _no_entering_states(x, delta, a, b, c, *, chunk):
    """Every chunk a sequence of its own: ``S_0 = 0`` at each chunk."""
    def each(t):
        return t.reshape((-1, chunk) + t.shape[2:])

    return _SSD_SCAN(each(x), each(delta), a, each(b), each(c),
                     chunk=chunk).reshape(x.shape)


def _diagonal_only(x, delta, a, b, c, *, chunk):
    """The decay mask cut to its diagonal: ``y_i = (C_i . B_i) delta_i
    x_i``."""
    gain = (c * b).sum(-1).repeat(x.shape[2] // b.shape[2], axis=2) * delta
    return gain[..., None] * x


@pytest.mark.parametrize("scan", [_no_entering_states, _diagonal_only],
                         ids=["entering_states_left_out",
                              "decay_mask_cut_to_the_diagonal"])
def test_reference_check_fails_a_scan_without_its_recurrence(scan,
                                                             monkeypatch):
    """The time steps the family draws let a state live across chunks, so
    a scan that forgets at every chunk's start, or at every token, is
    another model to the harness's own check (the chip's controls of the
    same names: the configuration file's ``tolerances.why``)."""
    from horovod_tpu.ops import ssd

    family = toy_family(compute_dtype="float32")
    params = jax.jit(family.init)(jax.random.PRNGKey(0))
    tight = dict(loss_rel=1e-4, grad_norm_rel=1e-3, leaf_cosine_min={
        p: 0.999 for p in family.tolerances["leaf_cosine_min"]})
    monkeypatch.setattr(ssd, "ssd_scan", scan)
    got = harness.reference_check(
        dataclasses.replace(family, tolerances=tight), params,
        jax.random.PRNGKey(1), jax.devices()[0], mosaic=False)
    assert not got["ok"], got
    # (cut to the diagonal no decay is left: a gradient of 0, a NaN)
    assert not got["leaf_cosine"]["period/0/a_log"] >= 0.99


def test_the_optimizer_is_the_catalog_cells_warm_up():
    assert config()["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                     "warmup_steps": 2000}
    assert granite_hybrid.optimizer_of is laguna.optimizer_of


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

NEW_READERS = ["ssd_ms", "ssd_proj_ms", "ssd_conv_ms", "ssd_scan_ms",
               "ssd_scan_roofline", "ssd_norm_ms", "ssd_scan_steps",
               "ssd_chunk_ms", "ssd_state_ms", "ssd_out_ms"]
UNITS = {"ssd_scan_roofline": "%", "ssd_scan_steps": "steps"}


def entries():
    """The entries a benchmark PR adds for the readers (B0 (r))."""
    return [{"name": name, "unit": UNITS.get(name, "ms"),
             "better": "higher" if name == "ssd_scan_roofline" else "lower",
             "source": "program_counter" if name == "ssd_scan_steps"
             else "program_span", "layer": "linear mixer",
             "moves": "tokens_per_s_chip", "workloads": [CELL]}
            for name in NEW_READERS]


def test_the_manifest_gives_the_cell_its_readers_and_no_old_cell_the_new(
        tmp_path):
    """Held by containment: no tail and no whole list is pinned."""
    cell = manifest.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["name"] == "s8192"
    assert cell["config"] == CONFIG
    for generic in ("host_gap_ms", "mfu_pct", "step_device_ms",
                    "device_idle_pct", "fwd_ms", "remat_ms", "bwd_ms",
                    "attention_ms", "loss_ms", "optimizer_ms", "unscoped_ms",
                    "flash_fwd_ms", "flash_bwd_ms", "compile_s",
                    "hbm_temp_gib"):
        assert generic in cell["layer_metrics"], generic
    # nothing to read (no window, no experts, no delta rule), a reader that
    # divides d_model by heads, or a list an accepted test pins (B0 (q))
    for other in ("flash_win_fwd_ms", "flash_fwd_roofline", "moe_ms",
                  "gdn_ms", "eva_ms", "attn_proj_ms", "attn_rope_ms",
                  "attn_core_ms", "attn_surround_ms", "attn_copies",
                  "embed_ms"):
        assert other not in cell["layer_metrics"], other
    assert "tokens_per_s_chip" in cell["end_to_end"]
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
    assert rehearsed["units"]["ssd_scan_roofline"] == "%"
    for name in NEW_READERS:
        manifest.load_layer_metric(name)
    for old in ("lm24x1024_s4096_b8", "resnet50_train", "laguna_xs2_s8192",
                "qwen3_next_s16384", "sdar_30b_s8192", "evabyte_s32768"):
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
    with open(os.path.join(DATA, "ssd_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "ssd_step.hlo.txt")) as f:
        return _ctx(trace, f.read(), cell)


def test_the_readers_on_a_recorded_step():
    """Two steps of 100 ms.  Under ``hvdt.ssd``: the projections 6 + 6 +
    12, the convolution 1 + 1 + 2, the scan's chunk part 4 + 4 + 8, its
    state loop (a ``while`` of 32 trips whose body's two events are the
    leaves) 1 + 1, what follows it 2 + 2 + 4, the gated norm 3 + 3 + 6, the
    pre-norm 1; the attention layer's flash calls 3 + 5 and the
    feed-forward 25 outside."""
    ctx = _recorded()
    read = lambda name: manifest.load_layer_metric(name)(ctx)  # noqa: E731
    assert read("ssd_proj_ms") == pytest.approx(24.0)
    assert read("ssd_conv_ms") == pytest.approx(4.0)
    assert read("ssd_chunk_ms") == pytest.approx(16.0)
    assert read("ssd_state_ms") == pytest.approx(2.0)
    assert read("ssd_out_ms") == pytest.approx(8.0)
    assert read("ssd_scan_ms") == pytest.approx(26.0)
    assert read("ssd_chunk_ms") + read("ssd_state_ms") + read(
        "ssd_out_ms") == pytest.approx(read("ssd_scan_ms"))
    assert read("ssd_norm_ms") == pytest.approx(12.0)
    assert read("ssd_ms") == pytest.approx(67.0)
    assert read("ssd_scan_steps") == 32
    # nine layers at 0.932 ms least over 26 ms
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * 9 * 0.932 / 26, abs=0.05)
    assert 0 < read("ssd_scan_roofline") < 100
    # the accepted readers beside them
    assert read("flash_fwd_ms") == pytest.approx(3.0)
    assert read("flash_bwd_ms") == pytest.approx(5.0)
    assert read("attention_ms") == pytest.approx(8.0)
    assert read("gdn_ms") is None and read("gdn_scan_steps") is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name):
    """On a program from before this PR (no ``hvdt.ssd``) and without a
    trace, a reader returns None and does not raise; nor on another
    configuration's recorded step."""
    assert manifest.load_layer_metric(name)(_ctx()) is None
    assert manifest.load_layer_metric(name)(
        _ctx(cell="qwen3_next_s16384")) is None
    with open(os.path.join(DATA, "scopes_trace.json")) as f:
        trace = tr.trace_from_json(f.read())
    with open(os.path.join(DATA, "scopes_step.hlo.txt")) as f:
        ctx = _ctx(trace, f.read(), cell="laguna_xs2_s8192")
    assert manifest.load_layer_metric(name)(ctx) is None
