"""Static topology cost model + perf-regression gate
(horovod_tpu/analysis/costmodel.py, topology.py, the `--perf` CLI gate,
autotune model pre-seeding, and the magic-peak-flops / stale-baseline
lint satellites)."""

import json
import os

import pytest

from horovod_tpu.analysis import costmodel as cm
from horovod_tpu.analysis import schedule as sched
from horovod_tpu.analysis import topology as tp
from horovod_tpu.analysis.__main__ import (_gate_lint,
                                           _reference_fingerprints,
                                           main as analysis_main)
from horovod_tpu.analysis.lint import MagicPeakFlopsRule, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ev(index, op, axes, dtype="float32", count=1024, nbytes=4096,
        context=(), post_barrier=False, barriers_before=0):
    return sched.CollectiveEvent(
        index=index, op=op, axes=tuple(axes), dtype=dtype, count=count,
        nbytes=nbytes, context=tuple(context),
        post_barrier=post_barrier, barriers_before=barriers_before)


# ---------------------------------------------------------------------------
# topology + geometry
# ---------------------------------------------------------------------------


class TestTopology:
    def test_spec_tiers_and_total(self):
        topo = tp.TopologySpec(pods=16, chips_per_pod=16)
        assert topo.total_chips == 256
        assert topo.tier_size("dcn") == 16
        assert topo.tier_size("ici") == 16
        with pytest.raises(ValueError):
            topo.tier_size("nvlink")

    def test_spec_validates(self):
        with pytest.raises(ValueError):
            tp.TopologySpec(pods=0)

    def test_from_env_pod_contract(self, monkeypatch):
        monkeypatch.setenv("HVDT_NUM_PODS", "4")
        monkeypatch.setenv("HVDT_POD_SIZE", "8")
        topo = tp.TopologySpec.from_env()
        assert (topo.pods, topo.chips_per_pod) == (4, 8)
        monkeypatch.delenv("HVDT_NUM_PODS")
        monkeypatch.delenv("HVDT_POD_SIZE")
        assert tp.TopologySpec.from_env().pods == 1

    def test_classify_axis(self):
        assert tp.classify_axis("dcn", ("dcn", "ici")) == "dcn"
        assert tp.classify_axis("ici", ("dcn", "ici")) == "ici"
        # position convention: innermost = ici, outer = dcn
        assert tp.classify_axis("dp", ("dp",)) == "ici"
        assert tp.classify_axis("dp", ("dp", "tp")) == "dcn"

    def test_peak_flops_from_one_table(self):
        from horovod_tpu.telemetry.step_stats import peak_flops_for

        assert tp.chip_peak_flops("v5 lite") == peak_flops_for(
            "v5 lite")[0]
        assert tp.chip_peak_flops("unknown-device") is None


class TestGeometry:
    def test_ring_allreduce(self):
        hops, wf = cm.collective_geometry("psum", "ring", 8)
        assert hops == 14 and wf == pytest.approx(1.75)

    def test_tree_allreduce(self):
        hops, wf = cm.collective_geometry("psum", "tree", 8)
        assert hops == 6 and wf == 2.0

    def test_reduce_scatter_and_gather(self):
        for op in ("reduce_scatter", "all_gather", "all_to_all"):
            hops, wf = cm.collective_geometry(op, "ring", 4)
            assert hops == 3 and wf == pytest.approx(0.75)

    def test_single_member_group_free(self):
        assert cm.collective_geometry("psum", "ring", 1) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# calibration: roundtrip, lookup chain, fitting
# ---------------------------------------------------------------------------


class TestCalibration:
    def test_roundtrip(self, tmp_path):
        cal = cm.Calibration(
            {("ici", "ring", "f32"): tp.LinkConstants(1e-6, 2e-9),
             ("dcn", "tree", "int8"): tp.LinkConstants(5e-6, 8e-9, 1e-10)},
            meta={"source": "unit"})
        p = str(tmp_path / "cal.json")
        cal.save(p)
        back = cm.load_calibration(p)
        assert back.groups == cal.groups
        assert back.meta["source"] == "unit"

    def test_missing_file_degrades(self, tmp_path):
        cal = cm.load_calibration(str(tmp_path / "nope.json"))
        assert cal.groups == {}
        assert "degraded" in cal.meta

    def test_lookup_fallback_chain(self):
        ring = tp.LinkConstants(1e-6, 2e-9)
        cal = cm.Calibration({("ici", "ring", "f32"): ring})
        assert cal.lookup("ici", "ring", "f32") is ring
        # wire falls back to the f32 sibling
        assert cal.lookup("ici", "ring", "bf16") is ring
        # unknown tier -> topology defaults, with the wire's gamma
        c = cal.lookup("dcn", "ring", "int8")
        assert c.beta_s_per_byte == pytest.approx(
            tp.DEFAULT_TIER_CONSTANTS["dcn"].beta_s_per_byte
            * cm.wire_shrink("int8"))
        assert c.gamma_s_per_byte > 0

    def test_env_path_override(self, tmp_path, monkeypatch):
        p = str(tmp_path / "alt.json")
        monkeypatch.setenv("HVDT_COSTMODEL_CALIBRATION", p)
        assert cm.default_calibration_path() == p
        monkeypatch.delenv("HVDT_COSTMODEL_CALIBRATION")
        assert cm.default_calibration_path().endswith(
            cm.CALIBRATION_NAME)


class TestFit:
    def _rows(self, alpha, beta, algorithm="ring", axis="ici",
              axis_size=4, wire="f32"):
        rows = []
        for size in (1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22):
            hops, wf = cm.collective_geometry("allreduce", algorithm,
                                              axis_size)
            wire_b = wf * size * cm.wire_shrink(wire)
            rows.append({"axis": axis, "algorithm": algorithm,
                         "wire": wire, "size_bytes": size,
                         "axis_size": axis_size,
                         "seconds": alpha * hops + beta * wire_b,
                         "bytes_on_wire": wire_b})
        return rows

    def test_recovers_known_constants(self):
        cal = cm.fit_from_bench(self._rows(alpha=5e-6, beta=3e-9))
        c = cal.groups[("ici", "ring", "f32")]
        assert c.alpha_s == pytest.approx(5e-6, rel=1e-6)
        assert c.beta_s_per_byte == pytest.approx(3e-9, rel=1e-6)

    def test_nonneg_clamp(self):
        # Constant-time rows regardless of size: pure latency; the
        # byte term must clamp to >= 0, never fit negative.
        rows = [{"axis": "dcn", "algorithm": "ring", "wire": "f32",
                 "size_bytes": s, "axis_size": 2, "seconds": 1e-3,
                 "bytes_on_wire": None}
                for s in (1 << 12, 1 << 16, 1 << 20)]
        cal = cm.fit_from_bench(rows)
        c = cal.groups[("dcn", "ring", "f32")]
        assert c.alpha_s >= 0 and c.beta_s_per_byte >= 0

    def test_single_row_group_skipped(self):
        cal = cm.fit_from_bench(self._rows(1e-6, 1e-9)[:1])
        assert cal.groups == {}

    def test_normalize_rows_legacy_and_compound_wire(self):
        doc = {"n_devices": 8, "mesh": {"dcn": 2, "ici": 4}, "rows": [
            {"axis": "ici", "algorithm": "ring", "wire": "f32",
             "bytes": 4096, "us": 100.0},
            {"axis": "ici+dcn", "algorithm": "hierarchical",
             "wire": "f32/f32", "bytes": 4096, "us": 50.0},
            {"axis": "ici+dcn", "algorithm": "hierarchical",
             "wire": "f32/int8", "bytes": 4096, "us": 40.0},
            {"axis": "", "bytes": 1, "us": 1.0},        # no axis: drop
            {"axis": "dp", "us": 1.0},                   # no size: drop
        ]}
        rows = cm.normalize_rows(doc)
        assert len(rows) == 3
        assert rows[0]["seconds"] == pytest.approx(1e-4)
        assert rows[0]["axis_size"] == 4
        wires = {r["wire"] for r in rows}
        # homogeneous compound collapses; mixed stays distinct
        assert wires == {"f32", "f32/int8"}

    def test_checked_in_calibration_is_fitted(self):
        cal = cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME))
        assert "degraded" not in cal.meta
        assert ("ici", "ring", "f32") in cal.groups
        assert ("dcn", "ring", "f32") in cal.groups
        assert ("ici+dcn", "flat", "f32") in cal.groups
        meas = cal.meta.get("measured_hier_speedup")
        assert meas and meas["value"] > 0 and meas["at_bytes"] > 0


# ---------------------------------------------------------------------------
# fingerprint evaluation: hidden/exposed, wire accounting
# ---------------------------------------------------------------------------


class TestEvaluate:
    def _model(self):
        return cm.CostModel(cm.Calibration())    # topology defaults

    def test_barrier_groups_split_hidden_vs_exposed(self):
        fp = sched.ScheduleFingerprint([
            _ev(0, "psum", ("ici",), barriers_before=0),
            _ev(1, "psum", ("ici",), post_barrier=True,
                barriers_before=1),
            _ev(2, "psum", ("ici",), post_barrier=True,
                barriers_before=2),
        ], n_barriers=2, label="pipe")
        fc = self._model().evaluate(
            fp, tp.TopologySpec(pods=1, chips_per_pod=4))
        # last barrier group is exposed; earlier buckets hide
        assert len(fc.per_bucket_s) == 3
        assert fc.exposed_comm_s == pytest.approx(fc.per_bucket_s[2])
        assert fc.hidden_comm_s == pytest.approx(
            fc.per_bucket_s[0] + fc.per_bucket_s[1])
        assert 0 < fc.overlap_fraction < 1

    def test_no_barriers_all_exposed(self):
        fp = sched.ScheduleFingerprint(
            [_ev(0, "psum", ("ici",)), _ev(1, "psum", ("ici",))],
            n_barriers=0, label="mono")
        fc = self._model().evaluate(
            fp, tp.TopologySpec(pods=1, chips_per_pod=4))
        assert fc.exposed_comm_s == pytest.approx(fc.total_comm_s)
        assert fc.overlap_fraction == 0.0

    def test_wire_accounting_ring(self):
        fp = sched.ScheduleFingerprint(
            [_ev(0, "psum", ("ici",), nbytes=8192, count=2048)])
        fc = self._model().evaluate(
            fp, tp.TopologySpec(pods=1, chips_per_pod=8))
        # ring allreduce moves 2(n-1)/n of the payload
        assert fc.wire_bytes_by_axis["ici"] == int(8192 * 1.75)

    def test_flat_multi_tier_pays_both_tiers(self):
        fp = sched.ScheduleFingerprint(
            [_ev(0, "psum", ("dcn", "ici"), nbytes=8192, count=2048)])
        fc = self._model().evaluate(
            fp, tp.TopologySpec(pods=2, chips_per_pod=4))
        assert set(fc.wire_bytes_by_axis) == {"ici", "dcn"}
        # full payload on the slow tier too — the flat penalty
        assert fc.wire_bytes_by_axis["dcn"] == 8192  # 2(2-1)/2 * 8192

    def test_int8_event_uses_wire_class(self):
        fp8 = sched.ScheduleFingerprint(
            [_ev(0, "all_to_all", ("dcn",), dtype="int8",
                 nbytes=1024, count=1024)])
        fc = self._model().evaluate(
            fp8, tp.TopologySpec(pods=4, chips_per_pod=1))
        assert fc.total_comm_s > 0
        # nbytes are wire bytes already; the tier total reflects them
        assert fc.wire_bytes_by_axis["dcn"] == int(1024 * 0.75)

    def test_evaluation_deterministic(self):
        fp = sched.ScheduleFingerprint(
            [_ev(0, "psum", ("dcn", "ici")),
             _ev(1, "reduce_scatter", ("ici",), barriers_before=1,
                 post_barrier=True)], n_barriers=1)
        m = self._model()
        topo = tp.TopologySpec(pods=2, chips_per_pod=4)
        a = m.evaluate(fp, topo).to_dict()
        b = m.evaluate(fp, topo).to_dict()
        assert a == b


# ---------------------------------------------------------------------------
# model-vs-measured + weak scaling (the acceptance asserts)
# ---------------------------------------------------------------------------


class TestModelValidation:
    def test_hier_speedup_matches_measured_within_25pct(self):
        """The fitted model must reproduce the cached measured
        hierarchical_speedup_vs_flat_at_peak of the calibration
        sweep."""
        cal = cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME))
        meas = cal.meta["measured_hier_speedup"]
        mesh = meas["mesh"]
        model = cm.CostModel(cal)
        pred = model.hierarchical_speedup(
            meas["at_bytes"],
            tp.TopologySpec(pods=mesh["dcn"],
                            chips_per_pod=mesh["ici"]))
        assert abs(pred - meas["value"]) / meas["value"] <= 0.25

    def test_weak_scaling_monotone_and_deterministic(self):
        cal = cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME))
        model = cm.CostModel(cal)
        wl = tp.REFERENCE_STEP_WORKLOAD
        a = model.weak_scaling_curve(wl["grad_bytes"],
                                     wl["flops_per_step"])
        b = model.weak_scaling_curve(wl["grad_bytes"],
                                     wl["flops_per_step"])
        assert a == b                      # pure arithmetic, no devices
        chips = [r["chips"] for r in a]
        assert chips == list(cm.DEFAULT_CURVE_CHIPS)
        frs = [r["comm_fraction"] for r in a]
        assert all(later >= earlier
                   for earlier, later in zip(frs, frs[1:]))
        assert all(r["comm_s"] > 0 for r in a)

    def test_curve_comm_grows_with_pods(self):
        model = cm.CostModel(cm.Calibration())
        rows = model.weak_scaling_curve(1 << 26, 1e9)
        comm = [r["comm_s"] for r in rows]
        assert comm == sorted(comm)
        assert rows[-1]["pods"] == 64 and rows[-1]["chips_per_pod"] == 4

    def test_256_chip_topology_evaluable_without_devices(self):
        """The point of ROADMAP 5(b): a 16x16 mesh priced on CPU."""
        fp = sched.ScheduleFingerprint(
            [_ev(0, "psum", ("dcn", "ici"), nbytes=1 << 20)])
        fc = cm.CostModel(cm.Calibration()).evaluate(
            fp, tp.TopologySpec(pods=16, chips_per_pod=16))
        assert fc.topology.total_chips == 256
        assert fc.total_comm_s > 0


# ---------------------------------------------------------------------------
# the --perf CI gate
# ---------------------------------------------------------------------------


class TestPerfGate:
    def test_repo_gate_clean(self, capsys):
        rc = analysis_main(["--perf"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "hier-speedup model" in out
        assert "weak-scaling comm fraction" in out

    def _export_reference(self, tmp_path, label="overlap-hier"):
        fps = {fp.label: fp for fp in _reference_fingerprints()}
        doc = fps[label].to_dict()
        path = tmp_path / f"{label}.json"
        path.write_text(json.dumps(doc))
        return doc, path

    def test_clean_fingerprint_roundtrip_passes(self, tmp_path,
                                                capsys):
        _, path = self._export_reference(tmp_path)
        rc = analysis_main(["--perf", "--perf-fingerprint", str(path)])
        assert rc == 0, capsys.readouterr().out

    def test_doubled_dcn_wire_bytes_fails_named(self, tmp_path,
                                                capsys):
        doc, _ = self._export_reference(tmp_path)
        for e in doc["events"]:
            if e["axes"] == ["dcn"]:
                e["nbytes"] *= 2
                e["count"] *= 2
        bad = tmp_path / "tampered.json"
        bad.write_text(json.dumps(doc))
        rc = analysis_main(["--perf", "--perf-fingerprint", str(bad)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "dcn wire bytes regression" in out
        assert "overlap-hier" in out

    def test_dropped_overlap_fails_named(self, tmp_path, capsys):
        doc, _ = self._export_reference(tmp_path)
        doc["n_barriers"] = 0
        for e in doc["events"]:
            e["post_barrier"] = False
            e["barriers_before"] = 0
        bad = tmp_path / "nooverlap.json"
        bad.write_text(json.dumps(doc))
        rc = analysis_main(["--perf", "--perf-fingerprint", str(bad)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "overlap fraction dropped" in out
        assert "exposed-comm regression" in out

    def test_update_baseline_roundtrip(self, tmp_path, capsys):
        bl = tmp_path / "perf.json"
        rc = analysis_main(["--perf", "--update-perf-baseline",
                            "--perf-baseline", str(bl)])
        assert rc == 0
        doc = json.loads(bl.read_text())
        assert set(doc["entries"]) == {
            "overlap-plain", "overlap-hier", "overlap-hier-zero",
            "parallel4d"}
        for entry in doc["entries"].values():
            assert entry["exposed_comm_s"] > 0
            assert entry["wire_bytes_by_axis"]
        rc = analysis_main(["--perf", "--perf-baseline", str(bl)])
        assert rc == 0, capsys.readouterr().out

    def test_missing_baseline_fails_with_hint(self, tmp_path, capsys):
        rc = analysis_main(["--perf", "--perf-baseline",
                            str(tmp_path / "nope.json")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "--update-perf-baseline" in out

    def test_committed_baseline_current(self, capsys):
        """The checked-in .hvdt-perf-baseline.json matches what the
        reference fingerprints + calibration predict today — the
        ratchet is live, not stale."""
        rc = analysis_main(["--perf"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "FAIL" not in out


# ---------------------------------------------------------------------------
# lint satellites: magic-peak-flops + stale-baseline hard mode
# ---------------------------------------------------------------------------


class TestMagicPeakFlopsRule:
    def _lint(self, src, path="horovod_tpu/somewhere/mod.py"):
        return [f for f in lint_source(src, path,
                                       rules=[MagicPeakFlopsRule()])]

    def test_peak_literal_flagged(self):
        fs = self._lint("PEAK = 918e12\n")
        assert len(fs) == 1 and fs[0].rule == "magic-peak-flops"

    def test_bandwidth_literal_flagged(self):
        assert self._lint("BW = 819e9\n")

    def test_sentinels_and_conversions_pass(self):
        assert self._lint("x = -1e30\ny = 1e9\nz = s / 1e6\n") == []

    def test_blessed_homes_exempt(self):
        src = "PEAK = 918e12\n"
        assert self._lint(
            src, "horovod_tpu/telemetry/step_stats.py") == []
        assert self._lint(
            src, "horovod_tpu/analysis/topology.py") == []

    def test_repo_clean_under_rule(self):
        from horovod_tpu.analysis.lint import (default_paths,
                                               lint_paths)

        findings = [f for f in lint_paths(default_paths(REPO), root=REPO,
                                          rules=[MagicPeakFlopsRule()])]
        assert findings == [], [f.format() for f in findings]


class TestStaleBaselineHardMode:
    def _tree(self, tmp_path):
        pkg = tmp_path / "horovod_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            "import os\n"
            "def read():\n"
            "    return os.environ.get('HVDT_NOT_DECLARED_XYZ')\n")
        return str(tmp_path)

    def test_stale_entry_fails_hard_mode(self, tmp_path, capsys):
        from horovod_tpu.analysis.lint import run_lint

        root = self._tree(tmp_path)
        bl = str(tmp_path / ".hvdt-lint-baseline.json")
        # Baseline the real finding, then add a stale entry.
        _, found, _ = run_lint(root, baseline_path=bl,
                               update_baseline=True)
        doc = json.loads(open(bl).read())
        doc["suppressions"].append(
            {"key": "knob-drift:horovod_tpu/mod.py:deadbeef0000:0",
             "rule": "knob-drift", "reason": "edited away"})
        open(bl, "w").write(json.dumps(doc))
        assert _gate_lint(root, bl, update=False,
                          fail_on_stale=False) == 0
        rc = _gate_lint(root, bl, update=False, fail_on_stale=True)
        out = capsys.readouterr().out
        assert rc == 1
        assert "FAIL stale-baseline" in out

    def test_update_baseline_prunes_stale(self, tmp_path):
        from horovod_tpu.analysis.lint import load_baseline, run_lint

        root = self._tree(tmp_path)
        bl = str(tmp_path / ".hvdt-lint-baseline.json")
        run_lint(root, baseline_path=bl, update_baseline=True)
        doc = json.loads(open(bl).read())
        doc["suppressions"].append(
            {"key": "knob-drift:horovod_tpu/mod.py:deadbeef0000:0",
             "rule": "knob-drift", "reason": "stale"})
        open(bl, "w").write(json.dumps(doc))
        run_lint(root, baseline_path=bl, update_baseline=True)
        keys = set(load_baseline(bl))
        assert "knob-drift:horovod_tpu/mod.py:deadbeef0000:0" not in keys
        assert _gate_lint(root, bl, update=False,
                          fail_on_stale=True) == 0

    def test_lock_suppressions_not_counted_stale(self, tmp_path,
                                                 capsys):
        root = self._tree(tmp_path)
        bl = str(tmp_path / ".hvdt-lint-baseline.json")
        from horovod_tpu.analysis.lint import run_lint

        run_lint(root, baseline_path=bl, update_baseline=True)
        doc = json.loads(open(bl).read())
        doc["suppressions"].append(
            {"key": "lock-cycle:A->B->A", "rule": "lock-cycle",
             "reason": "keyed by the locks gate"})
        open(bl, "w").write(json.dumps(doc))
        assert _gate_lint(root, bl, update=False,
                          fail_on_stale=True) == 0


# ---------------------------------------------------------------------------
# autotune model pre-seeding
# ---------------------------------------------------------------------------


class TestAutotuneModelSeed:
    @pytest.fixture(autouse=True)
    def _clean_env(self, monkeypatch):
        for k in ("HVDT_AUTOTUNE_MODEL_SEED", "HVDT_TRANSPORT",
                  "HVDT_AUTOTUNE_TRANSPORT_SEED", "HVDT_OVERLAP",
                  "HVDT_QUANT", "HVDT_COMPRESSION", "HVDT_ZERO"):
            monkeypatch.delenv(k, raising=False)
        from horovod_tpu import transport
        from horovod_tpu.ops import overlap as ovl

        transport.reset()
        ovl.reset()
        yield
        transport.reset()
        ovl.reset()

    def test_off_by_default_no_behavior_change(self):
        from horovod_tpu.autotune import (_env_overlap, _env_quant_wire,
                                          _env_transport, _model_seed)

        assert _model_seed("transport") is None
        assert _env_transport() is False
        assert _env_overlap() is False
        assert _env_quant_wire() is False

    def test_model_orders_legs_when_enabled(self, monkeypatch):
        from horovod_tpu.autotune import (_env_overlap, _env_quant_wire,
                                          _env_transport)

        monkeypatch.setenv("HVDT_AUTOTUNE_MODEL_SEED", "1")
        expect = cm.predict_leg_order(cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME)))
        assert _env_transport() is expect["transport"]
        assert _env_overlap() is expect["overlap"]
        assert _env_quant_wire() is expect["quant"]

    def test_calibration_path_value(self, tmp_path, monkeypatch):
        from horovod_tpu.autotune import _model_seed

        # Craft a calibration where hierarchy clearly wins: slow dcn
        # links, cheap ici — the model must order transport=hier.
        cal = cm.Calibration({
            ("ici", "ring", "f32"): tp.LinkConstants(1e-7, 1e-11),
            ("dcn", "ring", "f32"): tp.LinkConstants(1e-6, 1e-8),
        })
        p = str(tmp_path / "cal.json")
        cal.save(p)
        monkeypatch.setenv("HVDT_AUTOTUNE_MODEL_SEED", p)
        assert _model_seed("transport") is True

    def test_measured_seed_wins_over_model(self, tmp_path,
                                           monkeypatch):
        from horovod_tpu.autotune import _env_transport

        monkeypatch.setenv("HVDT_AUTOTUNE_MODEL_SEED", "1")
        seed = tmp_path / "sweep.json"
        seed.write_text(json.dumps(
            {"hierarchical_speedup_vs_flat_at_peak": 1.4}))
        monkeypatch.setenv("HVDT_AUTOTUNE_TRANSPORT_SEED", str(seed))
        assert _env_transport() is True
        seed.write_text(json.dumps(
            {"hierarchical_speedup_vs_flat_at_peak": 0.6}))
        assert _env_transport() is False

    def test_unreadable_seed_falls_back_to_model(self, tmp_path,
                                                 monkeypatch):
        from horovod_tpu.autotune import _env_transport

        monkeypatch.setenv("HVDT_AUTOTUNE_TRANSPORT_SEED",
                           str(tmp_path / "missing.json"))
        assert _env_transport() is False     # model off: blind default
        monkeypatch.setenv("HVDT_AUTOTUNE_MODEL_SEED", "1")
        expect = cm.predict_leg_order(cm.load_calibration(
            os.path.join(REPO, cm.CALIBRATION_NAME)))
        assert _env_transport() is expect["transport"]

    def test_explicit_env_wins_over_model(self, monkeypatch):
        from horovod_tpu.autotune import _env_overlap, _env_quant_wire

        monkeypatch.setenv("HVDT_AUTOTUNE_MODEL_SEED", "1")
        monkeypatch.setenv("HVDT_OVERLAP", "off")
        assert _env_overlap() is False
        monkeypatch.setenv("HVDT_COMPRESSION", "bf16")
        assert _env_quant_wire() is False
        monkeypatch.setenv("HVDT_COMPRESSION", "int8")
        assert _env_quant_wire() is True

    def test_predict_leg_order_shape(self):
        verdict = cm.predict_leg_order(cm.Calibration())
        assert set(verdict) == {"transport", "quant", "overlap",
                                "moe", "pipeline"}
        assert all(isinstance(v, bool) for v in verdict.values())
        # defaults: slow dcn, fast ici => hierarchy + overlap pay off
        assert verdict["transport"] is True
        assert verdict["overlap"] is True


# ---------------------------------------------------------------------------
# CLI subprocess (the compose `analysis` service contract)
# ---------------------------------------------------------------------------


@pytest.mark.integration
def test_cli_perf_gate_subprocess():
    """`python -m horovod_tpu.analysis --perf` exits 0 from a bare
    environment — the gate forces its own deterministic 8-device sim."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS",)}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--perf"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=200)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "hvdt-perf: 0 problem(s)" in proc.stdout
    assert "hvdt-analysis: CLEAN" in proc.stdout
