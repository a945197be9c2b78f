"""Static distributed-correctness analysis (horovod_tpu/analysis):
per-rule lint fixtures, the ratcheting baseline, the lock-order graph,
knob-table drift, schedule fingerprints on the mesh-8 overlapped +
hierarchical + ZeRO step, autotune flip-leg compatibility on all seven
dimensions, and the flight recorder's static-expected-vs-observed
desync reporting (unit + multiprocess E2E).  All CPU on the simulated
8-device mesh."""

import inspect
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map as shard_map

from horovod_tpu.analysis import lint as lint_mod
from horovod_tpu.analysis import locks as locks_mod
from horovod_tpu.analysis import schedule as sched
from horovod_tpu.analysis.lint import (Finding, LintContext, apply_baseline,
                                       check_knob_docs, knob_table_markdown,
                                       lint_source, load_baseline, run_lint,
                                       save_baseline)
from horovod_tpu.common.types import ReduceOp
from horovod_tpu.ops import device as dev
from horovod_tpu.ops import overlap as ovl
from horovod_tpu.ops import zero as zero_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smap_kw():
    sig = inspect.signature(jax.shard_map).parameters
    if "check_rep" in sig:
        return {"check_rep": False}
    if "check_vma" in sig:
        return {"check_vma": False}
    return {}


def _ctx():
    return LintContext(declared={"HVDT_KNOWN"}, contract={"HVDT_WIRED"})


def _findings(src, path="mod.py", rule=None):
    out = lint_source(textwrap.dedent(src), path, ctx=_ctx())
    if rule:
        out = [f for f in out if f.rule == rule]
    return out


# ---------------------------------------------------------------------------
# lint rules: positive (seeded violation caught) / negative (clean code
# passes) fixture per rule
# ---------------------------------------------------------------------------


class TestKnobDriftRule:
    def test_undeclared_read_flagged(self):
        fs = _findings('import os\nv = os.environ.get("HVDT_BOGUS")\n',
                       rule="knob-drift")
        assert len(fs) == 1 and "HVDT_BOGUS" in fs[0].message

    def test_declared_and_contract_pass(self):
        src = '''
        import os
        a = os.environ.get("HVDT_KNOWN")
        b = os.environ.get("HVDT_WIRED")
        '''
        assert _findings(src, rule="knob-drift") == []

    def test_docstring_mentions_ignored(self):
        src = '"""Uses HVDT_BOGUS for spice."""\nx = 1\n'
        assert _findings(src, rule="knob-drift") == []

    def test_config_py_itself_exempt(self):
        fs = _findings('k = "HVDT_BOGUS"\n',
                       path=os.path.join("common", "config.py"),
                       rule="knob-drift")
        assert fs == []


class TestZeroOverheadGateRule:
    def test_gate_without_none_path_flagged(self):
        src = '''
        import os
        def get_widget():
            raw = os.environ.get("HVDT_KNOWN")
            return Widget(raw)
        '''
        fs = _findings(src, rule="zero-overhead-gate")
        assert len(fs) == 1 and "get_widget" in fs[0].message

    def test_none_when_unset_passes(self):
        src = '''
        import os
        def get_widget():
            raw = os.environ.get("HVDT_KNOWN")
            return Widget(raw) if raw else None
        '''
        assert _findings(src, rule="zero-overhead-gate") == []

    def test_non_env_get_functions_ignored(self):
        src = 'def get_name(o):\n    return o.name\n'
        assert _findings(src, rule="zero-overhead-gate") == []


class TestNondetIterationRule:
    def test_set_iteration_flagged(self):
        src = '''
        for x in set(items):
            use(x)
        ys = [f(x) for x in {1, 2, 3}]
        '''
        assert len(_findings(src, rule="nondet-iteration")) == 2

    def test_sorted_wrapper_passes(self):
        src = '''
        for x in sorted(set(items)):
            use(x)
        '''
        assert _findings(src, rule="nondet-iteration") == []


class TestSleepPollRule:
    def test_sleep_in_loop_flagged(self):
        src = '''
        import time
        while not ready():
            time.sleep(0.1)
        '''
        assert len(_findings(src, rule="sleep-poll")) == 1

    def test_from_import_sleep_flagged(self):
        src = '''
        from time import sleep
        for _ in range(3):
            sleep(1)
        '''
        assert len(_findings(src, rule="sleep-poll")) == 1

    def test_sleep_outside_loop_passes(self):
        src = 'import time\ntime.sleep(1)\n'
        assert _findings(src, rule="sleep-poll") == []

    def test_retry_module_exempt(self):
        src = '''
        import time
        while True:
            time.sleep(0.1)
        '''
        fs = _findings(src, path=os.path.join("resilience", "retry.py"),
                       rule="sleep-poll")
        assert fs == []


class TestFindingKeys:
    def test_key_survives_line_moves(self):
        a = Finding("r", "p.py", 10, "m", snippet="  time.sleep(0.1)")
        b = Finding("r", "p.py", 99, "m", snippet="time.sleep(0.1)  ")
        assert a.key == b.key

    def test_duplicate_snippets_get_occurrences(self):
        src = '''
        import time
        while a():
            time.sleep(0.1)
        while b():
            time.sleep(0.1)
        '''
        fs = _findings(src, rule="sleep-poll")
        assert len({f.key for f in fs}) == 2


# ---------------------------------------------------------------------------
# ratcheting baseline
# ---------------------------------------------------------------------------


class TestBaselineRatchet:
    def test_suppress_new_and_stale(self, tmp_path):
        f1 = Finding("sleep-poll", "a.py", 1, "m", snippet="x")
        f2 = Finding("sleep-poll", "b.py", 2, "m", snippet="y")
        bp = str(tmp_path / "base.json")
        save_baseline(bp, [f1], reasons={f1.key: "legacy"})
        new, suppressed, stale = apply_baseline([f1, f2],
                                                load_baseline(bp))
        assert [f.key for f in new] == [f2.key]
        assert [f.key for f in suppressed] == [f1.key]
        assert stale == []
        # f1 fixed -> its suppression is stale
        new, suppressed, stale = apply_baseline([f2], load_baseline(bp))
        assert stale == [f1.key] and [f.key for f in new] == [f2.key]

    def test_lock_suppressions_survive_update(self, tmp_path):
        bp = str(tmp_path / "base.json")
        f1 = Finding("sleep-poll", "a.py", 1, "m", snippet="x")
        save_baseline(bp, [f1], keep={"lock-cycle:a->b": "legacy order"})
        doc = load_baseline(bp)
        assert doc["lock-cycle:a->b"] == "legacy order"
        assert f1.key in doc


# ---------------------------------------------------------------------------
# lock-order graph
# ---------------------------------------------------------------------------


class TestLockGraph:
    def _edges(self, src, tmp_path, name="m.py"):
        p = tmp_path / name
        p.write_text(textwrap.dedent(src))
        return locks_mod.extract_lock_graph([str(p)], root=str(tmp_path))

    def test_nested_with_records_edge(self, tmp_path):
        src = '''
        class A:
            def f(self):
                with self._lock:
                    with peer.lock:
                        pass
        '''
        edges = self._edges(src, tmp_path)
        assert len(edges) == 1
        assert edges[0].outer.endswith("A:self._lock")
        assert edges[0].inner.endswith("A:peer.lock")

    def test_multi_item_with_records_edge(self, tmp_path):
        src = '''
        def f():
            with a_lock, b_lock:
                pass
        '''
        edges = self._edges(src, tmp_path)
        assert len(edges) == 1

    def test_abba_cycle_detected(self, tmp_path):
        src = '''
        class A:
            def f(self):
                with self._lock:
                    with peer.lock:
                        pass
            def g(self):
                with peer.lock:
                    with self._lock:
                        pass
        '''
        cycles = locks_mod.find_cycles(self._edges(src, tmp_path))
        assert len(cycles) == 1 and len(cycles[0]) == 2

    def test_consistent_order_no_cycle(self, tmp_path):
        src = '''
        def f():
            with a_lock:
                with b_lock:
                    pass
        def g():
            with a_lock:
                with b_lock:
                    pass
        '''
        assert locks_mod.find_cycles(self._edges(src, tmp_path)) == []

    def test_cycle_key_rotation_invariant(self):
        assert locks_mod.cycle_key(["b", "a"]) == \
            locks_mod.cycle_key(["a", "b"])

    def test_non_lock_with_ignored(self, tmp_path):
        src = '''
        def f():
            with open(p) as fh:
                with self._lock:
                    pass
        '''
        assert self._edges(src, tmp_path) == []


# ---------------------------------------------------------------------------
# knob table + docs drift (the knob-drift killer satellite)
# ---------------------------------------------------------------------------


class TestKnobTable:
    def test_table_covers_every_knob(self):
        from horovod_tpu.common import config

        table = knob_table_markdown()
        for name in config.KNOBS:
            assert f"`{name}`" in table
        for name in config.CONTRACT_VARS:
            assert f"`{name}`" in table

    def test_repo_docs_in_sync(self):
        assert check_knob_docs(REPO) == []

    def test_stale_doc_detected(self, tmp_path):
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "knobs.md").write_text("# Runtime knob registry\nstale\n")
        probs = check_knob_docs(str(tmp_path))
        assert any("stale" in p for p in probs)

    def test_unknown_doc_token_detected(self, tmp_path):
        docs = tmp_path / "docs"
        docs.mkdir()
        lint_mod.write_knob_table(str(docs / "knobs.md"))
        (docs / "extra.md").write_text("set `HVDT_TOTALLY_BOGUS=1`\n")
        probs = check_knob_docs(str(tmp_path))
        assert any("HVDT_TOTALLY_BOGUS" in p for p in probs)

    def test_wildcard_prefix_mentions_pass(self, tmp_path):
        docs = tmp_path / "docs"
        docs.mkdir()
        lint_mod.write_knob_table(str(docs / "knobs.md"))
        (docs / "extra.md").write_text("all the HVDT_SERVE_* knobs\n")
        assert check_knob_docs(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# the repo gates themselves (what CI runs — must stay clean)
# ---------------------------------------------------------------------------


class TestRepoGate:
    def test_lint_gate_clean(self):
        new, suppressed, stale = run_lint(REPO)
        assert new == [], "\n".join(f.format() for f in new)
        # every suppression carries a hand-written reason
        bl = load_baseline(os.path.join(REPO, lint_mod.BASELINE_NAME))
        for key, reason in bl.items():
            assert reason and "needs a written reason" not in reason, key

    def test_lock_gate_clean(self):
        cycles, _edges = locks_mod.run_locks(REPO)
        assert cycles == []


# ---------------------------------------------------------------------------
# schedule fingerprint: mesh-8 overlapped + hierarchical + ZeRO step
# ---------------------------------------------------------------------------


@pytest.fixture()
def mesh_hier(devices):
    return Mesh(np.asarray(devices, dtype=object).reshape(2, 4),
                ("dcn", "ici"))


@pytest.fixture()
def hier_env(monkeypatch):
    from horovod_tpu import transport

    monkeypatch.setenv("HVDT_OVERLAP", "on")
    monkeypatch.setenv("HVDT_TRANSPORT",
                       "ici:ring:f32:64M,dcn:ring:f32:64M")
    ovl.reset()
    transport.reset()
    yield
    monkeypatch.delenv("HVDT_TRANSPORT", raising=False)
    monkeypatch.delenv("HVDT_OVERLAP", raising=False)
    ovl.reset()
    transport.reset()


def _mixed_tree():
    rng = np.random.RandomState(0)
    return {
        "w": jnp.asarray(rng.randn(8, 96), jnp.float32),
        "i": jnp.asarray(rng.randint(0, 9, (8, 16)), jnp.int32),
        "b": jnp.asarray(rng.randn(8, 33), jnp.float32),
    }


def _hier_zero_step(mesh_hier):
    """The composed mesh-8 step: overlapped bucketed exchange routed
    hierarchically over (dcn, ici) + a ZeRO reduce-scatter-wire
    exchange over ici — one traced program touching all three comm
    subsystems."""
    tree = _mixed_tree()
    leaves = list(tree.values())

    def body(*ls):
        g = ovl.OverlapScheduler().exchange(
            list(ls), axis=("dcn", "ici"), op=ReduceOp.AVERAGE,
            threshold_bytes=2048)
        z = zero_mod.rs_exchange(
            {"z": ls[0] * 2.0}, axis="ici", op=ReduceOp.AVERAGE,
            threshold_bytes=2048)
        return tuple(g) + (z["z"],)

    def step(*ls):
        return shard_map(
            body, mesh=mesh_hier,
            in_specs=(P(("dcn", "ici")),) * len(ls),
            out_specs=(P(),) * (len(ls) + 1), **_smap_kw())(*ls)

    return step, leaves


class TestScheduleFingerprint:
    def test_stable_across_two_traces(self, mesh_hier, hier_env):
        step, leaves = _hier_zero_step(mesh_hier)
        fp1 = sched.extract_schedule(step, *leaves, label="hz")
        fp2 = sched.extract_schedule(step, *leaves, label="hz")
        assert fp1.digest == fp2.digest
        assert len(fp1.events) >= 3            # hier float + int + zero
        kinds = set(fp1.counts())
        assert "reduce_scatter" in kinds and "psum" in kinds
        assert fp1.n_barriers >= 1

    def test_post_pin_psum_family_holds(self, mesh_hier, hier_env):
        step, leaves = _hier_zero_step(mesh_hier)
        fp = sched.extract_schedule(step, *leaves)
        assert sched.verify_post_pin_psum_family(fp) == []
        assert sched.verify_no_data_dependent_collectives(fp) == []

    def test_bucket_plan_permutation_invariant(self):
        leaves = list(_mixed_tree().values())
        assert sched.verify_bucket_plan_invariance(leaves, 2048) == []

    def test_fingerprint_roundtrip(self, tmp_path, mesh_hier, hier_env):
        step, leaves = _hier_zero_step(mesh_hier)
        fp = sched.extract_schedule(step, *leaves, label="hz")
        path = str(tmp_path / "fp.json")
        fp.save(path)
        back = sched.load_fingerprint(path)
        assert back.digest == fp.digest
        assert back.label == "hz"
        assert [e.op for e in back.events] == [e.op for e in fp.events]

    def test_data_dependent_collective_flagged(self, mesh8):
        def body(x):
            return lax.cond(x[0, 0] > 0,
                            lambda v: lax.psum(v, "dp"),
                            lambda v: v, x)

        def step(x):
            return shard_map(body, mesh=mesh8, in_specs=P("dp"),
                             out_specs=P("dp"), **_smap_kw())(x)

        fp = sched.extract_schedule(step, jnp.ones((8, 4)))
        findings = sched.verify_no_data_dependent_collectives(fp)
        assert len(findings) == 1
        assert "cond" in findings[0]["message"]

    def test_while_collective_flagged(self, mesh8):
        def body(x):
            return lax.while_loop(
                lambda s: s[0] < 3.0,
                lambda s: s + lax.psum(s, "dp")[0] * 0 + 1,
                x)

        def step(x):
            return shard_map(body, mesh=mesh8, in_specs=P("dp"),
                             out_specs=P("dp"), **_smap_kw())(x)

        fp = sched.extract_schedule(step, jnp.ones((8,)))
        assert sched.verify_no_data_dependent_collectives(fp)

    def test_post_pin_violation_detected_synthetic(self):
        ev = sched.CollectiveEvent(
            index=0, op="all_to_all", axes=("dcn",), dtype="float32",
            count=8, nbytes=32, context=(), post_barrier=True)
        fp = sched.ScheduleFingerprint([ev], n_barriers=1)
        assert len(sched.verify_post_pin_psum_family(fp)) == 1

    def test_scan_collective_not_flagged(self, mesh8):
        def body(x):
            out, _ = lax.scan(
                lambda c, _: (c + lax.psum(c, "dp") * 0, None),
                x, None, length=2)
            return out

        def step(x):
            return shard_map(body, mesh=mesh8, in_specs=P("dp"),
                             out_specs=P("dp"), **_smap_kw())(x)

        fp = sched.extract_schedule(step, jnp.ones((8, 4)))
        assert fp.events and \
            sched.verify_no_data_dependent_collectives(fp) == []

    def test_hlo_counts_cross_check(self, mesh8):
        def step(x):
            return shard_map(lambda v: lax.psum(v, "dp"), mesh=mesh8,
                             in_specs=P("dp"), out_specs=P(),
                             **_smap_kw())(x)

        counts = sched.hlo_collective_counts(step, jnp.ones((8, 4)))
        assert counts.get("all_reduce", 0) >= 1


# ---------------------------------------------------------------------------
# autotune flip-leg compatibility — all 7 tuned dimensions
# ---------------------------------------------------------------------------


def _flat_exchange(mesh, threshold=None, wire=None, use_overlap=False,
                   use_zero=False):
    def body(*ls):
        tree = list(ls)
        if use_zero:
            out = zero_mod.rs_exchange(tree, axis="dp",
                                       threshold_bytes=threshold)
        elif use_overlap:
            out = ovl.OverlapScheduler().exchange(
                tree, axis="dp", threshold_bytes=threshold,
                wire_dtype=wire)
        else:
            out = dev.fused_allreduce(tree, "dp",
                                      threshold_bytes=threshold,
                                      wire_dtype=wire)
        return tuple(out)

    def step(*ls):
        return shard_map(body, mesh=mesh,
                         in_specs=(P("dp"),) * len(ls),
                         out_specs=(P(),) * len(ls), **_smap_kw())(*ls)

    return step


class TestFlipLegCompat:
    """Every HVDT_AUTOTUNE_* dimension's leg pair must keep one state
    tree and identical output avals — the hot-swap contract
    AutotunedStep relies on for all seven dimensions."""

    def _grads(self):
        rng = np.random.RandomState(1)
        return [jnp.asarray(rng.randn(8, 64), jnp.float32),
                jnp.asarray(rng.randn(8, 17), jnp.float32)]

    def _assert_compat(self, res):
        assert res["compatible"], res["findings"]
        assert res["digest_a"] and res["digest_b"]

    def test_dim1_bucket_bytes(self, mesh8):
        g = self._grads()
        state = [jnp.zeros_like(l) for l in g]
        res = sched.verify_flip_compat(
            _flat_exchange(mesh8, threshold=2048),
            _flat_exchange(mesh8, threshold=16384),
            g, state_a=state, state_b=state, dim="log2_bucket")
        self._assert_compat(res)

    def test_dim2_overlap_buckets(self, mesh8):
        # The overlap_buckets knob is host-side pacing: both legs trace
        # the identical program — the flip is free by construction.
        g = self._grads()
        res = sched.verify_flip_compat(
            _flat_exchange(mesh8, threshold=4096),
            _flat_exchange(mesh8, threshold=4096),
            g, dim="overlap_buckets")
        self._assert_compat(res)
        assert res["delta"] == {}
        assert res["digest_a"] == res["digest_b"]

    def test_dim3_fused_optimizer(self):
        from horovod_tpu.ops.optim_kernels import fused_sgd

        g = {"w": jnp.ones((32,), jnp.float32)}
        legs = {}
        for use_kernels in (False, True):
            opt = fused_sgd(0.1, momentum=0.9, use_kernels=use_kernels)
            state = opt.init(g)
            legs[use_kernels] = (
                lambda gg, ss, _opt=opt: _opt.update(gg, ss), state)
        res = sched.verify_flip_compat(
            legs[False][0], legs[True][0], (g, legs[False][1]),
            state_a=legs[False][1], state_b=legs[True][1], dim="fused")
        self._assert_compat(res)

    def test_dim4_quant_wire(self, mesh8):
        g = self._grads()
        res = sched.verify_flip_compat(
            _flat_exchange(mesh8, threshold=4096),
            _flat_exchange(mesh8, threshold=4096,
                           wire="int8_blockwise"),
            g, dim="quant")
        self._assert_compat(res)

    def test_dim5_overlap_schedule(self, mesh8):
        g = self._grads()
        res = sched.verify_flip_compat(
            _flat_exchange(mesh8, threshold=4096),
            _flat_exchange(mesh8, threshold=4096, use_overlap=True),
            g, dim="overlap")
        self._assert_compat(res)

    def test_dim6_transport(self, mesh_hier, monkeypatch):
        from horovod_tpu import transport

        tree = [jnp.ones((8, 64), jnp.float32)]

        def leg(policy):
            def body(*ls):
                if policy:
                    os.environ["HVDT_TRANSPORT"] = policy
                else:
                    os.environ.pop("HVDT_TRANSPORT", None)
                transport.reset()
                out = dev.fused_allreduce(list(ls), ("dcn", "ici"),
                                          threshold_bytes=4096)
                return tuple(out)

            def step(*ls):
                return shard_map(
                    body, mesh=mesh_hier,
                    in_specs=(P(("dcn", "ici")),) * len(ls),
                    out_specs=(P(),) * len(ls), **_smap_kw())(*ls)

            return step

        try:
            res = sched.verify_flip_compat(
                leg(None), leg("ici:ring:f32:64M,dcn:ring:f32:64M"),
                tree, dim="transport")
        finally:
            os.environ.pop("HVDT_TRANSPORT", None)
            transport.reset()
        self._assert_compat(res)
        # the hierarchical leg really lowers differently
        assert res["delta"] != {}

    def test_dim7_zero_sharding(self, mesh8):
        g = self._grads()
        res = sched.verify_flip_compat(
            _flat_exchange(mesh8, threshold=4096),
            _flat_exchange(mesh8, threshold=4096, use_zero=True),
            g, dim="zero")
        self._assert_compat(res)

    def test_incompatible_legs_detected(self, mesh8):
        g = self._grads()
        state_a = [jnp.zeros_like(l) for l in g]
        state_b = {"different": jnp.zeros((3,))}
        res = sched.verify_flip_compat(
            _flat_exchange(mesh8), _flat_exchange(mesh8), g,
            state_a=state_a, state_b=state_b, dim="broken")
        assert not res["compatible"]
        assert any(f["check"] == "flip-state-treedef"
                   for f in res["findings"])


# ---------------------------------------------------------------------------
# static-expected vs runtime-observed (flight-recorder integration)
# ---------------------------------------------------------------------------


def _one_psum_fingerprint(mesh8, tmp_path):
    """A fingerprint matching the desync harness's one-allreduce-per-
    step pattern (op=allreduce, dtype=float32)."""
    def step(x):
        return shard_map(lambda v: lax.psum(v, "dp"), mesh=mesh8,
                         in_specs=P("dp"), out_specs=P(),
                         **_smap_kw())(x)

    fp = sched.extract_schedule(step, jnp.ones((8, 1024), jnp.float32),
                                label="lockstep")
    path = str(tmp_path / "expected_schedule.json")
    fp.save(path)
    return fp, path


class TestExpectedScheduleUnit:
    def test_matching_events_no_deviation(self, mesh8, tmp_path):
        fp, _ = _one_psum_fingerprint(mesh8, tmp_path)
        entries = fp.to_dict()["events"]
        events = [{"seq": i, "op": "allreduce", "dtype": "float32"}
                  for i in range(1, 6)]
        assert sched.first_schedule_deviation(events, entries) is None

    def test_wrong_op_named(self, mesh8, tmp_path):
        fp, _ = _one_psum_fingerprint(mesh8, tmp_path)
        entries = fp.to_dict()["events"]
        events = [{"seq": 1, "op": "allreduce", "dtype": "float32"},
                  {"seq": 2, "op": "allgather", "dtype": "float32"}]
        d = sched.first_schedule_deviation(events, entries)
        assert d and d["seq"] == 2 and "allgather" in d["reason"]

    def test_wrong_dtype_named(self, mesh8, tmp_path):
        fp, _ = _one_psum_fingerprint(mesh8, tmp_path)
        entries = fp.to_dict()["events"]
        events = [{"seq": 1, "op": "allreduce", "dtype": "bfloat16"}]
        d = sched.first_schedule_deviation(events, entries)
        assert d and d["seq"] == 1 and "bfloat16" in d["reason"]

    def test_extra_observed_collective_named(self, mesh_hier,
                                             hier_env):
        """The runtime issues an op the static schedule LACKS (e.g. a
        stray debug allgather injected mid-step): every later seq
        shifts against the expected cycle, so the deviation surfaces at
        the extra op's slot — the satellite coverage for the
        extra-collective path next to missing/mismatched."""
        step, leaves = _hier_zero_step(mesh_hier)
        fp = sched.extract_schedule(step, *leaves, label="hz")
        entries = fp.to_dict()["events"]
        assert len(entries) >= 3
        clean = [{"seq": i + 1, "op": e["event_op"],
                  "dtype": e["dtype"]}
                 for i, e in enumerate(entries)]
        assert sched.first_schedule_deviation(clean, entries) is None
        # Inject an extra alltoall the static schedule never issues;
        # everything after it shifts by one seq.
        extra_at = 2
        observed = clean[:extra_at - 1] + \
            [{"seq": extra_at, "op": "alltoall", "dtype": "float32"}] + \
            [{**e, "seq": e["seq"] + 1} for e in clean[extra_at - 1:]]
        d = sched.first_schedule_deviation(observed, entries)
        assert d is not None
        assert d["seq"] == extra_at
        assert "alltoall" in d["reason"]
        assert d["expected"]["event_op"] == entries[extra_at - 1][
            "event_op"]

    def test_extra_trailing_collective_wraps_cycle(self, mesh8,
                                                   tmp_path):
        """An extra op issued AFTER the step's schedule ran out wraps
        to the next cycle's slot — detected when its kind differs from
        the wrapped expectation."""
        fp, _ = _one_psum_fingerprint(mesh8, tmp_path)
        entries = fp.to_dict()["events"]     # one allreduce per step
        events = [{"seq": 1, "op": "allreduce", "dtype": "float32"},
                  {"seq": 2, "op": "broadcast", "dtype": "float32"}]
        d = sched.first_schedule_deviation(events, entries)
        assert d and d["seq"] == 2 and "broadcast" in d["reason"]

    def test_desync_report_carries_expected_schedule(
            self, mesh8, tmp_path, monkeypatch):
        from horovod_tpu.telemetry import flight_recorder as frm

        _fp, path = _one_psum_fingerprint(mesh8, tmp_path)
        monkeypatch.setenv("HVDT_FLIGHT_RECORDER", "1")
        monkeypatch.setenv("HVDT_RANK", "0")
        monkeypatch.setenv("HVDT_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("HVDT_EXPECTED_SCHEDULE", path)
        monkeypatch.delenv("HVDT_RENDEZVOUS_ADDR", raising=False)
        frm.reset()
        fr = frm.get_flight_recorder()
        for step in range(1, 6):
            seq = fr.record_begin(op="allreduce",
                                  name=f"grads.step{step}",
                                  dtype="float32", shape=(1024,),
                                  nbytes=4096)
            fr.record_end(seq)
        # size=2 with no KV: rank 1 never reported -> missing from the
        # start; the static schedule names what it should have issued.
        report = frm.emit_desync_report(stalled="grads.step5",
                                        age_s=1.0, size=2)
        frm.reset()
        assert report is not None
        sec = report["expected_schedule"]
        assert sec["collectives_per_step"] == 1
        assert sec["digest"]
        fd = sec["first_deviation"]
        assert fd is not None
        assert fd["reason"].startswith("missing")
        assert fd["expected"]["event_op"] == "allreduce"
        assert fd["observed"] is None

    def test_no_section_when_unset(self, tmp_path, monkeypatch):
        from horovod_tpu.telemetry import flight_recorder as frm

        monkeypatch.setenv("HVDT_FLIGHT_RECORDER", "1")
        monkeypatch.delenv("HVDT_EXPECTED_SCHEDULE", raising=False)
        monkeypatch.delenv("HVDT_RENDEZVOUS_ADDR", raising=False)
        frm.reset()
        fr = frm.get_flight_recorder()
        fr.record(op="allreduce", name="g", dtype="float32")
        report = frm.emit_desync_report(stalled="g", size=0)
        frm.reset()
        assert report is not None
        assert "expected_schedule" not in report


# ---------------------------------------------------------------------------
# E2E: seeded hang@step fault plan -> desync report names the static-
# expected collective the hung rank never issued
# ---------------------------------------------------------------------------


@pytest.mark.integration
def test_hang_desync_report_names_expected_collective(mesh8, tmp_path,
                                                      spawn):
    """The PR-6 hang scenario with HVDT_EXPECTED_SCHEDULE exported by
    the static analyzer: rank 1 wedges before step 6's collective; the
    desync report's expected_schedule section must name seq 6 and the
    static entry (allreduce/f32) rank 1 never issued."""
    import time

    from horovod_tpu.runner.http_kv import RendezvousServer

    _fp, fp_path = _one_psum_fingerprint(mesh8, tmp_path)
    server = RendezvousServer()
    port = server.start()
    procs = []
    try:
        for rank in (0, 1):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH",
                                                          ""),
                "HVDT_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVDT_RENDEZVOUS_PORT": str(port),
                "HVDT_SECRET": server.secret.hex(),
                "HVDT_RANK": str(rank),
                "HVDT_SIZE": "2",
                "HVDT_FLIGHT_RECORDER": "1",
                "HVDT_TRACE_DIR": str(tmp_path),
                "HVDT_EXPECTED_SCHEDULE": fp_path,
                "HVDT_FAULT_PLAN": "hang@step=6:rank=1:secs=6",
                "DESYNC_TEST_STEPS": "12",
                "DESYNC_TEST_ABORT_S": "1.0",
            })
            env.pop("HVDT_FAULT_JOURNAL", None)
            procs.append(spawn(
                [sys.executable,
                 os.path.join(REPO, "tests", "data", "desync_main.py")],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT))
        outs = []
        deadline = time.monotonic() + 120
        for p in procs:
            out, _ = p.communicate(
                timeout=max(5, deadline - time.monotonic()))
            outs.append(out.decode())
        assert procs[0].returncode == 0, outs[0][-3000:]
        assert procs[1].returncode == 0, outs[1][-3000:]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("desync scenario hung")
    finally:
        server.stop()

    report = json.load(open(os.path.join(str(tmp_path),
                                         "desync_report_rank0.json")))
    assert report["missing_ranks"] == [1]
    assert report["first_divergent_seq"] == 6
    sec = report["expected_schedule"]
    assert sec["collectives_per_step"] == 1
    fd = sec["first_deviation"]
    assert fd is not None and fd["seq"] == 6
    assert fd["expected"]["event_op"] == "allreduce"
    assert fd["expected"]["dtype"] == "float32"
    assert fd["observed"] is None
    assert fd["rank"] == [1]


# ---------------------------------------------------------------------------
# CLI (the CI gate commands)
# ---------------------------------------------------------------------------


@pytest.mark.integration
def test_cli_all_gate_exits_zero():
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--all"],
        cwd=REPO, capture_output=True, text=True, timeout=200,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert "hvdt-analysis: CLEAN" in r.stdout


def test_cli_knob_table_prints_rows():
    from horovod_tpu.analysis import main

    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["--knob-table"])
    assert rc == 0
    assert "`HVDT_FUSION_THRESHOLD`" in buf.getvalue()
