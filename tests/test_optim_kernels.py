"""Fused Pallas optimizer kernels (ops/optim_kernels.py).

Numerical parity against stock optax in Pallas interpret mode on CPU —
the very same kernel code that runs on TPU — across dtypes, across
eligible and fallback (non-tile-aligned) leaves, composed with
DistributedOptimizer under shard_map, plus the step-pipeline layer
(donation + persistent compilation cache) and the autotuner's
fused-vs-unfused dimension.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from conftest import jit_shard_map as shard_map

from horovod_tpu.ops.optim_kernels import (fused_adam, fused_sgd,
                                           fused_update_eligible)

# Mixed pytree: kernel-eligible leaves (f32 and bf16, tile-aligned) and
# fallback leaves (odd trailing sizes, too-few rows for the sublane
# floor) in one tree — every update exercises BOTH lowerings.
def _params(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return {
        "w": jax.random.normal(ks[0], (16, 128), jnp.float32),
        "deep": jax.random.normal(ks[1], (4, 8, 256), jnp.float32),
        "bias": jax.random.normal(ks[2], (130,), jnp.float32),   # % 128 != 0
        "tiny": jax.random.normal(ks[3], (256,), jnp.float32),   # rows 2 < 8
        "bf": jax.random.normal(ks[4], (32, 128), jnp.bfloat16),
        "bf_small": jax.random.normal(ks[5], (8, 128), jnp.bfloat16),
    }


def _grads(params, seed):
    return jax.tree.map(
        lambda p: (jnp.cos(p.astype(jnp.float32)) * (0.1 + 0.01 * seed)
                   ).astype(p.dtype), params)


def _run(tx, params, steps=3, jit=True):
    state = tx.init(params)
    update = jax.jit(tx.update) if jit else tx.update
    for i in range(steps):
        updates, state = update(_grads(params, i), state, params)
        params = optax.apply_updates(params, updates)
    return params, state


def _assert_tree_close(got, want, rtol_f32=2e-6, atol_f32=2e-7):
    for k in want:
        a = np.asarray(got[k], np.float32)
        b = np.asarray(want[k], np.float32)
        if jnp.dtype(want[k].dtype).itemsize == 2:
            # bf16 storage rounding dominates: ~2^-8 relative.
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=1e-2,
                                       err_msg=k)
        else:
            np.testing.assert_allclose(a, b, rtol=rtol_f32, atol=atol_f32,
                                       err_msg=k)


class TestEligibility:
    def test_gate(self):
        ok = jnp.zeros((16, 128), jnp.float32)
        assert fused_update_eligible(ok)
        # 130 % 128 != 0
        assert not fused_update_eligible(jnp.zeros((130,), jnp.float32))
        # 256 folds to 2 rows < the 8-row f32 sublane floor
        assert not fused_update_eligible(jnp.zeros((256,), jnp.float32))
        # bf16 floor is 16 rows: 8x128 folds to 8 rows
        assert not fused_update_eligible(jnp.zeros((8, 128), jnp.bfloat16))
        assert fused_update_eligible(jnp.zeros((16, 128), jnp.bfloat16))
        # a companion dtype tightens the floor (f32 leaf, bf16 moments)
        assert not fused_update_eligible(jnp.zeros((8, 128), jnp.float32),
                                         jnp.bfloat16)
        # non-float / sub-2-byte dtypes are ineligible
        assert not fused_update_eligible(jnp.zeros((16, 128), jnp.int32))
        assert not fused_update_eligible(jnp.zeros((32, 128), jnp.int8))

    def test_mixed_tree_routes_both_paths(self):
        p = _params()
        routed = {k: fused_update_eligible(v) for k, v in p.items()}
        assert routed["w"] and routed["deep"] and routed["bf"]
        assert not (routed["bias"] or routed["tiny"]
                    or routed["bf_small"])


class TestAdamParity:
    def test_matches_optax_adam(self):
        p = _params()
        got, gstate = _run(fused_adam(1e-3), p)
        want, wstate = _run(optax.adam(1e-3), p)
        _assert_tree_close(got, want)
        assert int(gstate.count) == 3

    def test_matches_optax_adamw(self):
        p = _params()
        got, _ = _run(fused_adam(1e-3, weight_decay=0.01), p)
        want, _ = _run(optax.adamw(1e-3, weight_decay=0.01), p)
        _assert_tree_close(got, want)

    def test_schedule_parity(self):
        sched = optax.exponential_decay(1e-3, 5, 0.7)
        p = _params()
        got, _ = _run(fused_adam(sched), p, steps=4)
        want, _ = _run(optax.adam(sched), p, steps=4)
        _assert_tree_close(got, want, rtol_f32=1e-5, atol_f32=1e-6)

    def test_moments_match_optax_state(self):
        p = _params()
        _, gstate = _run(fused_adam(1e-3), p, steps=2)
        _, wstate = _run(optax.adam(1e-3), p, steps=2)
        _assert_tree_close(gstate.mu, wstate[0].mu)
        _assert_tree_close(gstate.nu, wstate[0].nu)

    def test_unjitted_interpret_path(self):
        # The kernels must also run outside jit (pure eager interpret).
        p = {"w": jnp.ones((16, 128), jnp.float32)}
        got, _ = _run(fused_adam(1e-2), p, steps=1, jit=False)
        want, _ = _run(optax.adam(1e-2), p, steps=1, jit=False)
        _assert_tree_close(got, want)

    def test_weight_decay_requires_params(self):
        tx = fused_adam(1e-3, weight_decay=0.1)
        p = {"w": jnp.ones((16, 128), jnp.float32)}
        state = tx.init(p)
        with pytest.raises(ValueError, match="requires params"):
            tx.update(_grads(p, 0), state, None)

    def test_use_kernels_false_same_state_same_numbers(self):
        """The unfused A/B leg (use_kernels=False) must be numerically
        interchangeable AND state-compatible — the property the
        autotuner's fused dimension relies on to hot-swap mid-run."""
        p = _params()
        got, gstate = _run(fused_adam(1e-3), p)
        ref, rstate = _run(fused_adam(1e-3, use_kernels=False), p)
        _assert_tree_close(got, ref, rtol_f32=1e-6, atol_f32=1e-7)
        assert (jax.tree.structure(gstate) == jax.tree.structure(rstate))


class TestSgdParity:
    def test_momentum(self):
        p = _params()
        got, _ = _run(fused_sgd(0.01, momentum=0.9), p)
        want, _ = _run(optax.sgd(0.01, momentum=0.9), p)
        _assert_tree_close(got, want)

    def test_nesterov(self):
        p = _params()
        got, _ = _run(fused_sgd(0.01, momentum=0.9, nesterov=True), p)
        want, _ = _run(optax.sgd(0.01, momentum=0.9, nesterov=True), p)
        _assert_tree_close(got, want)

    def test_plain_sgd(self):
        p = _params()
        got, _ = _run(fused_sgd(0.05), p)
        want, _ = _run(optax.sgd(0.05), p)
        _assert_tree_close(got, want)

    def test_schedule_rejected(self):
        with pytest.raises(ValueError, match="float learning_rate"):
            fused_sgd(optax.constant_schedule(0.1), momentum=0.9)


class TestDistributedComposition:
    def test_distributed_fused_adam_matches_global_step(self, hvd, mesh8):
        """DistributedOptimizer(fused_adam) under dp8 shard_map ==
        fused_adam on the globally-averaged gradient."""
        opt = hvd.DistributedOptimizer(fused_adam(1e-2))
        params = {"w": jnp.zeros((16, 128), jnp.float32),
                  "b": jnp.zeros((130,), jnp.float32)}
        x = jnp.asarray(np.random.RandomState(0).randn(8, 16, 128),
                        jnp.float32)

        def grad_of(w_params, xs):
            def loss(p):
                return (jnp.mean((xs * p["w"]).astype(jnp.float32) ** 2)
                        + jnp.mean(p["b"] ** 2))
            return jax.grad(loss)(w_params)

        def per_shard(p, opt_state, xs):
            g = grad_of(p, xs[0])
            updates, opt_state = opt.update(g, opt_state, p)
            return optax.apply_updates(p, updates), opt_state

        opt_state = opt.init(params)
        # check_rep=False where the kwarg exists: pre-vma JAX has no
        # replication rule for pallas_call; on vma-tracking JAX the
        # kernels carry their own out-types (_vma_kw) and the kwarg is
        # gone or ignored.
        try:
            smapped = shard_map(per_shard, mesh=mesh8,
                                in_specs=(P(), P(), P("dp")),
                                out_specs=(P(), P()), check_rep=False)
        except TypeError:
            smapped = shard_map(per_shard, mesh=mesh8,
                                in_specs=(P(), P(), P("dp")),
                                out_specs=(P(), P()))
        stepped, _ = jax.jit(smapped)(params, opt_state, x)

        # Reference: plain fused_adam on the mean of per-shard grads.
        ref_tx = fused_adam(1e-2)
        ref_state = ref_tx.init(params)
        gs = [grad_of(params, x[i]) for i in range(8)]
        gmean = jax.tree.map(lambda *g: sum(g) / 8.0, *gs)
        updates, _ = ref_tx.update(gmean, ref_state, params)
        want = optax.apply_updates(params, updates)
        _assert_tree_close({"w": stepped["w"], "b": stepped["b"]},
                           {"w": want["w"], "b": want["b"]},
                           rtol_f32=1e-5, atol_f32=1e-6)


class TestStepPipeline:
    @pytest.fixture()
    def fresh_cache_state(self, monkeypatch):
        """No cache engaged and none named by the environment; JAX's own
        setting is put back afterwards so later tests' compiles don't
        land in a deleted tmp_path."""
        from horovod_tpu import step_pipeline as sp

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("HVDT_COMPILATION_CACHE", raising=False)
        monkeypatch.setattr(sp, "_engaged", None)
        yield sp
        jax.config.update("jax_compilation_cache_dir", before)

    def test_compilation_cache_knob(self, fresh_cache_state, monkeypatch,
                                    tmp_path):
        sp = fresh_cache_state
        cache = tmp_path / "xla-cache"
        monkeypatch.setenv("HVDT_COMPILATION_CACHE", str(cache))
        engaged = sp.enable_compilation_cache()
        assert engaged == str(cache)
        assert cache.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(cache)
        # Idempotent
        assert sp.enable_compilation_cache() == str(cache)

    def test_jax_env_var_wins_and_is_not_overwritten(
            self, fresh_cache_state, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR places the cache from outside: the
        knob, an explicit path and a root script's default all sit below
        it, and the program never writes jax_compilation_cache_dir."""
        sp = fresh_cache_state
        outside = tmp_path / "outside"
        # What JAX itself does at import when the variable is set.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
        jax.config.update("jax_compilation_cache_dir", str(outside))
        monkeypatch.setenv("HVDT_COMPILATION_CACHE", str(tmp_path / "knob"))
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real_update(k, v))[1])
        assert sp.enable_compilation_cache() == str(outside)
        assert sp.enable_compilation_cache(
            str(tmp_path / "arg"), default=str(tmp_path / "dflt")
        ) == str(outside)
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == str(outside)
        assert not (tmp_path / "knob").exists()

    def test_root_script_default_sits_below_the_knob(
            self, fresh_cache_state, monkeypatch, tmp_path):
        sp = fresh_cache_state
        dflt = tmp_path / "dflt"
        assert sp.enable_compilation_cache(default=str(dflt)) == str(dflt)
        monkeypatch.setattr(sp, "_engaged", None)
        monkeypatch.setenv("HVDT_COMPILATION_CACHE", str(tmp_path / "knob"))
        assert sp.enable_compilation_cache(default=str(dflt)) == str(
            tmp_path / "knob")
        monkeypatch.setattr(sp, "_engaged", None)
        monkeypatch.setenv("HVDT_COMPILATION_CACHE", "off")
        assert sp.enable_compilation_cache(default=str(dflt)) is None

    def test_disabled_by_default(self, fresh_cache_state):
        assert fresh_cache_state.enable_compilation_cache() is None

    def test_donated_step_runs_and_is_jitted(self, monkeypatch):
        from horovod_tpu.step_pipeline import donated_step

        monkeypatch.delenv("HVDT_COMPILATION_CACHE", raising=False)

        def step(params, opt_state, x):
            return jax.tree.map(lambda p: p - 0.1 * x.sum(), params), \
                opt_state, x.sum()

        params = {"w": jnp.ones((4,))}
        jitted = donated_step(step)
        p2, s2, loss = jitted(params, (), jnp.ones((2,)))
        np.testing.assert_allclose(np.asarray(p2["w"]),
                                   np.ones(4) - 0.2, rtol=1e-6)
        assert hasattr(jitted, "lower")   # still a jax.jit object


class TestAutotuneFusedDimension:
    def test_grid_gains_fused_column(self, monkeypatch):
        from horovod_tpu.autotune import ParameterManager

        pm = ParameterManager(tune_fused_optimizer=True)
        assert pm._bo.candidates.shape[1] == 3
        assert pm.tune_fused and pm.fused_optimizer is False
        pm2 = ParameterManager()
        assert pm2._bo.candidates.shape[1] == 2
        assert not pm2.tune_fused

    def test_fused_default_from_env(self, monkeypatch):
        from horovod_tpu.autotune import ParameterManager

        monkeypatch.setenv("HVDT_FUSED_OPTIMIZER", "1")
        pm = ParameterManager(tune_fused_optimizer=True)
        assert pm.fused_optimizer is True
        assert pm._current[2] == 1.0

    def test_autotuned_step_passes_fused_to_builder(self, monkeypatch):
        from horovod_tpu.autotune import autotuned_step

        monkeypatch.setenv("HVDT_AUTOTUNE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_FUSED_OPTIMIZER", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_WARMUP_SAMPLES", "0")
        monkeypatch.setenv("HVDT_AUTOTUNE_STEPS_PER_SAMPLE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", "4")

        calls = []

        def builder(threshold, fused=None):
            calls.append((threshold, fused))
            return lambda p, b: {"out": np.zeros(4)}

        step = autotuned_step(builder,
                              tree_example={"w": np.zeros(1024,
                                                          np.float32)})
        for _ in range(20):
            step({"w": np.zeros(4)}, 1)
        # Build 0 pins the env-default leg; every rebuild carries an
        # explicit fused bool from the tuner's current point.
        assert calls[0] == (None, False)
        assert len(calls) > 1
        assert all(isinstance(f, (bool, np.bool_)) for _, f in calls[1:])

    def test_builder_without_fused_kw_keeps_old_shape(self, monkeypatch):
        from horovod_tpu.autotune import autotuned_step

        monkeypatch.setenv("HVDT_AUTOTUNE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_FUSED_OPTIMIZER", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_WARMUP_SAMPLES", "0")
        monkeypatch.setenv("HVDT_AUTOTUNE_STEPS_PER_SAMPLE", "1")
        monkeypatch.setenv("HVDT_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", "3")

        calls = []

        def builder(threshold):
            calls.append(threshold)
            return lambda p, b: {"out": np.zeros(4)}

        step = autotuned_step(builder,
                              tree_example={"w": np.zeros(64, np.float32)})
        for _ in range(12):
            step({"w": np.zeros(4)}, 1)
        assert calls[0] is None
        assert all(c is None or isinstance(c, int) for c in calls)
