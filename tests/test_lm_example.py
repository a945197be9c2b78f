"""Smoke tests for the flagship LM example's CLI
(examples/jax_transformer_lm.py), which no benchmark cell runs.
Analog of the reference CI running its example scripts as smoke tests
(ref: .buildkite/gen-pipeline.sh:157-189)."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "jax_transformer_lm.py")
TOKS = re.compile(r"(\d+) tokens/sec, ~([\d.]+) model TFLOP/s")

TINY = ["--layers", "2", "--d-model", "64", "--heads", "4",
        "--d-ff", "128", "--vocab", "256", "--seq", "128",
        "--batch", "8", "--steps", "3"]


def _run(extra, env_extra=None, timeout=200):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.update(env_extra or {})
    out = subprocess.run([sys.executable, SCRIPT] + TINY + extra,
                         env=env, capture_output=True, text=True,
                         timeout=timeout, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    m = TOKS.search(out.stdout)
    assert m, f"no tokens/sec line in:\n{out.stdout[-1500:]}"
    return int(m.group(1))


@pytest.mark.integration
def test_meshless_single_device():
    assert _run(["--dp", "1", "--tp", "1"]) > 0


@pytest.mark.integration
def test_dp2_tp2_hybrid_with_remat_and_chunked_loss():
    assert _run(["--dp", "2", "--tp", "2", "--remat",
                 "--loss-chunk", "128"]) > 0


TOY_PUBLISHED = {
    "laguna_xs2": lambda published: dict(
        hidden_size=64, head_dim=32, num_key_value_heads=2,
        num_attention_heads_per_layer=[
            6 if h == 48 else 8
            for h in published["num_attention_heads_per_layer"]],
        sliding_window=16, intermediate_size=128, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, num_experts=16,
        num_experts_per_tok=2, vocab_size=512, experts=4, experts_first=4,
        vocab=256),
    "qwen3_next_80b": lambda published: dict(
        hidden_size=64, head_dim=32, num_attention_heads=4,
        num_key_value_heads=2, num_attention_heads_per_layer=[4] * 48,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts=16, num_experts_per_tok=3, vocab_size=512, experts=4,
        experts_first=4, vocab=256),
    "evabyte": lambda published: dict(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=8,
        intermediate_size=96, window_size=16, chunk_size=4,
        num_pred_heads=3, vocab_size=64, layers=2, heads=4, heads_first=4),
    "granite_4_0_h_micro": lambda published: dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=96, intermediate_size=96, mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16,
        vocab_size=512, vocab=128),
    "lfm2_24b_a2b": lambda published: dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, experts=4, vocab_size=512, vocab=128),
}


@pytest.mark.integration
@pytest.mark.parametrize("name", list(TOY_PUBLISHED))
def test_a_published_layer_pattern_model_by_the_same_path(tmp_path, name):
    """--published (the route the laguna-xs2 and qwen3-next presets take):
    the benchmark's configuration file at toy widths (Laguna-XS.2: five
    layers of three kinds; Qwen3-Next: three Gated DeltaNet layers and a
    gated full-attention one; 4 of 16 experts held; EvaByte: EVA attention
    over four windows with 4 of 8 heads held, three prediction heads;
    granite-4.0-h-micro: nine Mamba-2 layers and an attention layer without
    a position term; LFM2-24B-A2B: layers 1-5, short convolutions, a dense
    layer before four sparse ones routed by score plus bias), through the
    example's single-device step."""
    import json

    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        published = json.load(f)
    published.update(TOY_PUBLISHED[name](published))
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(published))
    out = subprocess.run(
        [sys.executable, SCRIPT, "--published", str(path), "--dp", "1",
         "--tp", "1", "--seq", "64", "--batch", "4", "--steps", "3",
         "--remat", "--loss-chunk", "96"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=200, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert TOKS.search(out.stdout) and "done." in out.stdout


def test_the_presets_of_published_models_name_the_benchmarks_files():
    sys.path.insert(0, os.path.dirname(SCRIPT))
    try:
        import jax_transformer_lm as example
    finally:
        sys.path.pop(0)
    for preset, name, seq, batch in (("laguna-xs2", "laguna_xs2", 8192, 2),
                                     ("qwen3-next", "qwen3_next_80b", 16384,
                                      1),
                                     ("evabyte", "evabyte", 32768, 1),
                                     ("granite_h_micro",
                                      "granite_4_0_h_micro", 8192, 1),
                                     ("lfm2_24b", "lfm2_24b_a2b", 8192, 2)):
        got = example.PRESETS[preset]
        assert os.path.samefile(got["published"], os.path.join(
            REPO, "benchmark", "configs", name + ".json"))
        assert (got["seq"], got["batch"]) == (seq, batch)
