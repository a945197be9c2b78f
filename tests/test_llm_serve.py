"""Continuous-batching LLM serving tests: paged KV allocator invariants
(property-tested), paged-decode correctness against the dense reference,
zero-steady-state-recompile contract, synthetic multi-tenant traffic with
forced evictions and exact block accounting, copy-on-write prefix
sharing, ring-attention prefill lowering, the empty-Summary percentile
contract, and the continuous engine behind the HTTP front end.  All CPU,
in-process, `not slow` — this module is part of the smoke tier
(ci/gen-matrix.sh --smoke).
"""

import http.client
import json
import random
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (TransformerConfig,
                                            transformer_apply,
                                            transformer_init)
from horovod_tpu.serve import MetricsRegistry, ModelServer
from horovod_tpu.serve.batcher import RequestDeadlineExceeded
from horovod_tpu.serve.llm import (ContinuousLLMEngine, PagedKVAllocator,
                                   SINK_BLOCK, Sequence)

CFG = TransformerConfig(vocab=64, layers=2, d_model=32, heads=4,
                        kv_heads=2, d_ff=64, max_seq=128,
                        dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return transformer_init(jax.random.PRNGKey(0), CFG)


@jax.jit
def _dense_logits(params, toks_padded):
    return transformer_apply(params, toks_padded, CFG)


def _dense_greedy(params, prompt, max_new):
    """Reference decode: full forward per token, padded to a FIXED length
    so the whole module shares one XLA program (causal attention makes
    the trailing zero-padding invisible to earlier positions)."""
    toks = list(prompt)
    padded = np.zeros((1, CFG.max_seq), np.int32)
    for _ in range(max_new):
        padded[0, :len(toks)] = toks
        logits = _dense_logits(params, padded)
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


def _drain(eng, futs, max_iters=5000):
    n = 0
    while not all(f.done() for f in futs):
        eng.step()
        n += 1
        assert n < max_iters, "engine failed to converge"
    return n


# ---------------------------------------------------------------------------
# Paged KV allocator
# ---------------------------------------------------------------------------

class TestPagedKVAllocator:
    def test_allocate_all_or_nothing(self):
        a = PagedKVAllocator(num_blocks=5, block_size=4)    # capacity 4
        t1 = a.allocate(16)                                 # 4 blocks
        assert t1 is not None and len(t1) == 4
        assert SINK_BLOCK not in t1
        assert a.allocate(1) is None                        # budget exhausted
        assert a.used_blocks == 4                           # no partial grab
        a.free(t1)
        a.check()
        assert a.used_blocks == 0

    def test_append_token_grows_at_boundary(self):
        a = PagedKVAllocator(num_blocks=8, block_size=4)
        t = a.allocate(4)                                   # exactly 1 block
        assert len(t) == 1
        assert a.append_token(t, 3) == []                   # inside block
        assert len(t) == 1
        copies = a.append_token(t, 4)                       # crosses boundary
        assert copies == [] and len(t) == 2
        a.free(t)
        a.check()

    def test_fork_and_cow(self):
        a = PagedKVAllocator(num_blocks=8, block_size=4)
        parent = a.allocate(8)                              # 2 blocks
        child = a.fork(parent)
        assert child == parent and child is not parent
        assert a.used_blocks == 2                           # shared, not copied
        # Child writes into the shared last block -> CoW copy.
        copies = a.append_token(child, 5)
        assert len(copies) == 1
        src, dst = copies[0]
        assert src == parent[1] and dst == child[1]
        assert child[1] != parent[1]
        assert a.cow_copies == 1
        a.free(parent)
        a.free(child)
        a.check()
        assert a.used_blocks == 0

    def test_double_free_raises(self):
        a = PagedKVAllocator(num_blocks=4, block_size=2)
        t = a.allocate(2)
        held = list(t)
        a.free(t)
        with pytest.raises(RuntimeError):
            a.free(held)

    def test_property_random_trace_no_leak_no_double_free(self):
        """Random admit/append/fork/evict trace: the audit invariant
        (allocated == freed + in_use, free list consistent) must hold
        after EVERY operation, and draining must return to zero."""
        rng = random.Random(1234)
        a = PagedKVAllocator(num_blocks=24, block_size=4)
        live = []        # (table, n_tokens)
        for _ in range(600):
            op = rng.random()
            if op < 0.40 or not live:
                t = a.allocate(rng.randint(1, 20))
                if t is not None:
                    live.append((t, 0))
            elif op < 0.70:
                i = rng.randrange(len(live))
                t, n = live[i]
                pos = len(t) * a.block_size - rng.randint(0, a.block_size - 1)
                got = a.append_token(t, max(pos, 0))
                if got is None:
                    a.free(t)                       # evict under pressure
                    live.pop(i)
                else:
                    live[i] = (t, n + 1)
            elif op < 0.85:
                t, n = live[rng.randrange(len(live))]
                live.append((a.fork(t), n))
            else:
                t, _ = live.pop(rng.randrange(len(live)))
                a.free(t)
            a.check()
        for t, _ in live:
            a.free(t)
        a.check()
        assert a.used_blocks == 0
        assert a.blocks_allocated == a.blocks_freed
        assert a.blocks_allocated > 0


# ---------------------------------------------------------------------------
# Engine correctness + compile contract
# ---------------------------------------------------------------------------

class TestContinuousEngine:
    def test_matches_dense_greedy(self, params):
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=4, num_blocks=64,
                                  block_size=8, seq_blocks=16,
                                  prefill_chunk=16)
        eng.warmup()
        rng = np.random.default_rng(7)
        prompts = [[int(t) for t in rng.integers(1, CFG.vocab, size=n)]
                   for n in (2, 9, 23, 40)]
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        _drain(eng, futs)
        for p, f in zip(prompts, futs):
            assert f.result() == _dense_greedy(params, p, 6)
        eng.alloc.check()
        assert eng.alloc.used_blocks == 0

    def test_zero_steady_state_recompiles(self, params):
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=4, num_blocks=64,
                                  block_size=8, seq_blocks=16,
                                  prefill_chunk=16)
        eng.warmup()
        baseline = eng.compile_count()
        rng = np.random.default_rng(3)
        futs = [eng.submit([int(t) for t in rng.integers(1, CFG.vocab,
                                                         size=n)],
                           max_new_tokens=5)
                for n in (3, 17, 33, 8, 25, 12)]
        _drain(eng, futs)
        assert eng.compile_count() == baseline, \
            "steady-state traffic must never trigger a new XLA compile"

    def test_deadline_expiry_fails_future(self, params):
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=2, num_blocks=32,
                                  block_size=8, seq_blocks=8)
        eng.warmup()
        fut = eng.submit([1, 2, 3], max_new_tokens=4, deadline_s=0.01)
        time.sleep(0.05)
        eng.step()
        with pytest.raises(RequestDeadlineExceeded):
            fut.result(timeout=5)
        assert eng.metrics.counter(
            "serve_deadline_expired_total").value() >= 1


# ---------------------------------------------------------------------------
# Synthetic multi-tenant traffic
# ---------------------------------------------------------------------------

class TestSyntheticTraffic:
    def test_mixed_tenants_forced_evictions_exact_accounting(self, params):
        # Tiny budget: 12 usable blocks of 8 tokens for up to 6 resident
        # sequences -> admission must evict and recompute to finish.
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=4, num_blocks=13,
                                  block_size=8, seq_blocks=8,
                                  prefill_chunk=16, batch_quota=0.5)
        eng.warmup()
        baseline = eng.compile_count()
        rng = np.random.default_rng(11)
        futs, prompts, tenants = [], [], []
        for i in range(10):
            n = int(rng.integers(2, 40))
            p = [int(t) for t in rng.integers(1, CFG.vocab, size=n)]
            tenant = "interactive" if i % 3 == 0 else "batch"
            prompts.append(p)
            tenants.append(tenant)
            futs.append(eng.submit(p, max_new_tokens=8, tenant=tenant))
        _drain(eng, futs)

        for p, f in zip(prompts, futs):
            out = f.result()
            assert len(out) == 8
            assert out == _dense_greedy(params, p, 8), \
                "eviction + recompute must not change the decoded tokens"
        # Exact accounting across every admit/evict/fork/finish.
        eng.alloc.check()
        assert eng.alloc.used_blocks == 0
        assert eng.alloc.blocks_allocated == eng.alloc.blocks_freed
        assert eng.sched.preemptions >= 1, \
            "this budget is sized to force at least one eviction"
        assert eng.compile_count() == baseline
        # Tenant plumbing: both classes admitted, waits observed, and the
        # adaptive batch quota stayed inside [1, decode_slots].
        assert eng.sched.admissions["interactive"] >= 1
        assert eng.sched.admissions["batch"] >= 1
        w = eng.metrics.summary("hvdt_engine_wait_ms_interactive")
        assert w.percentile(0.99) >= 0.0 and w.quantile(0.99) is not None
        assert 1 <= eng.sched.batch_quota_slots() <= eng.decode_slots

    def test_batch_quota_work_conserving(self, params):
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=4, num_blocks=64,
                                  block_size=8, seq_blocks=8,
                                  batch_quota=0.5)
        eng.warmup()
        # Zero interactive demand -> batch may take every slot.
        assert eng.sched.batch_quota_slots() == eng.decode_slots
        futs = [eng.submit([1, 2, 3, 4], max_new_tokens=4, tenant="batch")
                for _ in range(4)]
        _drain(eng, futs)
        assert all(len(f.result()) == 4 for f in futs)


# ---------------------------------------------------------------------------
# Prefix sharing (CoW fork on identical live prompt)
# ---------------------------------------------------------------------------

class TestPagedKeysAreTrainingsKeys:
    def test_prefill_and_decode_write_the_keys_training_computes(
            self, params):
        """The paged cache holds keys rotated exactly as training rotates
        them: what ``transformer_prefill_paged`` scatters for a prompt's
        tokens, and what ``transformer_decode_paged`` appends for the
        next token at its position, are ``_qkv_gate``'s k and v of the
        training forward at the same positions, layer by layer."""
        from horovod_tpu.models import transformer as tfm

        n, block, maxb = 11, 4, 4
        tokens = np.asarray(
            jax.random.randint(jax.random.PRNGKey(3), (n,), 0, CFG.vocab))
        positions = jnp.arange(n)[None]

        def training(params):           # every layer's k, v: [L, n, Hkv, D]
            x = params["embed"].astype(CFG.dtype)[tokens][None]

            def layer(h, p):
                _, k, v, _ = tfm._qkv_gate(
                    p, tfm._rmsnorm(h, p["ln1"]), positions, CFG)
                return tfm._block(p, h, positions, CFG), (k[0], v[0])
            return jax.lax.scan(layer, x, params["block"])[1]

        k_want, v_want = jax.jit(training)(params)
        table = jnp.arange(1, maxb + 1, dtype=jnp.int32)
        cache = jnp.zeros((CFG.layers, maxb + 1, block, CFG.kv_heads,
                           CFG.head_dim), CFG.dtype)
        chunk = np.zeros(12, np.int32)
        chunk[:n - 1] = tokens[:n - 1]
        kc, vc = jax.jit(lambda *a: tfm.transformer_prefill_paged(
            *a, CFG, block))(params, jnp.asarray(chunk), jnp.int32(0),
                             jnp.int32(n - 1), table, cache, cache)
        _, kc, vc = jax.jit(lambda *a: tfm.transformer_decode_paged(
            *a, CFG, block))(params, jnp.asarray(tokens[-1:]), table[None],
                             jnp.asarray([n], jnp.int32), kc, vc)
        for got, want in ((kc, k_want), (vc, v_want)):
            rows = got[:, 1:].reshape(CFG.layers, maxb * block,
                                      CFG.kv_heads, CFG.head_dim)
            np.testing.assert_allclose(rows[:, :n], want, rtol=1e-5,
                                       atol=1e-6)
            assert not np.asarray(rows[:, n:]).any()    # nothing past it
        # layer 0 sees the same input on all three paths: one function,
        # to the rounding of a projection at another batch shape
        np.testing.assert_allclose(
            kc[0, 1:].reshape(-1, CFG.kv_heads, CFG.head_dim)[:n], k_want[0],
            rtol=2e-6, atol=1e-6)


class TestPrefixSharing:
    def test_duplicate_prompt_forks_blocks(self, params):
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=4, num_blocks=64,
                                  block_size=8, seq_blocks=8,
                                  prefill_chunk=64)
        eng.warmup()
        prompt = [int(t) for t in
                  np.random.default_rng(5).integers(1, CFG.vocab, size=30)]
        f1 = eng.submit(prompt, max_new_tokens=10)
        # Step until the parent is fully prefilled and decoding, THEN
        # submit the identical prompt — admission must fork its table.
        for _ in range(50):
            eng.step()
            seqs = list(eng.sched.admitted)
            if seqs and seqs[0].decode_ready:
                break
        f2 = eng.submit(list(prompt), max_new_tokens=10)
        _drain(eng, [f1, f2])
        assert eng.sched.prefix_hits == 1
        assert eng.alloc.cow_copies >= 1, \
            "the fork's first decode write must copy-on-write"
        assert f1.result() == f2.result() == _dense_greedy(params, prompt,
                                                           10)
        eng.alloc.check()
        assert eng.alloc.used_blocks == 0


# ---------------------------------------------------------------------------
# Ring-attention prefill (8 simulated devices via conftest)
# ---------------------------------------------------------------------------

class TestRingPrefill:
    def test_ring_prefill_lowers_to_collective_permute(self, params,
                                                       devices):
        if len(devices) < 4:
            pytest.skip("needs >= 4 devices")
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=2, num_blocks=40,
                                  block_size=8, seq_blocks=16,
                                  ring_prefill=4)
        assert eng.ring_enabled()
        eng._build_ring()
        toks = np.zeros((1, eng.max_context), np.int32)
        hlo = eng._jits["ring_prefill"].lower(
            eng._packed, toks).compile().as_text()
        assert ("collective-permute" in hlo
                or "collective_permute" in hlo), \
            "ring prefill must lower to the ring_attention collective"

    def test_ring_prefill_matches_dense(self, params, devices):
        if len(devices) < 4:
            pytest.skip("needs >= 4 devices")
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=2, num_blocks=40,
                                  block_size=8, seq_blocks=16,
                                  ring_prefill=4)
        eng.warmup()
        # Long prompt (>= max_context // 2 = 64) -> the whole-prompt ring
        # path, not chunk streaming.
        prompt = [int(t) for t in
                  np.random.default_rng(9).integers(1, CFG.vocab, size=80)]
        seen = []
        orig = eng._run_ring_prefill
        eng._run_ring_prefill = lambda s: (seen.append(s), orig(s))[1]
        fut = eng.submit(prompt, max_new_tokens=4)
        _drain(eng, [fut])
        assert seen, "long prompt must take the ring prefill path"
        assert fut.result() == _dense_greedy(params, prompt, 4)


# ---------------------------------------------------------------------------
# Summary.percentile contract (satellite: empty ring -> 0.0, not crash)
# ---------------------------------------------------------------------------

class TestSummaryPercentile:
    def test_empty_percentile_zero_quantile_none(self):
        s = MetricsRegistry().summary("hvdt_engine_decode_step_seconds",
                                      "d")
        assert s.percentile(0.5) == 0.0
        assert s.percentile(0.99) == 0.0
        assert s.quantile(0.5) is None          # router's contract intact
        s.observe(2.0)
        s.observe(4.0)
        assert s.percentile(0.99) == s.quantile(0.99) == 4.0


# ---------------------------------------------------------------------------
# HTTP front end with the continuous engine
# ---------------------------------------------------------------------------

class TestServerContinuous:
    def test_predict_healthz_metrics(self, params):
        eng = ContinuousLLMEngine(params, CFG, auto_start=False,
                                  decode_slots=4, num_blocks=64,
                                  block_size=8, seq_blocks=8)
        eng.warmup()
        server = ModelServer(eng, port=0)
        assert server.continuous and server.batcher is None
        port = server.start()
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                if not eng.step():
                    time.sleep(0.002)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            body = json.dumps({"inputs": [[1, 2, 3], [4, 5, 6, 7]],
                               "max_new_tokens": 4})
            conn.request("POST", "/predict", body,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            doc = json.loads(r.read())
            conn.close()
            assert r.status == 200
            assert len(doc["outputs"]) == 2
            assert all(len(row) == 4 for row in doc["outputs"])

            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("GET", "/healthz")
            health = json.loads(conn.getresponse().read())
            conn.close()
            assert health["engine"] == "continuous"

            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
            conn.close()
            assert "hvdt_engine_tokens_per_sec" in text
            assert "hvdt_engine_kv_blocks_in_use" in text
        finally:
            stop.set()
            t.join(timeout=5)
            server.stop()
