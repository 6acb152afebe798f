"""Serving subsystem: paged KV pool, continuous batching, compiled step.

Covers the ISSUE-6 acceptance invariants: no page leaks and consistent
block tables across admit/evict churn, chunked prefill == whole-prompt
prefill bit-for-bit, the compiled (B, ctx)-bucketed decode step matching
the uncompiled ``decode_step`` token for token (two attention configs +
RWKV), grid conversion of the in-step attention, compilation-cache hits
across repeated shape buckets, and the env-configurable cache capacity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro import tracing
from repro.models.transformer import TransformerLM
from repro.pipeline.cache import (CACHE_SIZE_ENV, CompilationCache,
                                  _default_max_entries)
from repro.serving import KVPagePool, PageError, Scheduler


def _f32(cfg):
    """Serving math must match decode_step bit-for-bit; fp32 activations
    make argmax ties impossible to hit by rounding."""
    return dataclasses.replace(cfg, activation_dtype="float32")


def _model(arch: str, f32=True):
    cfg = get_config(arch).reduced()
    if f32:
        cfg = _f32(cfg)
    model = TransformerLM(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _reference_decode(model, params, prompts, new_tokens, max_model_len):
    """Greedy decode through jax.jit(decode_step) on a dense cache."""
    B = prompts.shape[0]
    cache = model.init_cache(B, max_model_len)
    step = jax.jit(model.decode_step)
    logits, cache = step(params, cache, jnp.asarray(prompts, jnp.int32))
    tokens = [[int(jnp.argmax(logits[b, -1]))] for b in range(B)]
    for _ in range(new_tokens - 1):
        toks = jnp.asarray([[t[-1]] for t in tokens], jnp.int32)
        logits, cache = step(params, cache, toks)
        for b in range(B):
            tokens[b].append(int(jnp.argmax(logits[b, 0])))
    return tokens, logits


# ---------------------------------------------------------------------------
# KVPagePool
# ---------------------------------------------------------------------------
class TestPagePool:
    def _pool(self, n_pages=8, page_size=4):
        return KVPagePool({0: (2, 8)}, n_pages, page_size)

    def test_null_page_never_allocated(self):
        pool = self._pool()
        pages = pool.alloc(pool.num_free, reserved=False)
        assert 0 not in pages
        assert len(pages) == pool.n_pages - 1

    def test_reserve_alloc_free_roundtrip(self):
        pool = self._pool()
        pool.reserve(3)
        assert pool.available == 7 - 3
        pages = pool.alloc(2)
        assert pool._reserved == 1
        pool.free(pages)
        pool.unreserve(1)
        assert pool.num_free == 7 and pool.available == 7

    def test_overcommit_rejected(self):
        pool = self._pool()
        pool.reserve(5)
        with pytest.raises(PageError):
            pool.reserve(3)
        with pytest.raises(PageError):
            pool.alloc(3, reserved=False)

    def test_double_free_and_null_free_rejected(self):
        pool = self._pool()
        (pg,) = pool.alloc(1, reserved=False)
        pool.free([pg])
        with pytest.raises(PageError):
            pool.free([pg])
        with pytest.raises(PageError):
            pool.free([0])

    def test_write_prefill_pads_to_page(self):
        pool = self._pool()
        pages = pool.alloc(2, reserved=False)
        k = jnp.ones((6, 2, 8), jnp.float32)  # 6 tokens over 2x4-slot pages
        kp, vp = pool.install_prefill(pool.k_pages, pool.v_pages,
                                      jnp.asarray(pages, jnp.int32),
                                      {0: (k, 2 * k)})
        got = kp[0][jnp.asarray(pages)].reshape(8, 2, 8)
        assert np.all(np.asarray(got[:6]) == 1.0)
        assert np.all(np.asarray(got[6:]) == 0.0)
        got = vp[0][jnp.asarray(pages)].reshape(8, 2, 8)
        assert np.all(np.asarray(got[:6]) == 2.0)
        assert np.all(np.asarray(got[6:]) == 0.0)

    def test_install_prefill_rejects_too_few_pages(self):
        pool = self._pool()
        k = jnp.ones((9, 2, 8), jnp.float32)  # 9 tokens, 2 pages hold 8
        with pytest.raises(PageError):
            pool.install_prefill(pool.k_pages, pool.v_pages,
                                 jnp.asarray([1, 2], jnp.int32), {0: (k, k)})


# ---------------------------------------------------------------------------
# Installing a prefill: one jitted call writes every layer's pages
# ---------------------------------------------------------------------------
def _bits(a) -> np.ndarray:
    a = np.ascontiguousarray(np.asarray(a))
    return a.view(np.uint8)


def _install_reference(model, cache, pages, slot, k_pages, v_pages, states,
                       page_size):
    """The per-layer install on the host: each attention layer's K/V
    padded to whole pages and set into ``pages``, each recurrent layer's
    entries set into row ``slot`` of its state arrays."""
    k_pages = {li: np.array(a) for li, a in k_pages.items()}
    v_pages = {li: np.array(a) for li, a in v_pages.items()}
    states = {name: np.array(a) for name, a in states.items()}
    layers = [{key: np.asarray(a[pp]) for key, a in cache["body"][pi].items()}
              for pp in range(model.n_periods)
              for pi in range(len(model.period_specs))]
    layers += [{key: np.asarray(a) for key, a in c.items()}
               for c in cache["tail"]]
    for li, layer in enumerate(layers):
        if "k" in layer:
            for key, dst in (("k", k_pages), ("v", v_pages)):
                a = layer[key][0]
                slab = np.zeros((len(pages) * page_size,) + a.shape[1:],
                                dst[li].dtype)
                slab[:len(a)] = a
                dst[li][pages] = slab.reshape(len(pages), page_size,
                                              *a.shape[1:])
        else:
            for key, a in layer.items():
                states[f"st{li}__{key}"][slot] = a[0]
    return k_pages, v_pages, states


def _random_like(tree, seed):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        a if not jnp.issubdtype(a.dtype, jnp.floating)
        else jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


class TestPrefillInstall:
    @pytest.mark.parametrize("arch", ["starcoder2-3b", "rwkv6-7b"])
    def test_install_matches_per_layer_reference(self, arch):
        """Random prior pages and state rows, a random prefill cache of 11
        tokens over 3 pages of 4: the named pages hold the cache with zero
        pad rows, the named slot holds the states, and every other page
        and row keeps its bits."""
        model, params = _model(arch, f32=False)
        sched = Scheduler(model, params, max_slots=4, page_size=4,
                          n_pages=16, max_model_len=32,
                          compile_cache=CompilationCache())
        L, pages, slot = 11, [5, 2, 9], 2
        sched.pool.k_pages = _random_like(sched.pool.k_pages, 1)
        sched.pool.v_pages = _random_like(sched.pool.v_pages, 2)
        sched.states = _random_like(sched.states, 3)
        cache = _random_like(model.init_cache(1, L, dtype=sched.pool.dtype),
                             4)
        ref = _install_reference(model, cache, pages, slot,
                                 sched.pool.k_pages, sched.pool.v_pages,
                                 sched.states, sched.page_size)
        got = sched._install(cache, np.asarray(pages, np.int32),
                             np.int32(slot), sched.pool.k_pages,
                             sched.pool.v_pages, sched.states)
        for want, have in zip(ref, got):
            assert set(want) == set(have)
            for key in want:
                np.testing.assert_array_equal(_bits(have[key]),
                                              _bits(want[key]), err_msg=key)
        k_pages, v_pages, states = got
        if arch == "rwkv6-7b":
            assert not k_pages and states
        else:
            assert len(k_pages) == model.cfg.n_layers and not states
            for pg in (k_pages, v_pages):
                for a in pg.values():
                    tail = np.asarray(a[pages[-1]], np.float32)
                    assert np.all(tail[L - 2 * 4:] == 0.0)  # pad rows
                    assert np.any(tail[:L - 2 * 4] != 0.0)

    def test_two_prompts_of_one_length_compile_the_install_once(self):
        model, params = _model("starcoder2-3b", f32=False)
        sched = Scheduler(model, params, max_slots=4, page_size=8,
                          n_pages=32, max_model_len=64, prefill_chunk=4,
                          compile_cache=CompilationCache())
        sched.submit([3, 1, 4, 1, 5], 3)
        sched.step()
        sched.submit([2, 7, 1, 8, 2], 3)
        sched.step()
        assert sched._install._cache_size() == 1
        sched.submit([1, 4, 1], 3)
        sched.run()
        assert sched._install._cache_size() == 2

    def test_one_scatter_call_per_admission(self):
        model, params = _model("starcoder2-3b", f32=False)
        sched = Scheduler(model, params, max_slots=4, page_size=8,
                          n_pages=32, max_model_len=64, prefill_chunk=4,
                          compile_cache=CompilationCache())
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5], [2, 7], [1, 8, 2, 8]]
        rec = tracing.enable()
        try:
            for p in prompts:
                sched.submit(p, 3)
            sched.run()
        finally:
            tracing.disable()
        calls = rec.counts("sched.scatter_calls")
        assert len(calls) == len(rec.spans("admit")) == len(prompts)
        assert all(c.value == 1 and c.span.name == "scatter" for c in calls)
        assert [c.attrs["pages"] for c in calls] == [2, 1, 1]
        assert all(c.attrs["layers"] == model.cfg.n_layers for c in calls)

    @pytest.mark.parametrize("donate", [True, False])
    def test_install_donates_when_the_step_does(self, donate):
        model, params = _model("starcoder2-3b", f32=False)
        sched = Scheduler(model, params, max_slots=4, page_size=8,
                          n_pages=32, max_model_len=64, prefill_chunk=4,
                          compile_cache=CompilationCache(), donate=donate)
        assert sched.compiler.donate is donate
        before = (list(sched.pool.k_pages.values())
                  + list(sched.pool.v_pages.values()))
        sched.submit([3, 1, 4, 1, 5], 3)
        sched._try_admit()
        assert all(a.is_deleted() is donate for a in before)
        assert not any(a.is_deleted() for a in sched.pool.k_pages.values())
        sched.run()
        sched.check_invariants()


# ---------------------------------------------------------------------------
# Scheduler invariants under churn
# ---------------------------------------------------------------------------
class TestSchedulerInvariants:
    def test_admit_evict_no_leaks(self):
        model, params = _model("starcoder2-3b", f32=False)
        sched = Scheduler(model, params, max_slots=3, page_size=8,
                          n_pages=24, max_model_len=64, prefill_chunk=4,
                          compile_cache=CompilationCache())
        rng = np.random.RandomState(0)
        for i in range(7):
            L = int(rng.randint(2, 14))
            sched.submit(list(rng.randint(0, model.cfg.vocab, size=L)),
                         int(rng.randint(2, 9)))
            if i % 2 == 0:
                sched.step()
                sched.check_invariants()
        reqs = sched.run()
        sched.check_invariants()
        assert len(reqs) == 7
        assert all(r.done for r in reqs)
        # every page returned, every reservation released
        assert sched.pool.num_free == sched.pool.n_pages - 1
        assert sched.pool._reserved == 0
        assert not np.any(sched.block_table)

    def test_queue_waits_for_pages(self):
        model, params = _model("starcoder2-3b", f32=False)
        # room for exactly one request's worst case at a time
        sched = Scheduler(model, params, max_slots=2, page_size=8,
                          n_pages=4, max_model_len=32, prefill_chunk=8,
                          compile_cache=CompilationCache())
        for _ in range(2):
            sched.submit(list(range(1, 9)), 8)  # 8+8 tokens -> 2 pages
        sched.step()
        assert sum(r is not None for r in sched.slots) == 1
        assert len(sched.queue) == 1
        reqs = sched.run()
        assert len(reqs) == 2
        sched.check_invariants()


# ---------------------------------------------------------------------------
# Chunked prefill == whole-prompt prefill
# ---------------------------------------------------------------------------
class TestChunkedPrefill:
    @pytest.mark.parametrize("chunk", [1, 3, 8])
    def test_chunked_matches_whole(self, chunk):
        model, params = _model("gemma3-4b")
        prompt = np.arange(1, 12) % model.cfg.vocab
        L = len(prompt)
        step = jax.jit(model.decode_step)

        whole_cache = model.init_cache(1, L)
        whole_logits, whole_cache = step(
            params, whole_cache, jnp.asarray(prompt[None], jnp.int32))

        cache = model.init_cache(1, L)
        logits = None
        i = 0
        while i < L:
            logits, cache = step(
                params, cache,
                jnp.asarray(prompt[None, i:i + chunk], jnp.int32))
            i += chunk

        # XLA CPU selects different matmul kernels for (s=L) vs (s=chunk)
        # activations, so equality across chunkings is to rounding, not
        # bit-for-bit; the sampled token must still be identical.
        wl, cl = np.asarray(whole_logits[0, -1]), np.asarray(logits[0, -1])
        np.testing.assert_allclose(wl, cl, rtol=2e-6, atol=2e-6)
        assert int(wl.argmax()) == int(cl.argmax())
        for leaf_w, leaf_c in zip(jax.tree.leaves(whole_cache),
                                  jax.tree.leaves(cache)):
            np.testing.assert_allclose(
                np.asarray(leaf_w, np.float32),
                np.asarray(leaf_c, np.float32), rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# Compiled step == uncompiled decode_step
# ---------------------------------------------------------------------------
class TestCompiledStep:
    @pytest.mark.parametrize("arch", ["starcoder2-3b", "gemma3-4b",
                                      "rwkv6-7b"])
    def test_tokens_match_reference(self, arch):
        model, params = _model(arch)
        B, L, new = 4, 6, 5
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (B, L), 0, model.cfg.vocab))
        ref_tokens, _ = _reference_decode(model, params, prompts, new, 64)

        sched = Scheduler(model, params, max_slots=4, page_size=8,
                          n_pages=32, max_model_len=64, prefill_chunk=4,
                          compile_cache=CompilationCache())
        for b in range(B):
            sched.submit(list(map(int, prompts[b])), new)
        reqs = sched.run()
        sched.check_invariants()
        for b, r in enumerate(reqs):
            assert r.tokens_out == ref_tokens[b], (
                f"slot {b}: {r.tokens_out} != reference {ref_tokens[b]}")

    def test_grid_kernel_in_compiled_step(self):
        """At a grid-converting bucket the per-layer attention maps become
        Pallas grid kernels inside the compiled step (dtype-aware tiling:
        fp32 -> 8-row sublane blocks, so B=16 yields >= 2 grid steps)."""
        model, params = _model("starcoder2-3b")
        B, L, new = 16, 6, 4
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (B, L), 0, model.cfg.vocab))
        ref_tokens, _ = _reference_decode(model, params, prompts, new, 64)

        sched = Scheduler(model, params, max_slots=B, page_size=8,
                          n_pages=64, max_model_len=64, prefill_chunk=8,
                          dtype_aware_sublanes=True,
                          compile_cache=CompilationCache())
        for b in range(B):
            sched.submit(list(map(int, prompts[b])), new)
        reqs = sched.run()
        for b, r in enumerate(reqs):
            assert r.tokens_out == ref_tokens[b]

        report = sched.compiler._steps[max(sched.compiler._steps)].report
        kernels = report.get("grid_kernels", [])
        assert len(kernels) == model.cfg.n_layers
        assert all("attn" in k for k in kernels)
        blocks = report["grid_converted"][0]["block_shape"]
        assert blocks[0] == 8  # fp32 sublane rows

    def test_padding_lanes_do_not_disturb_active(self):
        """A batch of 3 in 4 slots runs with one padding lane (null-page
        writes + masked gathers); results must equal the dense 3-lane
        reference."""
        model, params = _model("starcoder2-3b")
        B, L, new = 3, 5, 4
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (B, L), 0, model.cfg.vocab))
        ref_tokens, _ = _reference_decode(model, params, prompts, new, 64)
        sched = Scheduler(model, params, max_slots=4, page_size=8,
                          n_pages=32, max_model_len=64, prefill_chunk=4,
                          compile_cache=CompilationCache())
        for b in range(B):
            sched.submit(list(map(int, prompts[b])), new)
        reqs = sched.run()
        for b, r in enumerate(reqs):
            assert r.tokens_out == ref_tokens[b]


# ---------------------------------------------------------------------------
# Sampling beyond greedy argmax
# ---------------------------------------------------------------------------
class TestSampling:
    def test_invalid_sampling_args_rejected(self):
        model, params = _model("starcoder2-3b", f32=False)
        with pytest.raises(ValueError):
            Scheduler(model, params, temperature=-0.1)
        with pytest.raises(ValueError):
            Scheduler(model, params, top_k=0)

    def test_sample_respects_temperature_and_top_k(self):
        model, params = _model("starcoder2-3b", f32=False)
        row = np.asarray([0.0, 3.0, 2.5, -1.0, 2.9], np.float32)
        greedy = Scheduler(model, params, compile_cache=CompilationCache())
        assert greedy._sample(row) == 1  # temperature 0 == argmax
        sched = Scheduler(model, params, temperature=1.0, top_k=2, seed=11,
                          compile_cache=CompilationCache())
        draws = {sched._sample(row) for _ in range(200)}
        assert draws <= {1, 4}  # support truncated to the top-2 logits
        assert draws == {1, 4}  # both survivors actually drawn

    def test_seeded_sampling_deterministic(self):
        """Same seed -> identical token streams through the full
        scheduler (prefill sample + batched decode samples); greedy
        remains the temperature=0 default."""
        model, params = _model("starcoder2-3b", f32=False)
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(1, model.cfg.vocab, size=6))
                   for _ in range(3)]

        def decode(seed):
            sched = Scheduler(model, params, max_slots=3, page_size=8,
                              n_pages=24, max_model_len=64, prefill_chunk=4,
                              compile_cache=CompilationCache(),
                              temperature=0.8, top_k=8, seed=seed)
            for p in prompts:
                sched.submit(p, 6)
            return [r.tokens_out for r in sched.run()]

        first = decode(seed=3)
        assert first == decode(seed=3)
        assert any(len(set(t)) > 1 for t in first)  # it did sample tokens


# ---------------------------------------------------------------------------
# Compilation-cache behavior
# ---------------------------------------------------------------------------
class TestServingCompileCache:
    def test_bucket_reuse_hits_cache(self):
        model, params = _model("starcoder2-3b", f32=False)
        cc = CompilationCache()

        def run_once():
            sched = Scheduler(model, params, max_slots=3, page_size=8,
                              n_pages=24, max_model_len=64,
                              prefill_chunk=4, compile_cache=cc)
            for _ in range(3):
                sched.submit(list(range(1, 6)), 4)
            sched.run()

        run_once()
        first = dict(cc.stats)
        assert first["misses"] >= 1
        run_once()  # identical workload -> identical (B, ctx) buckets
        second = cc.stats
        assert second["misses"] == first["misses"]
        assert second["hits"] == first["hits"] + first["misses"]

    def test_env_var_configures_capacity(self, monkeypatch):
        monkeypatch.setenv(CACHE_SIZE_ENV, "2")
        assert _default_max_entries() == 2
        cc = CompilationCache()
        assert cc.max_entries == 2
        for i in range(4):
            cc.store(i, i)
        assert len(cc) == 2
        # explicit argument wins over the env var
        assert CompilationCache(max_entries=7).max_entries == 7

    def test_env_var_rejects_garbage(self, monkeypatch):
        monkeypatch.setenv(CACHE_SIZE_ENV, "zero")
        with pytest.raises(ValueError):
            CompilationCache()
        monkeypatch.setenv(CACHE_SIZE_ENV, "0")
        with pytest.raises(ValueError):
            CompilationCache()
