"""Continuous-batching engine (serve/engine.py).

The engine is a serving redesign of the scanned generate() path — the
non-negotiable property is EQUIVALENCE: whatever order requests are
admitted, interleaved, and retired in, each one's greedy tokens must match
a solo ``generate`` run of the same prompt. Reference analog: none (the
reference leaves batching to user handlers) — this is the beyond-parity
serving subsystem, so the contract is defined entirely by these tests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.models.generate import generate
from kubetorch_tpu.models.llama import LlamaConfig, llama_init
from kubetorch_tpu.serve import GenerationEngine

pytestmark = [pytest.mark.level("unit"), pytest.mark.slow]


@pytest.fixture(scope="module")
def dense():
    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _reference_tokens(params, cfg, prompt, n):
    out = generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


class TestEquivalence:
    def test_single_request_matches_generate(self, dense):
        params, cfg = dense
        prompt = [5, 17, 42, 99]
        want = _reference_tokens(params, cfg, prompt, 8)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 16))
        got = eng.submit(prompt, max_new_tokens=8)
        while eng.step():
            pass
        assert got.result(timeout=0) == want

    def test_concurrent_requests_each_match_solo_runs(self, dense):
        """Three prompts of different lengths share the grid; interleaved
        decode must not cross-contaminate slots."""
        params, cfg = dense
        prompts = [[7, 8, 9], [100, 200, 300, 400, 401], [1, 2]]
        ns = [6, 9, 4]
        want = [_reference_tokens(params, cfg, p, n)
                for p, n in zip(prompts, ns)]
        eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                               prefill_buckets=(8,))
        handles = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, ns)]
        while eng.step():
            pass
        for h, w in zip(handles, want):
            assert h.result(timeout=0) == w

    def test_mid_flight_admission(self, dense):
        """A request admitted while another is mid-decode (the continuous
        part of continuous batching) still matches its solo run — and the
        early request's tokens are unchanged by the newcomer."""
        params, cfg = dense
        p1, p2 = [11, 12, 13, 14], [250, 251]
        want1 = _reference_tokens(params, cfg, p1, 10)
        want2 = _reference_tokens(params, cfg, p2, 5)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 8))
        h1 = eng.submit(p1, max_new_tokens=10)
        for _ in range(3):               # p1 decodes alone for a while
            eng.step()
        h2 = eng.submit(p2, max_new_tokens=5)
        while eng.step():
            pass
        assert h1.result(timeout=0) == want1
        assert h2.result(timeout=0) == want2

    def test_slot_reuse_after_retirement(self, dense):
        """A retired slot's stale cache rows must never leak into the next
        occupant (rows are only ever read at positions the new request has
        itself written)."""
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        pa, pb = [31, 32, 33], [77]
        wa = _reference_tokens(params, cfg, pa, 12)
        wb = _reference_tokens(params, cfg, pb, 12)
        ha = eng.submit(pa, max_new_tokens=12)
        while eng.step():
            pass
        hb = eng.submit(pb, max_new_tokens=12)   # reuses slot 0
        while eng.step():
            pass
        assert ha.result(timeout=0) == wa
        assert hb.result(timeout=0) == wb

    def test_queueing_beyond_slots(self, dense):
        """More requests than slots: the overflow waits in the queue and is
        admitted as slots free up; everyone still matches solo."""
        params, cfg = dense
        prompts = [[i + 1, i + 2] for i in range(5)]
        want = [_reference_tokens(params, cfg, p, 3) for p in prompts]
        eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                               prefill_buckets=(4,))
        handles = [eng.submit(p, max_new_tokens=3) for p in prompts]
        assert eng.stats().queued == 5
        while eng.step():
            pass
        for h, w in zip(handles, want):
            assert h.result(timeout=0) == w
        s = eng.stats()
        assert s.finished_total == 5 and s.active == 0 and s.queued == 0


class TestLifecycle:
    def test_eos_retires_early(self, dense):
        params, cfg = dense
        prompt = [3, 4, 5]
        solo = _reference_tokens(params, cfg, prompt, 12)
        eos = solo[2]                     # stop at this token's 1st occurrence
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,), eos_id=eos)
        h = eng.submit(prompt, max_new_tokens=12)
        while eng.step():
            pass
        got = h.result(timeout=0)
        stop = solo.index(eos) + 1        # ends WITH the eos token
        assert got == solo[:stop] and len(got) < 12
        assert eng.stats().finished_total == 1

    def test_streaming_iteration(self, dense):
        params, cfg = dense
        prompt = [9, 10]
        want = _reference_tokens(params, cfg, prompt, 5)
        eng = GenerationEngine(params, cfg, slots=1, max_len=32,
                               prefill_buckets=(4,))
        h = eng.submit(prompt, max_new_tokens=5)
        streamed = []
        while eng.step():
            pass
        for tok in h:
            streamed.append(tok)
        assert streamed == want
        assert h.time_to_first_token() is not None

    def test_background_thread_generate(self, dense):
        """The deployed-service surface: start() + blocking generate()."""
        params, cfg = dense
        prompt = [21, 22, 23]
        want = _reference_tokens(params, cfg, prompt, 6)
        eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                               prefill_buckets=(4,)).start()
        try:
            assert eng.generate(prompt, max_new_tokens=6, timeout=120) == want
        finally:
            eng.stop()

    def test_submit_validates_length(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=16)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit([1] * 10, max_new_tokens=10)
        with pytest.raises(ValueError, match="empty"):
            eng.submit([], max_new_tokens=1)

    def test_sampled_mode_runs(self, dense):
        """Temperature>0: not bit-compared (different rng consumption than
        generate), but tokens must be in-vocab and the count exact."""
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                               prefill_buckets=(4,), temperature=0.8,
                               top_k=20, seed=7)
        h = eng.submit([2, 3, 4], max_new_tokens=6)
        while eng.step():
            pass
        got = h.result(timeout=0)
        assert len(got) == 6
        assert all(0 <= t < cfg.vocab_size for t in got)


class TestMoE:
    def test_moe_engine_matches_generate(self):
        from kubetorch_tpu.models.moe import MoeConfig, moe_init

        cfg = MoeConfig.tiny(dtype=jnp.float32, remat=False, attn_impl="xla")
        params = moe_init(jax.random.PRNGKey(1), cfg)
        prompt = [5, 6, 7]
        want = _reference_tokens(params, cfg, prompt, 6)
        eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                               prefill_buckets=(4,))
        h = eng.submit(prompt, max_new_tokens=6)
        while eng.step():
            pass
        assert h.result(timeout=0) == want


class TestHandleRetry:
    def test_result_timeout_keeps_drained_tokens(self, dense):
        """A result() that times out mid-decode must not eat the tokens it
        already drained — a retry sees the full stream from the start."""
        params, cfg = dense
        prompt = [5, 17, 42, 99]
        want = _reference_tokens(params, cfg, prompt, 8)
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        h = eng.submit(prompt, max_new_tokens=8)
        for _ in range(3):              # partial decode only
            eng.step()
        with pytest.raises(TimeoutError):
            h.result(timeout=0.01)
        while eng.step():
            pass
        assert h.result(timeout=0) == want       # nothing lost
        assert h.result(timeout=0) == want       # idempotent after done
        assert list(h) == want                   # iteration agrees too

    def test_max_new_tokens_validated(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=16)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2], max_new_tokens=0)

    def test_start_is_idempotent_single_loop(self, dense):
        import threading

        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=16)
        try:
            threads = [threading.Thread(target=eng.start) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            alive = [t for t in threading.enumerate()
                     if t.name == "kt-gen-engine"]
            assert len(alive) == 1
        finally:
            eng.stop()


@pytest.mark.level("release")
class TestShardedServing:
    def test_engine_matches_under_tensor_sharded_mesh(self, cpu_mesh_devices):
        """Multi-chip serving is the training sharding story: the same
        engine jits run GSPMD-partitioned when params carry NamedShardings
        on a data×tensor mesh — and the greedy tokens are unchanged."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        params, cfg = (llama_init(jax.random.PRNGKey(0),
                                  LlamaConfig.tiny(attn_impl="xla",
                                                   dtype=jnp.float32,
                                                   remat=False)),
                       LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                        remat=False))
        prompts = [[5, 17, 42], [9, 9, 9, 9]]
        want = [_reference_tokens(params, cfg, p, 6) for p in prompts]

        mesh = build_mesh({"data": 2, "tensor": 2}, devices=cpu_mesh_devices[:4])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        with use_mesh(mesh):
            eng = GenerationEngine(sharded, cfg, slots=4, max_len=32,
                                   prefill_buckets=(4,))
            handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
            while eng.step():
                pass
        for h, w in zip(handles, want):
            assert h.result(timeout=0) == w


class TestPerRequestSampling:
    def test_greedy_and_sampled_share_the_grid(self, dense):
        """A greedy request decoding next to a sampled one must produce its
        exact solo-run tokens — per-slot temperatures ride one compiled
        step, never a recompile or cross-slot contamination."""
        params, cfg = dense
        prompt_g = [5, 17, 42, 99]
        want = _reference_tokens(params, cfg, prompt_g, 8)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4,), temperature=0.9, seed=3)
        hg = eng.submit(prompt_g, max_new_tokens=8, temperature=0.0)
        hs = eng.submit([7, 7], max_new_tokens=8)        # engine default 0.9
        while eng.step():
            pass
        assert hg.result(timeout=0) == want
        sampled = hs.result(timeout=0)
        assert len(sampled) == 8
        assert all(0 <= t < cfg.vocab_size for t in sampled)


class TestPrefixCache:
    def test_prefix_cached_matches_full_prompt(self, dense):
        """submit(suffix, prefix_id) must equal a solo generate of
        prefix+suffix — the cached K/V plus positional offsets reproduce
        the from-zero prefill exactly (dense)."""
        params, cfg = dense
        prefix = [11, 12, 13, 14, 15]
        suffixes = [[21, 22], [31, 32, 33]]
        want = [_reference_tokens(params, cfg, prefix + s, 6)
                for s in suffixes]
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 8))
        pid = eng.register_prefix(prefix)
        handles = [eng.submit(s, max_new_tokens=6, prefix_id=pid)
                   for s in suffixes]
        while eng.step():
            pass
        for h, w in zip(handles, want):
            assert h.result(timeout=0) == w

    def test_prefix_and_plain_requests_interleave(self, dense):
        params, cfg = dense
        prefix = [50, 51, 52]
        plain = [1, 2, 3]
        want_pref = _reference_tokens(params, cfg, prefix + [60], 5)
        want_plain = _reference_tokens(params, cfg, plain, 5)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4,))
        pid = eng.register_prefix(prefix)
        h1 = eng.submit([60], max_new_tokens=5, prefix_id=pid)
        h2 = eng.submit(plain, max_new_tokens=5)
        while eng.step():
            pass
        assert h1.result(timeout=0) == want_pref
        assert h2.result(timeout=0) == want_plain

    def test_prefix_validation(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=16,
                               prefill_buckets=(4,))
        with pytest.raises(KeyError):
            eng.submit([1], max_new_tokens=1, prefix_id=99)
        pid = eng.register_prefix([1, 2, 3, 4])
        with pytest.raises(ValueError, match="max_len"):
            eng.submit([1] * 8, max_new_tokens=8, prefix_id=pid)


class TestPrefixLifecycle:
    def test_unregister_frees_and_queued_request_fails_cleanly(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        pid = eng.register_prefix([1, 2, 3])
        h_ok = eng.submit([4], max_new_tokens=3, prefix_id=pid)
        eng.step()         # admits h_ok into the single slot
        # queue a second against the same prefix, then unregister BEFORE it
        # can be admitted (the slot is busy with h_ok)
        h_fail = eng.submit([5], max_new_tokens=3, prefix_id=pid)
        assert eng.unregister_prefix(pid) is True
        assert eng.unregister_prefix(pid) is False
        while eng.step():
            pass
        assert len(h_ok.result(timeout=0)) == 3   # admitted before removal
        with pytest.raises(KeyError):
            h_fail.result(timeout=0)
        # the loop survived: new plain requests still serve
        h_next = eng.submit([6, 7], max_new_tokens=2)
        while eng.step():
            pass
        assert len(h_next.result(timeout=0)) == 2


def test_prefix_in_oversized_bucket_config(dense):
    """A short prefix must not eat a whole oversized bucket's worth of the
    max_len budget: when the smallest bucket leaves no room for suffix +
    generation, the stored K/V trims to the exact prefix length."""
    params, cfg = dense
    eng = GenerationEngine(params, cfg, slots=1, max_len=16,
                           prefill_buckets=(16,))   # only bucket == max_len
    prefix = [11, 12, 13]
    want = _reference_tokens(params, cfg, prefix + [60], 4)
    pid = eng.register_prefix(prefix)
    assert eng._prefixes[pid][0].shape[2] == 3      # trimmed, not 16
    h = eng.submit([60], max_new_tokens=4, prefix_id=pid)
    while eng.step():
        pass
    assert h.result(timeout=0) == want


# ---------------------------------------------------------------------------
# multi-LoRA serving
# ---------------------------------------------------------------------------


def _rand_adapters(seed, params, lcfg, scale=0.05):
    """Non-trivial adapters: lora_init's B factors are zeros (identity), so
    randomize them — each seed is a distinct adapter."""
    from kubetorch_tpu.models.lora import lora_init
    adap = lora_init(jax.random.PRNGKey(seed), params, lcfg)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1000),
                            len(adap["layers"]))
    adap["layers"] = {
        k: (v if k.endswith("__a")
            else jax.random.normal(kk, v.shape, v.dtype) * scale)
        for kk, (k, v) in zip(keys, sorted(adap["layers"].items()))}
    return adap


class TestMultiLora:
    """Unmerged activation-path adapters: different slots run different
    adapters through ONE compiled decode step. The contract mirrors
    TestEquivalence — a slot's tokens must be bit-identical to the same
    request run alone on an identically-configured engine."""

    @pytest.fixture(scope="class")
    def bank(self, dense):
        from kubetorch_tpu.models.lora import LoraConfig
        params, cfg = dense
        lcfg = LoraConfig(rank=4)
        return lcfg, _rand_adapters(7, params, lcfg), _rand_adapters(8, params, lcfg)

    def _engine(self, dense, bank):
        params, cfg = dense
        lcfg, ad_a, ad_b = bank
        eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                               prefill_buckets=(8,))
        ida = eng.register_adapter(ad_a, lcfg)
        idb = eng.register_adapter(ad_b, lcfg)
        return eng, ida, idb

    def test_slot_isolation(self, dense, bank):
        """Adapter-A request beside an adapter-B neighbor == the same
        A request alone on a fresh engine with identical banks."""
        pa, na = [5, 17, 42], 6
        pb, nb = [9, 9, 2, 30], 8
        solo = {}
        for which in ("a", "b"):
            eng, ida, idb = self._engine(dense, bank)
            h = (eng.submit(pa, max_new_tokens=na, adapter_id=ida)
                 if which == "a"
                 else eng.submit(pb, max_new_tokens=nb, adapter_id=idb))
            while eng.step():
                pass
            solo[which] = h.result(timeout=0)
        eng, ida, idb = self._engine(dense, bank)
        ha = eng.submit(pa, max_new_tokens=na, adapter_id=ida)
        hb = eng.submit(pb, max_new_tokens=nb, adapter_id=idb)
        while eng.step():
            pass
        assert ha.result(timeout=0) == solo["a"]
        assert hb.result(timeout=0) == solo["b"]
        # the adapters genuinely differ (A's tokens aren't B's on a shared
        # prompt would be a weaker check; assert the deltas did something)
        base = GenerationEngine(dense[0], dense[1], slots=4, max_len=64,
                                prefill_buckets=(8,))
        hbase = base.submit(pa, max_new_tokens=na)
        while base.step():
            pass
        assert hbase.result(timeout=0) != solo["a"]

    def test_adapter_beside_base_traffic(self, dense, bank):
        """A no-adapter request on an engine WITH banks (bank index 0 = the
        zero adapter) is bit-identical to the plain engine: the gathered
        zero factors contribute exactly 0.0."""
        params, cfg = dense
        prompt, n = [7, 8, 9], 6
        want = _reference_tokens(params, cfg, prompt, n)
        eng, ida, _ = self._engine(dense, bank)
        h_base = eng.submit(prompt, max_new_tokens=n)
        h_lora = eng.submit([4, 4], max_new_tokens=5, adapter_id=ida)
        while eng.step():
            pass
        assert h_base.result(timeout=0) == want
        assert len(h_lora.result(timeout=0)) == 5

    def test_activation_path_matches_merged(self, dense, bank):
        """The unmerged x·W + s·(x·A)·B path must agree with serving
        merge_lora(base, A) weights — the oracle the adapters train
        against."""
        from kubetorch_tpu.models.lora import merge_lora
        params, cfg = dense
        lcfg, ad_a, _ = bank
        prompt, n = [5, 17, 42, 99], 8
        merged = merge_lora(params, ad_a, lcfg)
        want = _reference_tokens(merged, cfg, prompt, n)
        eng, ida, _ = self._engine(dense, bank)
        h = eng.submit(prompt, max_new_tokens=n, adapter_id=ida)
        while eng.step():
            pass
        assert h.result(timeout=0) == want

    def test_prefix_with_adapter(self, dense, bank):
        """A prefix computed through adapter A + suffix/decode through A ==
        the full prompt through A."""
        params, cfg = dense
        lcfg, ad_a, _ = bank
        prefix, suffix, n = [11, 12, 13, 14], [60, 61], 5
        eng, ida, _ = self._engine(dense, bank)
        h_full = eng.submit(prefix + suffix, max_new_tokens=n, adapter_id=ida)
        while eng.step():
            pass
        want = h_full.result(timeout=0)
        eng2, ida2, _ = self._engine(dense, bank)
        pid = eng2.register_prefix(prefix, adapter_id=ida2)
        h = eng2.submit(suffix, max_new_tokens=n, prefix_id=pid,
                        adapter_id=ida2)
        while eng2.step():
            pass
        assert h.result(timeout=0) == want

    def test_unregister_reuses_slot_and_fails_queued(self, dense, bank):
        params, cfg = dense
        lcfg, ad_a, ad_b = bank
        eng, ida, idb = self._engine(dense, bank)
        n_bank = eng._banks["wq"][0].shape[1]
        assert eng.unregister_adapter(idb) is True
        assert eng.unregister_adapter(idb) is False
        # freed slot is reused: no bank growth
        idc = eng.register_adapter(ad_b, lcfg)
        assert eng._banks["wq"][0].shape[1] == n_bank
        # a submit against the evicted id fails fast...
        with pytest.raises(KeyError):
            eng.submit([1, 2], max_new_tokens=2, adapter_id=idb)
        # ...and one already queued fails cleanly through its handle
        h = eng.submit([1, 2], max_new_tokens=2, adapter_id=idc)
        eng.unregister_adapter(idc)
        while eng.step():
            pass
        with pytest.raises(KeyError):
            h.result(timeout=0)
        # the loop survived
        h2 = eng.submit([3], max_new_tokens=2, adapter_id=ida)
        while eng.step():
            pass
        assert len(h2.result(timeout=0)) == 2

    def test_config_mismatch_rejected(self, dense, bank):
        from kubetorch_tpu.models.lora import LoraConfig
        params, cfg = dense
        lcfg, ad_a, _ = bank
        eng, _, _ = self._engine(dense, bank)
        bad = _rand_adapters(9, params, LoraConfig(rank=2))
        with pytest.raises(ValueError, match="rank|config"):
            eng.register_adapter(bad, LoraConfig(rank=2))

    def test_late_registration_grows_bank(self, dense, bank):
        """Registering after traffic ran (bank growth → one recompile)
        still serves both old and new adapters correctly."""
        params, cfg = dense
        lcfg, ad_a, ad_b = bank
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(8,))
        ida = eng.register_adapter(ad_a, lcfg)
        h = eng.submit([5, 17, 42], max_new_tokens=4, adapter_id=ida)
        while eng.step():
            pass
        first = h.result(timeout=0)
        idb = eng.register_adapter(ad_b, lcfg)      # grows the bank
        h2 = eng.submit([5, 17, 42], max_new_tokens=4, adapter_id=ida)
        while eng.step():
            pass
        assert h2.result(timeout=0) == first        # A unchanged by growth

    def test_non_attention_targets_rejected(self, dense, bank):
        """Training/merging adapt any leaf; the activation path serves only
        the attention projections — banking w_gate would silently drop it."""
        from kubetorch_tpu.models.lora import LoraConfig
        params, cfg = dense
        lcfg = LoraConfig(rank=4, targets=("wq", "w_gate"))
        bad = _rand_adapters(11, params, lcfg)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(8,))
        with pytest.raises(ValueError, match="merge_lora"):
            eng.register_adapter(bad, lcfg)

    def test_unregister_repoints_inflight_to_base(self, dense, bank):
        """Evicting an adapter mid-decode must repoint its slots at bank
        index 0 (base model) — slot reuse by a new tenant must never leak
        into the old request's remaining tokens."""
        params, cfg = dense
        lcfg, ad_a, ad_b = bank
        eng, ida, idb = self._engine(dense, bank)
        h = eng.submit([5, 17, 42], max_new_tokens=6, adapter_id=ida)
        eng.step()                                   # admit + first decode
        slot = next(i for i, r in enumerate(eng._slot_req) if r is not None)
        assert eng._aidx[slot] == eng._adapter_slots[ida]
        eng.unregister_adapter(ida)
        assert eng._aidx[slot] == 0                  # base fallback
        idc = eng.register_adapter(ad_b, lcfg)       # reuses the freed index
        while eng.step():
            pass
        assert len(h.result(timeout=0)) == 6         # drained, no crash

    def test_eviction_during_prefill_falls_back_to_base(self, dense, bank,
                                                        monkeypatch):
        """The adapter can be evicted (and its bank index reused by a new
        tenant) in the window between admission resolving the index and
        the prefill finishing — the slot must then point at base (0),
        never at the reusing tenant's factors."""
        import kubetorch_tpu.serve.engine as eng_mod
        params, cfg = dense
        lcfg, ad_a, ad_b = bank
        eng, ida, idb = self._engine(dense, bank)
        orig = eng_mod._prefill
        hit = {}

        def racy_prefill(*a, **kw):
            out = orig(*a, **kw)
            if "adapter" in kw and not hit:   # only the adapter prefill
                hit["idx"] = eng._adapter_slots[ida]
                eng.unregister_adapter(ida)
                hit["reused"] = eng.register_adapter(ad_b, lcfg)
            return out

        monkeypatch.setattr(eng_mod, "_prefill", racy_prefill)
        h = eng.submit([5, 17, 42], max_new_tokens=4, adapter_id=ida)
        eng.step()
        slot = next(i for i, r in enumerate(eng._slot_req) if r is not None)
        assert hit and eng._adapter_slots[hit["reused"]] == hit["idx"]
        assert eng._aidx[slot] == 0            # base, not the new tenant
        while eng.step():
            pass
        assert len(h.result(timeout=0)) == 4


class TestContextShardedServing:
    """Long-context serving: the cache's sequence axis sharded over the
    ``context`` mesh axis, decode via local attention + one online-softmax
    combine (parallel/ring_attention.sp_decode_attention) — no chip ever
    holds more than 1/C of the cache."""

    def test_sp_decode_op_matches_einsum(self, cpu_mesh_devices):
        """Direct op check: sharded decode == the unsharded masked-einsum
        reference, across frontier positions including shard boundaries."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.ring_attention import (
            sp_decode_attention_sharded)

        b, nh, nkv, hd, s = 4, 4, 2, 32, 64
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, nh, hd), jnp.float32)
        ck = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd),
                               jnp.float32)
        cv = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd),
                               jnp.float32)
        # frontiers: inside shard 0, exactly at a shard boundary, deep in
        # the last shard, and row 0
        pos = jnp.array([5, 15, 63, 0], jnp.int32)
        mesh = build_mesh({"data": 2, "context": 4},
                          devices=cpu_mesh_devices[:8])
        # the op takes one layer of the head-major grid: (B, NKV, S, Hd)
        got = jax.jit(lambda *a: sp_decode_attention_sharded(
            *a, mesh, scale=hd ** -0.5))(
                q, ck.swapaxes(1, 2), cv.swapaxes(1, 2), pos)

        group = nh // nkv
        qg = q.reshape(b, nkv, group, hd)
        logits = (jnp.einsum("bkgh,bskh->bkgs", qg, ck)
                  .astype(jnp.float32) * (hd ** -0.5))
        mask = jnp.arange(s)[None, :] <= pos[:, None]
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
        want = jnp.einsum("bkgs,bskh->bkgh", probs, cv).reshape(b, nh, hd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_engine_matches_under_context_sharded_mesh(self,
                                                      cpu_mesh_devices):
        """The engine on a data×context mesh emits the same greedy tokens
        as the single-device run — the serving-side long-context story."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               remat=False)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 17, 42], [9, 9, 9, 9]]
        want = [_reference_tokens(params, cfg, p, 6) for p in prompts]

        mesh = build_mesh({"data": 2, "context": 4},
                          devices=cpu_mesh_devices[:8])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        with use_mesh(mesh):
            eng = GenerationEngine(sharded, cfg, slots=4, max_len=32,
                                   prefill_buckets=(4,))
            handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
            while eng.step():
                pass
        for h, w in zip(handles, want):
            assert h.result(timeout=0) == w

    def test_background_loop_keeps_context_sharding(self, cpu_mesh_devices):
        """The ambient mesh is THREAD-LOCAL: an engine built under
        use_mesh but driven by its background loop thread (start()/
        generate() — the kt.cls deployment mode) must still trace the
        context-sharded decode path, not silently fall back."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               remat=False)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        want = _reference_tokens(params, cfg, [5, 17, 42], 6)
        mesh = build_mesh({"data": 2, "context": 4},
                          devices=cpu_mesh_devices[:8])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        with use_mesh(mesh):
            eng = GenerationEngine(sharded, cfg, slots=2, max_len=32,
                                   prefill_buckets=(4,))
        # OUTSIDE the mesh context, on the loop thread:
        eng.start()
        try:
            got = eng.generate([5, 17, 42], 6)
        finally:
            eng.stop()
        assert got == want
        spec = str(eng._cache.k.sharding.spec)
        assert "context" in spec, spec
        # really 1/8 of the grid per chip
        leaf = eng._cache.k
        assert leaf.addressable_shards[0].data.nbytes * 8 == leaf.nbytes

    def test_non_dividing_shapes_fall_back_densely(self, cpu_mesh_devices):
        """max_len not divisible by the context axis: the sp path must
        step aside (shard_map cannot pad) and serving stays exact through
        the dense path."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               remat=False)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        want = _reference_tokens(params, cfg, [5, 17, 42], 6)
        mesh = build_mesh({"data": 2, "context": 4},
                          devices=cpu_mesh_devices[:8])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        with use_mesh(mesh):
            eng = GenerationEngine(sharded, cfg, slots=2, max_len=30,
                                   prefill_buckets=(4,))   # 30 % 4 != 0
            h = eng.submit([5, 17, 42], max_new_tokens=6)
            while eng.step():
                pass
        assert h.result(timeout=0) == want

    def test_quantized_context_sharded(self, cpu_mesh_devices):
        """int8 KV cache × context sharding compose: the quant sp combine
        serves exactly what the single-device quant engine serves."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               remat=False)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        solo = GenerationEngine(params, cfg, slots=2, max_len=32,
                                prefill_buckets=(4,), quantize_kv=True)
        hs = solo.submit([5, 17, 42], max_new_tokens=6)
        while solo.step():
            pass
        want = hs.result(timeout=0)

        mesh = build_mesh({"data": 2, "context": 4},
                          devices=cpu_mesh_devices[:8])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        with use_mesh(mesh):
            eng = GenerationEngine(sharded, cfg, slots=2, max_len=32,
                                   prefill_buckets=(4,), quantize_kv=True)
            h = eng.submit([5, 17, 42], max_new_tokens=6)
            while eng.step():
                pass
        assert h.result(timeout=0) == want
        assert "context" in str(eng._cache.kq.sharding.spec)

    def test_long_prompt_ring_prefill(self, cpu_mesh_devices):
        """Prompts at/above RING_PREFILL_MIN_T prefill via sequence-sharded
        ring attention on a context mesh — no chip holds the full (T, T)
        attention problem — and serving stays exact vs the single-device
        engine. An explicit attn_impl="xla" is a single-chip choice the
        gate must honor."""
        from kubetorch_tpu.models import generate as gen_mod
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        cfg = LlamaConfig.tiny(attn_impl="auto", dtype=jnp.float32,
                               remat=False)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        prompt = [int(x) for x in
                  np.random.RandomState(3).randint(
                      1, cfg.vocab_size, gen_mod.RING_PREFILL_MIN_T)]

        solo = GenerationEngine(params, cfg, slots=1, max_len=520,
                                prefill_buckets=(512,))
        h = solo.submit(prompt, max_new_tokens=6)
        while solo.step():
            pass
        want = h.result(timeout=0)

        mesh = build_mesh({"data": 2, "context": 4},
                          devices=cpu_mesh_devices[:8])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        # spy at TRACE time: the ring path must actually engage, not
        # silently fall back to the dense prefill
        import kubetorch_tpu.parallel.ring_attention as ring_mod
        traced = {}
        orig = ring_mod.ring_attention_sharded

        def spy(*a, **kw):
            traced["ring"] = True
            return orig(*a, **kw)

        ring_mod.ring_attention_sharded = spy
        try:
            with use_mesh(mesh):
                eng = GenerationEngine(sharded, cfg, slots=1, max_len=520,
                                       prefill_buckets=(512,))
                h = eng.submit(prompt, max_new_tokens=6)
                while eng.step():
                    pass
        finally:
            ring_mod.ring_attention_sharded = orig
        assert traced.get("ring"), "ring prefill never traced"
        assert h.result(timeout=0) == want
        # explicit "xla" opts OUT of the sequence-sharded prefill
        xcfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                remat=False)
        assert gen_mod._sp_prefill_impl(xcfg, 1, 512) is None


def test_engine_kt_metrics_hook(dense):
    """The engine's __kt_metrics__ gauges: numeric, complete, and live —
    what a deployed engine exports through the pod scrape."""
    params, cfg = dense
    eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                           prefill_buckets=(4,))
    h = eng.submit([1, 2], max_new_tokens=3)
    while eng.step():
        pass
    m = eng.__kt_metrics__()
    assert all(isinstance(v, float) for v in m.values())
    assert m["engine_finished_total"] == 1.0
    assert m["engine_tokens_generated"] == 3.0
    assert m["engine_slots"] == 2.0
    # speculative engines add acceptance gauges
    from kubetorch_tpu.serve import SpeculativeEngine
    dcfg = LlamaConfig.tiny(dim=32, n_layers=1, n_heads=2, n_kv_heads=1,
                            ffn_dim=64, attn_impl="xla", dtype=jnp.float32,
                            remat=False)
    draft = llama_init(jax.random.PRNGKey(7), dcfg)
    spec = SpeculativeEngine(params, cfg, draft, dcfg, spec_k=2, slots=2,
                             max_len=32, prefill_buckets=(4,))
    h = spec.submit([1, 2], max_new_tokens=3)
    while spec.step():
        pass
    sm = spec.__kt_metrics__()
    assert "engine_spec_acceptance_rate" in sm
    assert sm["engine_spec_rounds"] >= 1.0
    assert h.result(timeout=0) is not None


class TestCancellation:
    def test_cancel_queued_never_admits(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=32,
                               prefill_buckets=(4,))
        h1 = eng.submit([1, 2], max_new_tokens=8)
        h2 = eng.submit([3, 4], max_new_tokens=8)      # queued behind h1
        assert h2.cancel() is True
        assert h2.cancel() is False                    # idempotent
        while eng.step():
            pass
        assert len(h1.result(timeout=0)) == 8
        assert h2.result(timeout=0) == []              # clean empty stream
        assert eng.stats().admitted_total == 1

    def test_cancel_active_frees_slot_mid_stream(self, dense):
        """An active request stops at the next step boundary, keeps its
        partial tokens, and its slot serves the next caller exactly."""
        params, cfg = dense
        want_next = _reference_tokens(params, cfg, [9, 8], 5)
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        h = eng.submit([1, 2, 3], max_new_tokens=30)
        for _ in range(3):
            eng.step()
        assert h.cancel() is True
        while eng.step():
            pass
        got = h.result(timeout=0)
        assert 1 <= len(got) < 30                      # partial stream
        s = eng.stats()
        assert s.active == 0 and s.finished_total == 1
        # the freed slot serves the next request bit-exactly
        h2 = eng.submit([9, 8], max_new_tokens=5)
        while eng.step():
            pass
        assert h2.result(timeout=0) == want_next

    def test_cancel_unknown_or_finished_is_noop(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=32,
                               prefill_buckets=(4,))
        h = eng.submit([1, 2], max_new_tokens=2)
        while eng.step():
            pass
        assert len(h.result(timeout=0)) == 2
        assert h.cancel() is False                     # already finished
        assert eng.cancel(99999) is False              # unknown id

    def test_cancel_speculative_slot(self, dense):
        """Cancellation frees a SPECULATIVE slot's ledgers too — the next
        occupant must not inherit pending tokens or a stale frontier."""
        from kubetorch_tpu.serve import SpeculativeEngine
        params, cfg = dense
        dcfg = LlamaConfig.tiny(dim=32, n_layers=1, n_heads=2, n_kv_heads=1,
                                ffn_dim=64, attn_impl="xla",
                                dtype=jnp.float32, remat=False)
        draft = llama_init(jax.random.PRNGKey(7), dcfg)
        eng = SpeculativeEngine(params, cfg, draft, dcfg, spec_k=2,
                                slots=1, max_len=64, prefill_buckets=(4,))
        want = _reference_tokens(params, cfg, [9, 8], 5)
        h = eng.submit([1, 2, 3], max_new_tokens=30)
        eng.step()
        assert h.cancel() is True
        while eng.step():
            pass
        assert eng._slot_pending[0] == [] and eng._spec_valid[0] == 0
        h2 = eng.submit([9, 8], max_new_tokens=5)
        while eng.step():
            pass
        assert h2.result(timeout=0) == want

    def test_cancel_mid_admission_window(self, dense, monkeypatch):
        """A cancel landing while _admit_one's prefill runs (popped from
        the queue, slot not yet assigned) must take effect — the first
        compile can last seconds and disconnects love that window."""
        import kubetorch_tpu.serve.engine as eng_mod
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        orig = eng_mod._prefill
        hit = {}

        def racy_prefill(*a, **kw):
            out = orig(*a, **kw)
            if "cancelled" not in hit:      # cancel DURING the admission
                hit["cancelled"] = eng.cancel(h.request_id)
            return out

        monkeypatch.setattr(eng_mod, "_prefill", racy_prefill)
        h = eng.submit([1, 2, 3], max_new_tokens=30)
        while eng.step():
            pass
        assert hit["cancelled"] is True
        got = h.result(timeout=0)
        assert len(got) < 30                 # never decoded its budget
        assert eng.stats().active == 0

    def test_double_cancel_active_reads_false(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=32,
                               prefill_buckets=(4,))
        h = eng.submit([1, 2], max_new_tokens=10)
        eng.step()
        assert h.cancel() is True
        assert h.cancel() is False           # same contract as queued path
        while eng.step():
            pass


class TestDecodeBlock:
    """K decode steps per dispatch (``decode_block``): the host pays one
    dispatch per K tokens while admission/retirement stay host-side at
    block boundaries. The contract is bit-equivalence with the one-step
    engine for everything deterministic — mid-block retirement (budget,
    eos, stop sequences), penalties, int8 KV — since greedy decode is
    RNG-independent and the block scan runs the same per-step math."""

    def _run(self, eng, submits):
        handles = [eng.submit(*a, **k) for a, k in submits]
        while eng.step():
            pass
        return [h.result(timeout=0) for h in handles]

    def test_block_matches_oracle_mid_block_retirement(self, dense):
        """Budgets 3/8/5 against block=4: slots retire mid-block (the
        garbage tail past each stop point must be discarded) and every
        stream still matches its solo generate run."""
        params, cfg = dense
        prompts = [[7, 8, 9], [100, 200, 300, 400, 401], [1, 2]]
        ns = [3, 8, 5]
        want = [_reference_tokens(params, cfg, p, n)
                for p, n in zip(prompts, ns)]
        eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                               prefill_buckets=(8,), decode_block=4)
        got = self._run(eng, [((p,), {"max_new_tokens": n})
                              for p, n in zip(prompts, ns)])
        assert got == want
        # 8 tokens of budget after the prefill token = 7 needed decodes;
        # every dispatch runs the FULL block (no tail-sized recompiles),
        # so the engine pays two 4-step blocks and discards the overshoot
        assert eng.stats().decode_steps == 8

    def test_block_eos_and_stop_sequences(self, dense):
        """eos and stop-sequence retirement land mid-block; the emitted
        streams end exactly where the one-step engine's do."""
        params, cfg = dense
        prompt = [3, 4, 5]
        solo = _reference_tokens(params, cfg, prompt, 12)
        eos = solo[2]
        stop_seq = solo[1:3]              # retires at token 3 of the solo run
        for kwargs, want in (
                ({"eos_id": eos}, solo[:solo.index(eos) + 1]),
                ({}, None),               # stop= goes on the request below
        ):
            eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                                   prefill_buckets=(4,), decode_block=8,
                                   **kwargs)
            sub_kw = {"max_new_tokens": 12}
            if not kwargs:
                sub_kw["stop"] = [stop_seq]
                want = solo[:3]
            got = self._run(eng, [((prompt,), sub_kw)])[0]
            assert got == want and len(got) < 12

    def test_block_penalties_match_one_step(self, dense):
        """Greedy + repetition penalties are deterministic: the block
        engine's counts ledger (carried through the scan) must steer
        exactly like the one-step engine's."""
        params, cfg = dense
        prompt = [5, 17, 42, 99]
        runs = []
        for block in (1, 4):
            eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                                   prefill_buckets=(4,), decode_block=block)
            runs.append(self._run(eng, [
                ((prompt,), {"max_new_tokens": 10,
                             "frequency_penalty": 0.8}),
                (([1, 2],), {"max_new_tokens": 6,
                             "presence_penalty": 1.1}),
            ]))
        assert runs[0] == runs[1]
        # the penalties actually bit: the penalized stream differs from the
        # unpenalized oracle
        assert runs[0][0] != _reference_tokens(params, cfg, prompt, 10)

    def test_block_quantized_kv_matches_one_step(self, dense):
        params, cfg = dense
        prompts = [[7, 8, 9], [1, 2]]
        runs = []
        for block in (1, 4):
            eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                                   prefill_buckets=(4,), decode_block=block,
                                   quantize_kv=True)
            runs.append(self._run(eng, [((p,), {"max_new_tokens": 7})
                                        for p in prompts]))
        assert runs[0] == runs[1]

    def test_spec_engine_refuses_decode_block(self, dense):
        params, cfg = dense
        from kubetorch_tpu.serve.spec_engine import SpeculativeEngine
        with pytest.raises(ValueError, match="decode_block"):
            SpeculativeEngine(params, cfg, params, cfg, decode_block=4)


class TestAutoPrefix:
    """auto_prefix=True: submit() reuses the longest registered prefix the
    prompt starts with — full prompt in, cached K/V spliced, exact same
    tokens out as a from-zero prefill of the whole prompt."""

    def test_longest_match_reused_and_exact(self, dense):
        params, cfg = dense
        short = [5, 17]
        long = [5, 17, 42, 7]
        tail = [9, 11]
        want = _reference_tokens(params, cfg, long + tail, 6)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 8), auto_prefix=True)
        eng.register_prefix(short)
        pid_long = eng.register_prefix(long)
        h = eng.submit(long + tail, max_new_tokens=6)
        while eng.step():
            pass
        assert h.result(timeout=0) == want
        assert eng._prefix_hits == 1
        # the LONGEST prefix was the one matched: its bucket (4) + suffix
        # rows landed, which the slot frontier position reflects — and a
        # prompt that extends only the short prefix still matches short
        h2 = eng.submit([5, 17, 200], max_new_tokens=4)
        while eng.step():
            pass
        want2 = _reference_tokens(params, cfg, [5, 17, 200], 4)
        assert h2.result(timeout=0) == want2
        assert eng._prefix_hits == 2
        assert eng.unregister_prefix(pid_long)

    def test_no_match_and_exact_equal_prompt_fall_back(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,), auto_prefix=True)
        eng.register_prefix([5, 17, 42])
        # prompt EQUAL to the prefix leaves no suffix to prefill → full
        # prefill path, not a degenerate zero-length suffix
        want = _reference_tokens(params, cfg, [5, 17, 42], 4)
        h = eng.submit([5, 17, 42], max_new_tokens=4)
        # unrelated prompt → no match
        want2 = _reference_tokens(params, cfg, [9, 9], 3)
        h2 = eng.submit([9, 9], max_new_tokens=3)
        while eng.step():
            pass
        assert h.result(timeout=0) == want
        assert h2.result(timeout=0) == want2
        assert eng._prefix_hits == 0

    def test_adapter_mismatch_not_matched(self, dense):
        """A prefix cached through adapter A must not serve base traffic:
        the auto-match is adapter-keyed."""
        from kubetorch_tpu.models.lora import LoraConfig
        params, cfg = dense
        lcfg = LoraConfig(rank=2, targets=("wq",))
        ad = _rand_adapters(7, params, lcfg)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 8), auto_prefix=True)
        aid = eng.register_adapter(ad, lcfg)
        eng.register_prefix([5, 17, 42], adapter_id=aid)
        want = _reference_tokens(params, cfg, [5, 17, 42, 9], 4)
        h = eng.submit([5, 17, 42, 9], max_new_tokens=4)   # base traffic
        while eng.step():
            pass
        assert h.result(timeout=0) == want
        assert eng._prefix_hits == 0                       # no cross-use
        # but a request ON adapter A does match it
        ha = eng.submit([5, 17, 42, 9], max_new_tokens=4, adapter_id=aid)
        while eng.step():
            pass
        assert eng._prefix_hits == 1
        assert len(ha.result(timeout=0)) == 4

    def test_eviction_between_submit_and_admission_falls_back(self, dense):
        """An auto-matched prefix evicted while the request is queued must
        not fail the request — admission restores the full prompt."""
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4, 8), auto_prefix=True)
        pid = eng.register_prefix([5, 17, 42])
        blocker = eng.submit([8, 8], max_new_tokens=3)     # occupies slot 0
        h = eng.submit([5, 17, 42, 9], max_new_tokens=4)   # queued, matched
        eng.unregister_prefix(pid)                          # evicted in-flight
        while eng.step():
            pass
        want = _reference_tokens(params, cfg, [5, 17, 42, 9], 4)
        assert blocker.result(timeout=0) == _reference_tokens(
            params, cfg, [8, 8], 3)
        assert h.result(timeout=0) == want
        assert eng._prefix_hits == 0


class TestChunkedPrefill:
    """prefill_chunk=C: a prompt longer than C admits over multiple engine
    steps — one C-token chunk of prefill between decode blocks — via the
    prefix-suffix math, so a long admission never stalls active streams
    for more than one chunk. Contract: bit-exact vs the one-shot engine
    for dense models, neighbors unaffected, cancel honored mid-chunk."""

    def test_long_prompt_exact_with_active_neighbor(self, dense):
        params, cfg = dense
        long_prompt = list(range(5, 16))            # 11 tokens → 4+4+3
        want = _reference_tokens(params, cfg, long_prompt, 6)
        nbr_want = _reference_tokens(params, cfg, [1, 2], 8)
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 16), prefill_chunk=4,
                               decode_block=2)
        nbr = eng.submit([1, 2], max_new_tokens=8)
        h = eng.submit(long_prompt, max_new_tokens=6)
        while eng.step():
            pass
        assert h.result(timeout=0) == want
        assert nbr.result(timeout=0) == nbr_want

    def test_short_prompt_still_one_shot(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=32,
                               prefill_buckets=(4,), prefill_chunk=4)
        want = _reference_tokens(params, cfg, [7, 8], 4)
        h = eng.submit([7, 8], max_new_tokens=4)
        while eng.step():
            pass
        assert h.result(timeout=0) == want

    def test_chunked_behind_registered_prefix(self, dense):
        """A cached prefix seeds the accumulator; the long suffix chunks
        in behind it at the right positions."""
        params, cfg = dense
        prefix = [5, 17, 42]
        suffix = list(range(30, 39))                 # 9 tokens → 4+4+1
        want = _reference_tokens(params, cfg, prefix + suffix, 5)
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4, 8), prefill_chunk=4,
                               auto_prefix=True)
        eng.register_prefix(prefix)
        h = eng.submit(prefix + suffix, max_new_tokens=5)
        while eng.step():
            pass
        assert h.result(timeout=0) == want
        assert eng._prefix_hits == 1

    def test_chunked_penalties_match_one_shot(self, dense):
        params, cfg = dense
        long_prompt = list(range(50, 60))
        runs = []
        for chunk in (None, 4):
            eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                                   prefill_buckets=(4, 16),
                                   prefill_chunk=chunk)
            h = eng.submit(long_prompt, max_new_tokens=8,
                           frequency_penalty=0.7, presence_penalty=0.3)
            while eng.step():
                pass
            runs.append(h.result(timeout=0))
        assert runs[0] == runs[1]

    def test_chunked_quantized_kv(self, dense):
        params, cfg = dense
        long_prompt = list(range(5, 14))
        runs = []
        for chunk in (None, 4):
            eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                                   prefill_buckets=(4, 16),
                                   prefill_chunk=chunk, quantize_kv=True)
            h = eng.submit(long_prompt, max_new_tokens=6)
            while eng.step():
                pass
            runs.append(h.result(timeout=0))
        assert runs[0] == runs[1]

    def test_cancel_mid_chunking(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4, 16), prefill_chunk=4)
        nbr_want = _reference_tokens(params, cfg, [1, 2], 6)
        nbr = eng.submit([1, 2], max_new_tokens=6)
        h = eng.submit(list(range(5, 16)), max_new_tokens=6)
        eng.step()                     # chunk 1 ran; admission in flight
        assert h.cancel() is True
        while eng.step():
            pass
        assert h.result(timeout=0) == []     # stream ended, no tokens
        assert nbr.result(timeout=0) == nbr_want
        # the reserved slot was released: a new request admits and runs
        w2 = _reference_tokens(params, cfg, [9], 3)
        h2 = eng.submit([9], max_new_tokens=3)
        while eng.step():
            pass
        assert h2.result(timeout=0) == w2

    def test_chunked_sampled_mode_matches_one_shot(self, dense):
        """Intermediate chunks use a constant dummy key, so the engine's
        key-split stream is IDENTICAL to one-shot admission — sampled
        requests (same seed) decode the same tokens either way."""
        params, cfg = dense
        long_prompt = list(range(40, 51))
        runs = []
        for chunk in (None, 4):
            eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                                   prefill_buckets=(4, 16),
                                   prefill_chunk=chunk, seed=11)
            h = eng.submit(long_prompt, max_new_tokens=8, temperature=0.9,
                           top_p=0.8)
            while eng.step():
                pass
            runs.append(h.result(timeout=0))
        assert runs[0] == runs[1]

    def test_chunked_fills_to_exact_max_len(self, dense):
        """A prompt whose accumulated chunks reach the max_len boundary
        (chunk width not dividing the budget) still admits: the fixed
        max_len-capacity accumulator makes the final splice exact."""
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=32,
                               prefill_buckets=(4, 32), prefill_chunk=8)
        prompt = list(range(1, 30))          # 29 tokens; 29+1 <= 32
        want = _reference_tokens(params, cfg, prompt, 1)
        h = eng.submit(prompt, max_new_tokens=1)
        while eng.step():
            pass
        assert h.result(timeout=0) == want

    def test_chunked_prefix_plus_long_suffix_at_boundary(self, dense):
        """Registered prefix (bucket 4) + 59-token suffix at max_len=64:
        submit validates 4+59+1 <= 64 and the chunked path must not
        overflow the cache width."""
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,), prefill_chunk=8,
                               auto_prefix=True)
        prefix = [5, 17, 42]
        eng.register_prefix(prefix)
        suffix = list(range(100, 159))       # 59 tokens
        h = eng.submit(prefix + suffix, max_new_tokens=1)
        while eng.step():
            pass
        got = h.result(timeout=0)
        assert len(got) == 1 and eng._prefix_hits == 1
        assert got == _reference_tokens(params, cfg, prefix + suffix, 1)


    def test_two_long_prompts_queue_for_the_chunker(self, dense,
                                                    monkeypatch):
        """A second long prompt while the chunker is busy waits for it
        (never a one-shot prefill at a wide bucket) and both match their
        oracles. A width spy proves every prefill ran at the CHUNK width —
        the regression (falling back to one-shot) would show width 16."""
        import kubetorch_tpu.serve.engine as eng_mod
        params, cfg = dense
        widths = []
        real_prefill = eng_mod._prefill

        def spy(params_, tokens, *a, **kw):
            widths.append(tokens.shape[1])
            return real_prefill(params_, tokens, *a, **kw)

        monkeypatch.setattr(eng_mod, "_prefill", spy)
        p1 = list(range(5, 16))
        p2 = list(range(60, 73))
        w1 = _reference_tokens(params, cfg, p1, 5)
        w2 = _reference_tokens(params, cfg, p2, 5)
        eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                               prefill_buckets=(4, 16), prefill_chunk=4)
        h1 = eng.submit(p1, max_new_tokens=5)
        h2 = eng.submit(p2, max_new_tokens=5)
        while eng.step():
            pass
        assert h1.result(timeout=0) == w1
        assert h2.result(timeout=0) == w2
        assert widths == [4, 4], widths   # first chunks only, chunk-wide


class TestLogitBias:
    """OpenAI logit_bias: per-request additive bias on the logits, applied
    at the prefill sampling and every decode step. Slot-isolated (mask
    neutralizes stale rows) and reported logprobs stay raw-model."""

    def test_positive_bias_forces_token(self, dense):
        params, cfg = dense
        prompt = [5, 17, 42]
        solo = _reference_tokens(params, cfg, prompt, 6)
        forced = (solo[0] + 123) % cfg.vocab_size     # not the greedy pick
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4,))
        h = eng.submit(prompt, max_new_tokens=6,
                       logit_bias={forced: 1000.0})
        while eng.step():
            pass
        assert h.result(timeout=0) == [forced] * 6    # prefill + decode

    def test_negative_bias_suppresses_token(self, dense):
        params, cfg = dense
        prompt = [5, 17, 42]
        solo = _reference_tokens(params, cfg, prompt, 6)
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        h = eng.submit(prompt, max_new_tokens=6,
                       logit_bias={solo[0]: -1000.0})
        while eng.step():
            pass
        got = h.result(timeout=0)
        assert solo[0] not in got and got != solo

    def test_bias_is_slot_isolated_and_cleared_on_reuse(self, dense):
        params, cfg = dense
        prompt = [5, 17, 42]
        solo = _reference_tokens(params, cfg, prompt, 6)
        forced = (solo[0] + 7) % cfg.vocab_size
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4,))
        hb = eng.submit(prompt, max_new_tokens=6,
                        logit_bias={forced: 1000.0})
        hn = eng.submit(prompt, max_new_tokens=6)     # unbiased neighbor
        while eng.step():
            pass
        assert hb.result(timeout=0) == [forced] * 6
        assert hn.result(timeout=0) == solo
        # slot reuse: the retired biased slot's stale row must not leak
        h2 = eng.submit(prompt, max_new_tokens=6)
        h3 = eng.submit(prompt, max_new_tokens=6)
        while eng.step():
            pass
        assert h2.result(timeout=0) == solo
        assert h3.result(timeout=0) == solo

    def test_bias_block_path_matches_one_step(self, dense):
        params, cfg = dense
        prompt = [9, 9, 9]
        runs = []
        for block in (1, 4):
            eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                                   prefill_buckets=(4,),
                                   decode_block=block)
            h = eng.submit(prompt, max_new_tokens=7,
                           logit_bias={3: 5.0, 11: -5.0})
            while eng.step():
                pass
            runs.append(h.result(timeout=0))
        assert runs[0] == runs[1]

    def test_bias_validates_vocab_range(self, dense):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=1, max_len=32)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit([1, 2], max_new_tokens=2,
                       logit_bias={cfg.vocab_size + 5: 1.0})



class TestPerRequestSeed:
    """submit(..., seed=S): the sampled stream is a pure function of
    (seed, prompt positions) — invariant to slot placement, neighbors,
    engine seed, decode_block, and admission order."""

    def _run(self, dense, engine_seed, neighbors, seed, block=1,
             chunk=None, prompt=(3, 4)):
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                               prefill_buckets=(4, 16), seed=engine_seed,
                               decode_block=block, prefill_chunk=chunk)
        for p in neighbors:
            eng.submit(p, max_new_tokens=5, temperature=1.0)
        h = eng.submit(list(prompt), max_new_tokens=6, temperature=1.0,
                       seed=seed)
        while eng.step():
            pass
        return h.result(timeout=0)

    def test_seed_invariant_to_everything_else(self, dense):
        a = self._run(dense, 0, [[1, 1]], 42)
        b = self._run(dense, 7, [[9, 9], [2, 2]], 42)   # slot 2, new chain
        d = self._run(dense, 0, [[1, 1]], 42, block=4)
        ch = self._run(dense, 0, [[1, 1]], 42, chunk=4,
                       prompt=tuple(range(3, 14)))
        ch2 = self._run(dense, 3, [], 42, chunk=4,
                        prompt=tuple(range(3, 14)))
        assert a == b == d
        assert ch == ch2                                 # chunked too
        assert a != self._run(dense, 0, [[1, 1]], 43)    # seeds diverge

    def test_greedy_ignores_seed(self, dense):
        params, cfg = dense
        want = _reference_tokens(params, cfg, [5, 17, 42], 6)
        eng = GenerationEngine(params, cfg, slots=1, max_len=64,
                               prefill_buckets=(4,))
        h = eng.submit([5, 17, 42], max_new_tokens=6, temperature=0.0,
                       seed=99)
        while eng.step():
            pass
        assert h.result(timeout=0) == want

    def test_openai_seed_reproducible_over_the_wire(self, dense):
        import asyncio
        from aiohttp.test_utils import TestClient, TestServer
        from kubetorch_tpu.serve.openai_api import build_app
        params, cfg = dense
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(4,)).start()

        async def body():
            client = TestClient(TestServer(build_app(eng)))
            await client.start_server()
            outs = []
            for _ in range(2):
                r = await client.post("/v1/completions", json={
                    "prompt": [5, 17, 42], "max_tokens": 5,
                    "temperature": 1.0, "seed": 1234})
                outs.append((await r.json())["choices"][0]["token_ids"])
            await client.close()
            return outs

        try:
            outs = asyncio.run(body())
        finally:
            eng.stop()
        assert outs[0] == outs[1]


def test_ttft_stat_populates(dense):
    params, cfg = dense
    eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                           prefill_buckets=(4,))
    assert eng.stats().ttft_avg == 0.0
    h = eng.submit([1, 2], max_new_tokens=3)
    while eng.step():
        pass
    s = eng.stats()
    assert s.ttft_avg > 0.0
    assert abs(s.ttft_avg - h.time_to_first_token()) < 1e-6
    assert eng.__kt_metrics__()["engine_ttft_avg_seconds"] == s.ttft_avg
