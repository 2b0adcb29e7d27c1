"""Speculative continuous batching: the slot-grid engine with a draft.

``speculative_generate`` (serve/speculative.py) speculates ONE request;
``GenerationEngine`` batches many requests but decodes one token per slot
per step. This engine does both at once: every round, a draft model
proposes ``k`` tokens for EVERY active slot, and one target forward scores
all slots' pending+proposal windows together — so each target
weight-stream yields 1..k+1 tokens per slot, across the whole grid.

The shapes stay static (the engine's contract): the draft ingests a
(SLOTS, k+1) block of per-slot pending tokens, proposes via k-1 grid
decode steps, and the target verifies a (SLOTS, 2k+1) block — per-slot
true lengths ride as traced vectors, so mixed progress (a slot that
accepted everything beside one that accepted nothing, idle slots at
length 0) shares one compile. Rows past a slot's frontier hold stale
garbage by design: every round writes its rows BEFORE attending and the
per-slot causal mask never admits an unwritten row — the same position
ledger the standalone implementation proves (speculative.py docstring).

Greedy verification is EXACT per slot: each request's emitted stream is
bit-identical to the target's own greedy decode of that prompt, whatever
the draft proposes and whatever the neighbors do — the oracle
``tests/test_spec_engine.py`` asserts, for dense AND MoE targets (MoE
windows route drop-free like the standalone; the prefill mirrors the
oracle's real-length capacity).

Reference analog: none — beyond-parity serving, docs/serving.md.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry

from ..models.block import decoder_block, qkv_attend, rmsnorm
from ..models.generate import ffn_block, rope_freqs
from ..models.quant import lm_head_dot
from .engine import (GenerationEngine, _decode_block, _einsum_attention,
                     _prefill, _prefill_suffix, _splice_slot, init_grid_cache)
from .speculative import SpecStats


def window_attend(cfg, leaves, posm, s_eff: int):
    """The block's attention operation over ONE layer of the head-major grid
    for a window a slot: write the (B, W) new rows at ``posm`` (B, W), then
    attend the first ``s_eff`` rows under the per-(slot, offset) causal mask
    with the engine's reference einsums (``engine._einsum_attention``), so
    the verify window attends bit-compatibly with the T=1 decode it must
    match.

    ``leaves``: (ck, cv) (B, NKV, S, Hd), or the int8 (kq, ks, vq, vs) with
    scales (B, NKV, S): new rows are quantized before they are written.
    Returns ``attend(q, k, v) -> (attn, leaves')``."""
    from .kv_quant import grid_rows

    def attend(q, k, v):
        bi = jnp.arange(q.shape[0])[:, None]
        with jax.named_scope("kt.cache_update"):
            # layer slices are head-major (B, NKV, S, ...): the advanced
            # indices around the head slice put (B, W) first, as the rows are
            grid = tuple(g.at[bi, :, posm].set(r.astype(g.dtype))
                         for g, r in zip(leaves, grid_rows(leaves, k, v)))
        with jax.named_scope("kt.attention"):
            attn = _einsum_attention(
                q, tuple(lax.slice_in_dim(g, 0, s_eff, axis=2) for g in grid),
                posm, cfg.head_dim ** -0.5)
            return attn.astype(q.dtype), grid

    return attend


@partial(jax.jit, static_argnames=("cfg", "s_eff", "lora_scale"),
         donate_argnums=(1,))
def _grid_ingest(params, cache, blocks, start, true_len, cfg,
                 s_eff: Optional[int] = None, banks=None, aidx=None,
                 lora_scale: float = 1.0):
    """Run a (B, W) token window through the model, each slot at its own
    absolute positions ``start[b] + i``, writing cache rows and returning
    fp32 logits for EVERY window position (B, W, V).

    ``true_len`` (B,) marks each slot's real tokens: padding (and wholly
    idle slots at true_len 0) writes garbage rows past the frontier that a
    later round overwrites before the mask can admit them, and never
    claims MoE expert capacity (token_mask + no_drop routing — each real
    token routes exactly as it would alone, the T=1 oracle).

    ``s_eff`` (static) bounds the attended cache rows: the causal mask
    never admits a row past ``max(start) + W``, so the caller passes that
    frontier rounded up to a power-of-two bucket and the attention einsums
    stream ``s_eff`` rows instead of all ``S_max`` — the frontier-skip the
    flash-decode kernel gives the T=1 path, as a static slice here (one
    compile per bucket, a handful over a request's lifetime).

    ``cache`` is the engine's head-major grid (L, SLOTS, NKV, S_max, Hd),
    a fp ``KVCache`` or an int8 ``QuantKVCache`` (``serve.kv_quant``) —
    the pytree structure keys the jit (:func:`window_attend` handles
    both). The grid is scanned as ``xs``/``ys`` here, a layer slice a step,
    where the decode block carries it."""
    b, w = blocks.shape
    s_max = cache[0].shape[3]
    if s_eff is None:
        s_eff = s_max
    x = params["embed"][blocks].astype(cfg.dtype)
    posm = start[:, None] + jnp.arange(w)[None, :]          # (B, W)
    freqs = rope_freqs(cfg, s_max)[posm]                     # (B, W, Hd/2)
    token_mask = jnp.arange(w)[None, :] < true_len[:, None]  # (B, W)
    ffn = partial(ffn_block, cfg, token_mask=token_mask, moe_no_drop=True)

    from ..models.lora import gather_slot_adapters

    def body(h, layer):
        lw, leaves, bank_l = layer
        # the SAME gather the plain decode step uses (shared helper — the
        # bank layout / zero-adapter convention cannot drift)
        lora = gather_slot_adapters(bank_l, aidx, lora_scale, banks)
        h, leaves, _ = decoder_block(
            cfg, h, lw,
            qkv_attend(cfg, freqs, window_attend(cfg, leaves, posm, s_eff)),
            ffn, lora=lora)
        return h, leaves

    x, leaves = lax.scan(body, x, (params["layers"], tuple(cache),
                                   banks or {}))
    new_cache = type(cache)(*leaves)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head_dot(x, params, cfg.dtype)
    return logits, new_cache


class SpeculativeEngine(GenerationEngine):
    """Continuous batching with per-slot speculative decoding (module
    docstring has the design). Greedy-only — the exactness proof is the
    argmax acceptance rule; sampled speculation needs rejection sampling
    and is out of scope. int8 KV composes (``quantize_kv=True`` — the
    TARGET cache quantizes; the draft stays fp, its cache is small), and
    so does multi-LoRA (per-request ``adapter_id``: the target's window
    forwards gather each slot's adapter while the draft proposes from
    base weights — proposal quality only, never tokens), and so does
    prefix caching (``register_prefix`` prefills BOTH models' prefixes;
    admission splices each into its own grid), and chunked prefill
    (``prefill_chunk`` — both accumulators advance one chunk per step).
    Tensor/data meshes work GSPMD-sharded like the plain engine; a CONTEXT axis is also correct here but the window forwards
    have no per-shard combine yet, so the cache won't stay
    sequence-sharded — context-sharded serving is the plain engine's
    feature (``sp_decode_attention``)."""

    def __init__(self, params: Dict[str, Any], cfg,
                 draft_params: Dict[str, Any], draft_cfg, *, spec_k: int = 4,
                 spec_k_min: Optional[int] = None,
                 spec_k_max: Optional[int] = None,
                 spec_adapt_every: int = 4, **kwargs):
        if kwargs.get("temperature", 0.0) != 0.0:
            raise ValueError("SpeculativeEngine is greedy-only "
                             "(temperature=0); use GenerationEngine for "
                             "sampled serving")
        if kwargs.get("top_p") is not None:
            raise ValueError("top_p requires sampling — SpeculativeEngine "
                             "is greedy-only; use GenerationEngine")
        if kwargs.get("decode_block", 1) != 1:
            raise ValueError("decode_block tunes GenerationEngine's plain "
                             "decode loop; a speculation round already "
                             "batches its device work — use spec_k")
        if kwargs.get("auto_prefix"):
            # the verify-window headroom check runs in submit() BEFORE the
            # base engine would auto-match a prefix — an auto-matched
            # bucket could push the speculation window past max_len
            raise ValueError("auto_prefix is not supported with "
                             "speculation — pass prefix_id explicitly")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        for c in (cfg, draft_cfg):
            if getattr(c, "cache_kind", "kv") != "kv":
                from ..exceptions import UnsupportedMechanismError
                raise UnsupportedMechanismError(
                    "speculative decoding (SpeculativeEngine)", c.cache_kind)
        super().__init__(params, cfg, **kwargs)
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.k = int(spec_k)
        # Adaptive draft length (ISSUE 12 satellite): `k` is a *bet* on the
        # draft's acceptance rate, and a static bet is wrong in both
        # directions — a well-aligned draft wastes target weight-streams on
        # too-short windows, a misaligned one burns k draft decodes per
        # emitted token. An acceptance-rate EWMA shrinks/grows k within
        # [k_min, k_max] (env KT_SPEC_K_MIN/KT_SPEC_K_MAX or kwargs; both
        # default to spec_k, i.e. adaptation off unless bounds are widened).
        # Each distinct k is its own compile of the window forwards — the
        # bounds cap that to a handful, like the s_eff buckets.
        env_min = os.environ.get("KT_SPEC_K_MIN")
        env_max = os.environ.get("KT_SPEC_K_MAX")
        self.k_min = int(spec_k_min if spec_k_min is not None
                         else (env_min or self.k))
        self.k_max = int(spec_k_max if spec_k_max is not None
                         else (env_max or self.k))
        if not (1 <= self.k_min <= self.k <= self.k_max):
            raise ValueError(
                f"need 1 <= k_min ({self.k_min}) <= spec_k ({self.k}) <= "
                f"k_max ({self.k_max})")
        self._adapt_every = max(1, int(spec_adapt_every))
        self._rounds_since_adapt = 0
        self._accept_ewma: Optional[float] = None
        self._draft_cache = init_grid_cache(draft_cfg, self.slots,
                                            self.max_len)
        # per-slot ledgers: rows both caches validly cover, and the tokens
        # emitted but not yet ingested (1..k+1 long while active).
        # NB: self._pending is the BASE class's request queue — the token
        # ledger gets its own name
        self._spec_valid = np.zeros(self.slots, np.int32)
        self._slot_pending: List[List[int]] = [[] for _ in range(self.slots)]
        # pid → (draft prefix K, V) — the target's tuples live in the base
        # self._prefixes; widths are trimmed to match
        self._draft_prefixes: Dict[int, tuple] = {}
        self.spec_stats = SpecStats()

    # -- unsupported registrations refused at REGISTRATION time, before
    # they commit device memory no request could ever use ------------------

    # register_adapter/unregister_adapter: the BASE implementations — the
    # bank/aidx machinery is shared; the target's window forwards gather
    # per-slot adapters exactly like the plain decode step

    def register_prefix(self, tokens: Sequence[int],
                        adapter_id: Optional[int] = None) -> int:
        """Prefix caching under speculation: the TARGET's prefix K/V comes
        from the base machinery; the DRAFT (its own model) prefills the
        same tokens through its own weights — both caches splice their
        prefix at admission, at the same bucket widths (shared bucket
        table), so the position ledgers stay aligned."""
        pid = super().register_prefix(tokens, adapter_id)   # validates
        with self._mesh_scope():
            pk = self._prefixes[pid][0]
            t = len(tokens)
            # pad straight to the TARGET's stored width: one source of
            # truth for the bucket/trim policy (the base), and the two
            # models' prefix widths cannot desynchronize
            padded = np.zeros((1, pk.shape[2]), np.int32)
            padded[0, :t] = [int(x) for x in tokens]
            _f, dk, dv, _lp = _prefill(
                self.draft_params, jnp.asarray(padded), jnp.int32(t),
                self._next_key(), jnp.zeros((1,), jnp.float32),
                self.draft_cfg)
            self._draft_prefixes[pid] = (dk, dv)
        return pid

    def unregister_prefix(self, prefix_id: int) -> bool:
        self._draft_prefixes.pop(prefix_id, None)
        return super().unregister_prefix(prefix_id)

    # -- submission ---------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 64,
               temperature: Optional[float] = None,
               prefix_id: Optional[int] = None,
               adapter_id: Optional[int] = None,
               top_p: Optional[float] = None,
               frequency_penalty: float = 0.0,
               presence_penalty: float = 0.0,
               stop: Optional[Sequence] = None,
               logit_bias=None, seed=None):
        if temperature not in (None, 0.0):
            raise ValueError("SpeculativeEngine is greedy-only")
        if top_p is not None:
            raise ValueError("top_p requires sampling — SpeculativeEngine "
                             "is greedy-only; use GenerationEngine")
        if frequency_penalty or presence_penalty:
            # penalties change even the greedy argmax, which would break
            # the exact-verification acceptance rule (target argmax is
            # computed penalty-free in the verify window)
            raise ValueError("repetition penalties are not supported with "
                             "speculation — use GenerationEngine")
        if logit_bias:
            # same argmax-steering problem as penalties
            raise ValueError("logit_bias is not supported with "
                             "speculation — use GenerationEngine")
        if seed is not None:
            raise ValueError("seed is meaningless for greedy speculation "
                             "(deterministic already) — use "
                             "GenerationEngine for sampled serving")
        prompt = [int(t) for t in prompt]
        p_bucket = 0
        if prefix_id is not None:
            pref = self._prefixes.get(prefix_id)
            if pref is None:
                raise KeyError(f"unknown prefix_id {prefix_id}")
            p_bucket = pref[0].shape[2]
        # the verify window writes up to 2k+1 rows past the last emitted
        # token — reserve headroom for the LARGEST k adaptation may pick,
        # so a later grow can never push a seated request out of bounds
        if (prompt and max_new_tokens >= 1
                and p_bucket + len(prompt) + max_new_tokens
                + 2 * self.k_max + 1 > self.max_len):
            raise ValueError(
                f"prefix bucket ({p_bucket}) + prompt ({len(prompt)}) + "
                f"max_new_tokens ({max_new_tokens}) + verify window "
                f"({2 * self.k_max + 1}) exceeds max_len ({self.max_len})")
        # stop sequences work unchanged: emission goes through the shared
        # _emit suffix check, and speculation is exact-greedy so stopping
        # early never changes the tokens that were already emitted
        return super().submit(prompt, max_new_tokens, stop=stop,
                              adapter_id=adapter_id, prefix_id=prefix_id)

    # -- admission ----------------------------------------------------------

    def _admit_one(self, req, slot: int) -> None:
        pref = self._resolve_prefix(req)
        t = len(req.prompt)
        temps = jnp.zeros((1,), jnp.float32)
        adapter, aidx = self._resolve_adapter(req.adapter_id)
        lkw = ({"adapter": adapter, "lora_scale": self._lora_cfg.scale}
               if adapter is not None else {})
        if req.prefix_id is not None:
            # both models continue behind their OWN cached prefix, at the
            # same widths (registration pads the draft to the target's).
            # Fetch the draft half ONCE: an unregister racing admission
            # must fail this request cleanly, not half-resolve
            pk, pv, p_real, _toks, _pad = pref
            dpref = self._draft_prefixes.get(req.prefix_id)
            if dpref is None:
                raise KeyError(f"unknown prefix_id {req.prefix_id}")
            dk_p, dv_p = dpref
            p_bucket = pk.shape[2]
            bucket = next((b for b in self._buckets if b >= t
                           and p_bucket + b <= self.max_len), None)
            if bucket is None:
                bucket = self.max_len - p_bucket
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :t] = req.prompt
            block = jnp.asarray(padded)
            first, k_new, v_new, _flp = _prefill_suffix(
                self.params, block, jnp.int32(t), pk, pv,
                jnp.int32(p_real), self._next_key(), temps, self.cfg,
                **lkw)
            _f2, dk, dv, _dlp = _prefill_suffix(
                self.draft_params, block, jnp.int32(t), dk_p, dv_p,
                jnp.int32(p_real), self._next_key(), temps,
                self.draft_cfg)
            start = int(p_real) + t
            self._prefix_hits += 1
        else:
            bucket = next(b for b in self._buckets if b >= t)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :t] = req.prompt
            block = jnp.asarray(padded)
            first, k_new, v_new, _flp = _prefill(
                self.params, block, jnp.int32(t), self._next_key(), temps,
                self.cfg, **lkw)
            # the draft prefills the same prompt into ITS grid (its
            # first-token sample is discarded — the target owns every
            # emitted token)
            _f2, dk, dv, _dlp = _prefill(
                self.draft_params, block, jnp.int32(t), self._next_key(),
                temps, self.draft_cfg)
            start = t
        self._seat(req, slot, first, k_new, v_new, dk, dv, start, aidx)

    def _seat(self, req, slot, first, k_new, v_new, dk, dv, start,
              aidx) -> None:
        """Post-prefill seating shared by one-shot and chunked admission:
        splice BOTH caches, set the speculation ledgers, re-check the
        adapter mapping, emit the first (target-sampled) token."""
        self._cache = _splice_slot(self._cache, jnp.int32(slot),
                                   k_new, v_new)
        self._draft_cache = _splice_slot(self._draft_cache, jnp.int32(slot),
                                         dk, dv)
        first_tok = int(first[0])
        self._slot_req[slot] = req
        with self._lock:
            # the base engine's stale-index re-check: an adapter evicted
            # during the prefill must fall back to base, never to a
            # reused bank index
            if (req.adapter_id is not None
                    and self._adapter_slots.get(req.adapter_id) != aidx):
                aidx = 0
            self._aidx[slot] = aidx
        self._spec_valid[slot] = start
        self._slot_pending[slot] = [first_tok]
        self._admitted += 1
        # a retirement on this first token clears the ledgers through the
        # shared _retire_slot → _free_slot_ledgers path
        self._emit(slot, first_tok)

    # -- chunked prefill (both models) --------------------------------------

    def _start_chunking(self, req, slot: int) -> None:
        """First chunk of a long admission, for BOTH models: two
        max_len-capacity accumulators advance in lockstep (the base
        engine's single-accumulator scheme, doubled)."""
        pref = self._resolve_prefix(req)
        adapter, aidx = self._resolve_adapter(req.adapter_id)
        lkw = ({"adapter": adapter, "lora_scale": self._lora_cfg.scale}
               if adapter is not None else {})
        c = self.prefill_chunk
        zero_t = jnp.zeros((1,), jnp.float32)
        if req.prefix_id is not None:
            pk, pv, p_real, _toks, _pad = pref
            dpref = self._draft_prefixes.get(req.prefix_id)
            if dpref is None:
                raise KeyError(f"unknown prefix_id {req.prefix_id}")
            tk, tv = pk, pv
            dk, dv = dpref
            self._prefix_hits += 1
            consumed, frontier = 0, int(p_real)
        else:
            toks = req.prompt[:c]
            padded = np.zeros((1, c), np.int32)
            padded[0, :] = toks
            block = jnp.asarray(padded)
            _f, tk, tv, _lp = _prefill(
                self.params, block, jnp.int32(c), self._dummy_key, zero_t,
                self.cfg, **lkw)
            _f2, dk, dv, _lp2 = _prefill(
                self.draft_params, block, jnp.int32(c), self._dummy_key,
                zero_t, self.draft_cfg)
            consumed = frontier = c

        def widen(arr):
            pad_w = self.max_len - arr.shape[2]
            spec = [(0, 0)] * arr.ndim
            spec[2] = (0, pad_w)
            return jnp.pad(arr, spec)

        self._chunking = (req, slot, widen(tk), widen(tv), widen(dk),
                          widen(dv), consumed, frontier, lkw, aidx)

    def _chunk_step(self) -> None:
        (req, slot, tk, tv, dk, dv, consumed, frontier,
         lkw, aidx) = self._chunking
        if req.cancelled:
            self._chunking = None
            req.out.put(None)
            return
        c = self.prefill_chunk
        rest = len(req.prompt) - consumed
        take = min(c, rest)
        padded = np.zeros((1, c), np.int32)
        padded[0, :take] = req.prompt[consumed:consumed + take]
        block = jnp.asarray(padded)
        zero_t = jnp.zeros((1,), jnp.float32)
        last = take == rest
        try:
            key = (self._next_key() if last else self._dummy_key)
            first, tk, tv, _lp = _prefill_suffix(
                self.params, block, jnp.int32(take), tk, tv,
                jnp.int32(frontier), key, zero_t, self.cfg, **lkw)
            _f2, dk, dv, _lp2 = _prefill_suffix(
                self.draft_params, block, jnp.int32(take), dk, dv,
                jnp.int32(frontier), self._dummy_key, zero_t,
                self.draft_cfg)
            if not last:
                self._chunking = (req, slot, tk[:, :, :self.max_len],
                                  tv[:, :, :self.max_len],
                                  dk[:, :, :self.max_len],
                                  dv[:, :, :self.max_len],
                                  consumed + take, frontier + take,
                                  lkw, aidx)
                return
            self._chunking = None
            self._seat(req, slot, first, tk[:, :, :self.max_len],
                       tv[:, :, :self.max_len], dk[:, :, :self.max_len],
                       dv[:, :, :self.max_len], frontier + take, aidx)
        except Exception as e:   # noqa: BLE001 — fail THIS request only
            self._chunking = None
            req.error = e
            req.out.put(None)

    # -- the speculative round ----------------------------------------------

    def _free_slot_ledgers(self, slot: int) -> None:
        self._slot_pending[slot] = []
        self._spec_valid[slot] = 0

    def step(self) -> int:
        with self._mesh_scope():
            self._reap_cancelled()
            self._admit()
            active = [i for i, r in enumerate(self._slot_req)
                      if r is not None]
            if active:
                self._round(active)
        with self._lock:
            queued = len(self._pending)
        # a mid-chunked-admission request is neither seated nor pending —
        # count it so drive loops don't stop with work in flight (the
        # base _step_once has the same term)
        return (sum(r is not None for r in self._slot_req) + queued
                + (1 if self._chunking is not None else 0))

    def _round(self, active: List[int]) -> None:
        b, k = self.slots, self.k
        wd, wt = k + 1, 2 * k + 1
        c = np.zeros(b, np.int32)
        for i in active:
            c[i] = len(self._slot_pending[i])
        start = self._spec_valid.astype(np.int32).copy()
        # static frontier bucket: no slot attends a row past its own
        # start + W, so both window forwards stream s_eff rows, not S_max
        # (a power-of-two bucket bounds compiles to a handful)
        need = int(start[active].max()) + wt
        s_eff = self.max_len
        while s_eff // 2 >= need and s_eff > 1:
            s_eff //= 2

        # draft: ingest each slot's pending block, then propose greedily
        # (temps 0 ⇒ argmax) — the first proposal from the ingest logits,
        # the remaining k-1 from one scanned decode block below
        dblock = np.zeros((b, wd), np.int32)
        for i in active:
            dblock[i, :c[i]] = self._slot_pending[i]
        dlog, self._draft_cache = _grid_ingest(
            self.draft_params, self._draft_cache, jnp.asarray(dblock),
            jnp.asarray(start), jnp.asarray(c), self.draft_cfg,
            s_eff=s_eff)
        last = np.clip(c - 1, 0, wd - 1)
        tok = jnp.argmax(dlog[jnp.arange(b), last],
                         axis=-1).astype(jnp.int32)
        zeros = jnp.zeros(b, jnp.float32)
        if k > 1:
            # all k-1 remaining proposals in ONE dispatch: the scanned
            # decode block returns the stacked per-step tokens, so the
            # whole draft phase costs two device round-trips (ingest +
            # block) instead of k. Greedy (temps 0) ⇒ the key is unused.
            self._draft_cache, _fp, _ft, toks_k, _lps, _cnt = _decode_block(
                self.draft_params, self._draft_cache,
                jnp.asarray(start + c), tok, self._dummy_key, zeros,
                self.draft_cfg, n_steps=k - 1)
            # (B, k) = first proposal + the block's (k-1, B) transposed
            proposals = np.concatenate(
                [np.asarray(tok)[:, None], np.asarray(toks_k).T], axis=1)
        else:
            proposals = np.asarray(tok)[:, None]          # (B, 1)

        # target: one forward over pending+proposals for every slot
        tblock = np.zeros((b, wt), np.int32)
        tl = np.zeros(b, np.int32)
        for i in active:
            tblock[i, :c[i]] = self._slot_pending[i]
            tblock[i, c[i]:c[i] + k] = proposals[i]
            tl[i] = c[i] + k
        with self._lock:
            banks = self._banks
        lkw = ({"banks": banks, "aidx": jnp.asarray(self._aidx),
                "lora_scale": self._lora_cfg.scale} if banks else {})
        tlog, self._cache = _grid_ingest(
            self.params, self._cache, jnp.asarray(tblock),
            jnp.asarray(start), jnp.asarray(tl), self.cfg, s_eff=s_eff,
            **lkw)
        greedy = np.asarray(jnp.argmax(tlog, axis=-1))   # (B, WT)
        self._steps += 1

        round_accepted = 0
        for i in active:
            ci = int(c[i])
            accepted = 0
            while (accepted < k
                   and proposals[i, accepted] == greedy[i, ci - 1 + accepted]):
                accepted += 1
            correction = int(greedy[i, ci - 1 + accepted])
            emitted = [int(t) for t in proposals[i, :accepted]] + [correction]
            sent = 0
            for t in emitted:
                self._emit(i, t)
                sent += 1
                if self._slot_req[i] is None:
                    break
            self.spec_stats.rounds += 1
            self.spec_stats.proposed += k
            # count only acceptances that were EMITTED: matches past a
            # retirement point (budget/eos) are comparisons against the
            # target's post-stream continuation, and counting them would
            # flatter acceptance_rate for exactly the requests that end
            self.spec_stats.accepted += min(accepted, sent)
            round_accepted += min(accepted, sent)
            # a slot retired during emission had its ledgers cleared by
            # _retire_slot → _free_slot_ledgers; only live slots advance
            if self._slot_req[i] is not None:
                self._spec_valid[i] = start[i] + ci
                self._slot_pending[i] = emitted
        self._note_round(round_accepted, len(active) * k)

    def _note_round(self, accepted: int, proposed: int) -> None:
        """Acceptance-rate EWMA → draft-length adaptation (ISSUE 12
        satellite). Grows ``k`` while the draft keeps earning its windows
        (EWMA ≥ 0.8), shrinks it when more than half the proposals are
        wasted draft decodes (EWMA ≤ 0.5); the 0.5–0.8 band is hysteresis.
        At most one ±1 move per ``spec_adapt_every`` rounds, bounded by
        [k_min, k_max] — the bounds also cap how many window-shape
        compiles adaptation can ever trigger."""
        if not proposed:
            return
        rate = accepted / proposed
        self._accept_ewma = (rate if self._accept_ewma is None
                             else 0.8 * self._accept_ewma + 0.2 * rate)
        gauges = telemetry.spec_metrics()
        gauges["accept_rate"].set(self._accept_ewma)
        gauges["draft_len"].set(self.k)
        if self.k_min == self.k_max:
            return
        self._rounds_since_adapt += 1
        if self._rounds_since_adapt < self._adapt_every:
            return
        self._rounds_since_adapt = 0
        if self._accept_ewma >= 0.8 and self.k < self.k_max:
            self.k += 1
        elif self._accept_ewma <= 0.5 and self.k > self.k_min:
            self.k -= 1
        gauges["draft_len"].set(self.k)
