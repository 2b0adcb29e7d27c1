"""The engine runs one decode block ahead when nothing could be seated at the
boundary (ISSUE 32): the decode carry stays on the device, the successor of
the block in flight is dispatched before that block is fetched, and every
request still reads exactly the tokens it would have read.

Tier-1: one tiny engine on the CPU, driven by ``step()`` and by its thread.
The reference is the same engine with ``decode_block=1`` and a slot to spare,
which never runs ahead (``blocks_run_ahead == 0`` is asserted of it).
"""

import threading

import pytest

import jax
import jax.numpy as jnp

import kubetorch_tpu.serve.engine as eng_mod
from kubetorch_tpu.models.llama import LlamaConfig, llama_init
from kubetorch_tpu.serve import GenerationEngine

pytestmark = pytest.mark.level("unit")

BLOCKS = (1, 2, 4)
PROMPTS = ([3, 5, 7], [11, 2, 9, 4], [8, 8, 1], [6, 10, 12, 14, 16])


@pytest.fixture(scope="module")
def dense():
    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    return llama_init(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture(scope="module")
def latent():
    """The second cache kind (``serve.latent_cache``): latent rows, a dense
    layer before the expert layers, the routing tally in the carry."""
    from kubetorch_tpu.models.mla import MlaMoeConfig, mla_moe_init
    cfg = MlaMoeConfig.tiny(dtype=jnp.float32)
    return mla_moe_init(jax.random.PRNGKey(0), cfg), cfg


def _engine(dense, **kw):
    params, cfg = dense
    kw = {"slots": 2, "max_len": 64, "prefill_buckets": (8,), **kw}
    return GenerationEngine(params, cfg, **kw)


def _drive(eng):
    """Step the backlog dry; a 0 is returned only with nothing in flight."""
    n = eng.step()
    while n:
        n = eng.step()
    assert not eng._inflight
    return eng


def _reference(dense, submits, **kw):
    """Tokens and log-probabilities of the one-step engine that never runs
    ahead: a slot more than requests."""
    eng = _engine(dense, slots=len(submits) + 1, decode_block=1, **kw)
    handles = [eng.submit(p, **s) for p, s in submits]
    _drive(eng)
    assert eng.stats().blocks_run_ahead == 0
    return [(h.result(0), h.logprobs) for h in handles]


def _sampling(mode, i):
    return ({} if mode == "greedy"
            else {"temperature": 0.8, "seed": 100 + i})


# -- (a) streams and log-probabilities are today's ----------------------------

@pytest.mark.parametrize("mode", ("greedy", "seeded"))
@pytest.mark.parametrize("k", BLOCKS)
def test_streams_equal_the_one_step_reference(dense, k, mode):
    submits = [(p, {"max_new_tokens": 30 + 3 * i, **_sampling(mode, i)})
               for i, p in enumerate(PROMPTS[:2])]
    want = _reference(dense, submits)
    eng = _engine(dense, decode_block=k)
    handles = [eng.submit(p, **s) for p, s in submits]
    _drive(eng)
    assert eng.stats().blocks_run_ahead > 0         # every slot was seated
    assert [(h.result(0), h.logprobs) for h in handles] == want
    assert all(h.timeline()["blocks_ahead"] > 0 for h in handles)


@pytest.mark.parametrize("mode", ("greedy", "seeded"))
@pytest.mark.parametrize("k", BLOCKS)
def test_slot_retired_under_a_successor_in_flight(dense, k, mode):
    """The short request retires while the successor of its block is already
    queued: that block is garbage for its slot and is dropped, the slot's
    next occupant reads its own exact stream, and so does the neighbour."""
    subs = [(p, {"max_new_tokens": n, **_sampling(mode, i)})
            for i, (p, n) in enumerate(zip(PROMPTS, (4 * k + 2, 50, 21)))]
    want = [_reference(dense, [s])[0] for s in subs]
    eng = _engine(dense, decode_block=k)
    short, long_ = (eng.submit(p, **s) for p, s in subs[:2])
    dropped = False
    while eng.stats().finished_total == 0:
        eng.step()
        # the short one's slot is free with a block in flight that was
        # dispatched while it was seated
        dropped |= (bool(eng._inflight) and eng._inflight[0].ahead
                    and eng.stats().finished_total == 1)
    assert dropped
    late = eng.submit(subs[2][0], **subs[2][1])
    _drive(eng)
    got = [(h.result(0), h.logprobs) for h in (short, long_, late)]
    assert got == want


@pytest.mark.parametrize("mode", ("greedy", "seeded"))
@pytest.mark.parametrize("k", BLOCKS)
def test_latent_cache_streams_equal_the_one_step_reference(latent, k, mode):
    """Run-ahead on and off give the same tokens through the latent cache,
    and the routing tally rides the carry of blocks dispatched ahead."""
    submits = [(p, {"max_new_tokens": 30 + 3 * i, **_sampling(mode, i)})
               for i, p in enumerate(PROMPTS[:2])]
    want = _reference(latent, submits)
    eng = _engine(latent, decode_block=k)
    handles = [eng.submit(p, **s) for p, s in submits]
    _drive(eng)
    assert eng.stats().blocks_run_ahead > 0
    assert [(h.result(0), h.logprobs) for h in handles] == want
    s, cfg = eng.stats(), latent[1]
    assert s.moe_routed_pairs.sum() <= (
        s.decode_steps * 2 * cfg.experts_per_token * cfg.n_moe_layers)
    assert s.moe_routed_pairs.sum() >= (
        (s.tokens_generated - 2) * cfg.experts_per_token * cfg.n_moe_layers)


@pytest.mark.parametrize("k", BLOCKS[1:])
def test_latent_cache_slot_retired_under_a_successor_in_flight(latent, k):
    subs = [(p, {"max_new_tokens": n})
            for p, n in zip(PROMPTS, (4 * k + 2, 50, 21))]
    want = [_reference(latent, [s])[0] for s in subs]
    eng = _engine(latent, decode_block=k)
    short, long_ = (eng.submit(p, **s) for p, s in subs[:2])
    while eng.stats().finished_total == 0:
        eng.step()
    late = eng.submit(subs[2][0], **subs[2][1])
    _drive(eng)
    assert [(h.result(0), h.logprobs) for h in (short, long_, late)] == want


# -- (b) FIFO; a free slot is filled at the next boundary ---------------------

@pytest.mark.parametrize("k", BLOCKS)
def test_no_run_ahead_while_a_slot_is_free_or_a_request_waits(dense, k):
    eng = _engine(dense, slots=4, decode_block=k)
    first = [eng.submit(p, max_new_tokens=20 + i)
             for i, p in enumerate(PROMPTS[:2])]
    for _ in range(3):
        assert eng.step()
        assert not eng._inflight                    # fetched in the same pass
    # it arrives with a slot free: seated, and its first token out, at the
    # very next boundary
    late = eng.submit(PROMPTS[2], max_new_tokens=9)
    eng.step()
    assert late.time_to_first_token() is not None
    _drive(eng)
    assert eng.stats().blocks_run_ahead == 0        # a slot was always free
    want = _reference(dense, [(p, {"max_new_tokens": n}) for p, n in
                              zip(PROMPTS, (20, 21, 9))])
    assert [(h.result(0), h.logprobs) for h in first + [late]] == want

    # more requests than slots: admitted in the order submitted, and no
    # block is dispatched ahead while one of them waits
    eng = _engine(dense, decode_block=k)
    handles = [eng.submit(PROMPTS[i % 4], max_new_tokens=6 + 2 * i)
               for i in range(5)]
    while eng.step():
        if eng._pending:
            assert not any(f.ahead for f in eng._inflight)
    admitted = [h._req.admitted_at for h in handles]
    assert admitted == sorted(admitted)
    assert [len(h.result(0)) for h in handles] == [6, 8, 10, 12, 14]


@pytest.mark.parametrize("family", ("dense", "latent"))
@pytest.mark.parametrize("k", BLOCKS[1:])
def test_arrival_during_a_boundarys_prefill_is_seated_behind_it(
        family, k, request):
    """A slot is free and a prefill of this boundary is running: a request
    that arrives before that prefill's first token is out is seated at the
    same boundary (``_admit_late``), not a block later, the decode block is
    dispatched once, behind it, and every stream reads its own tokens. With
    no slot free the boundary does not wait."""
    model = request.getfixturevalue(family)
    eng = _engine(model, slots=3, decode_block=k)
    early = eng.submit(PROMPTS[0], max_new_tokens=12)
    late, dispatched = [], []
    emit_firsts, dispatch = eng._emit_firsts, eng._dispatch

    def arrives_meanwhile():
        if not late:                # the caller's next request, mid-prefill
            assert not dispatched and eng._seating
            late.append(eng.submit(PROMPTS[1], max_new_tokens=10))
        emit_firsts()

    eng._emit_firsts = arrives_meanwhile
    eng._dispatch = lambda ahead: (dispatched.append(ahead), dispatch(ahead))
    eng.step()
    assert early.time_to_first_token() is not None
    assert late[0].time_to_first_token() is not None    # the same boundary
    assert dispatched == [False] and not eng._pending
    assert late[0]._req in eng._slot_req
    _drive(eng)
    want = _reference(model, [(PROMPTS[0], {"max_new_tokens": 12}),
                              (PROMPTS[1], {"max_new_tokens": 10})])
    assert [(h.result(0), h.logprobs) for h in (early, late[0])] == want

    # no slot left after the admission: nothing could be seated, so the
    # block is dispatched behind the prefill before its first token is read
    eng = _engine(model, slots=1, decode_block=k)
    order = []
    emit_firsts, dispatch = eng._emit_firsts, eng._dispatch
    eng._emit_firsts = lambda: (order.append("firsts"), emit_firsts())
    eng._dispatch = lambda ahead: (order.append("dispatch"), dispatch(ahead))
    only = eng.submit(PROMPTS[2], max_new_tokens=5)
    eng.step()
    assert order[:2] == ["dispatch", "firsts"]
    _drive(eng)
    assert len(only.result(0)) == 5


# -- (c) a boundary hook sees nothing in flight -------------------------------

@pytest.mark.parametrize("k", BLOCKS)
def test_boundary_hook_from_another_thread_sees_nothing_in_flight(dense, k):
    other = llama_init(jax.random.PRNGKey(1), dense[1])
    n_new, long_ = 240, {"max_len": 256}
    subs = [(p, {"max_new_tokens": n_new}) for p in PROMPTS[:2]]
    eng = _engine(dense, decode_block=k, **long_)
    handles = [eng.submit(p, **s) for p, s in subs]
    seen = {}

    def swap():
        seen["inflight"] = len(eng._inflight)
        seen["thread"] = threading.current_thread().name
        seen["made"] = [h._req.generated for h in handles]
        seen["counted"] = eng.stats().tokens_generated
        eng.params = other

    eng.start()
    try:
        while eng.stats().blocks_run_ahead < 2:     # it is running ahead
            assert eng._thread.is_alive()
        eng.at_batch_boundary(swap, timeout=60)
        got = [h.result(timeout=60) for h in handles]
    finally:
        eng.stop()
    assert seen["inflight"] == 0 and seen["thread"] == "kt-gen-engine"
    # every token dispatched before the hook had been emitted and counted
    assert seen["counted"] == sum(seen["made"])
    assert 1 < seen["made"][0] < n_new and seen["made"][0] == seen["made"][1]
    # and the very next token is the new weights': the one-step engine
    # swapped after exactly as many tokens reads the same streams
    ref = _engine(dense, slots=3, decode_block=1, **long_)
    want = [ref.submit(p, **s) for p, s in subs]
    while want[0]._req.generated < seen["made"][0]:
        ref.step()
    ref.at_batch_boundary(lambda: setattr(ref, "params", other))
    _drive(ref)
    assert got == [h.result(0) for h in want]
    unswapped = _reference(dense, subs[:1], **long_)[0][0]
    assert got[0][:seen["made"][0]] == unswapped[:seen["made"][0]]
    assert got[0] != unswapped


def test_boundary_hook_inline_drains_what_step_left(dense):
    eng = _engine(dense, decode_block=2)
    handles = [eng.submit(p, max_new_tokens=20) for p in PROMPTS[:2]]
    while not eng._inflight:
        eng.step()
    seen = eng.at_batch_boundary(
        lambda: (len(eng._inflight), eng.stats().tokens_generated))
    assert seen == (0, sum(h._req.generated for h in handles))
    _drive(eng)


# -- (d) step(), cancel(), stop() ---------------------------------------------

@pytest.mark.parametrize("k", BLOCKS)
def test_step_reaches_zero_only_when_drained(dense, k):
    """A stop sequence retires the only request while its successor block is
    in flight: the pass that retires it still reports work, the next one
    fetches the garbage block and reports none."""
    sub = (PROMPTS[0], {"max_new_tokens": 40})
    want = _reference(dense, [sub])[0][0]
    # the first token that is new to the stream, inside a block run ahead
    stop_at = next(i for i in range(k + 1, 40) if want[i] not in want[:i])
    eng = _engine(dense, slots=1, decode_block=k)
    h = eng.submit(sub[0], max_new_tokens=40, stop=[want[stop_at]])
    returned = []
    while True:
        n = eng.step()
        returned.append((n, len(eng._inflight), eng.stats().active))
        if n == 0:
            break
    assert all(inflight == 0 for n, inflight, _ in returned if n == 0)
    assert (1, 1, 0) in returned                # nothing left but the block
    got = h.result(0)
    assert got == want[:len(got)] and got[-1] == want[stop_at]
    assert eng.stats().decode_steps % k == 0
    assert eng.stats().decode_steps > len(got) - 1  # dispatched, not emitted


@pytest.mark.parametrize("k", BLOCKS)
def test_cancel_with_a_block_in_flight_emits_nothing_more(dense, k):
    eng = _engine(dense, decode_block=k)
    doomed, kept = (eng.submit(p, max_new_tokens=40) for p in PROMPTS[:2])
    while not eng._inflight:
        eng.step()
    made = doomed._req.generated
    assert doomed.cancel() is True
    _drive(eng)
    assert len(doomed.result(0)) == made and doomed.timeline()["cancelled"]
    assert kept.result(0) == _reference(
        dense, [(PROMPTS[1], {"max_new_tokens": 40})])[0][0]


def test_stop_returns_and_a_restart_reads_on_exactly(dense):
    subs = [(p, {"max_new_tokens": 58}) for p in PROMPTS[:2]]
    eng = _engine(dense, decode_block=2)
    handles = [eng.submit(p, **s) for p, s in subs]
    eng.start()
    while eng.stats().blocks_run_ahead < 2:
        assert eng._thread.is_alive()
    eng.stop()
    assert eng._thread is None and not eng._inflight
    # what was dispatched for a seated request was emitted, none of it twice
    eng.start()
    try:
        got = [(h.result(timeout=60), h.logprobs) for h in handles]
    finally:
        eng.stop()
    assert got == _reference(dense, subs)


# -- (e) a device error at the fetch of a block run ahead ---------------------

def test_error_at_the_fetch_fails_the_blocks_requests_only(dense):
    class Lost:
        def __array__(self, *a, **kw):
            raise RuntimeError("device lost the block")

    eng = _engine(dense, decode_block=2)
    handles = [eng.submit(p, max_new_tokens=40) for p in PROMPTS[:2]]
    while not (eng._inflight and eng._inflight[-1].ahead):
        eng.step()
    eng._inflight[-1].toks = Lost()
    _drive(eng)
    for h in handles:
        with pytest.raises(RuntimeError, match="device lost"):
            h.result(0)
    assert eng.stats().active == 0
    # the loop is alive and the slots are clean
    sub = (PROMPTS[2], {"max_new_tokens": 17})
    after = eng.submit(sub[0], **sub[1])
    _drive(eng)
    assert (after.result(0), after.logprobs) == _reference(dense, [sub])[0]


# -- hazard (i): one signature, whether or not a slot changed -----------------

@pytest.mark.parametrize("k", BLOCKS)
def test_run_ahead_compiles_nothing_the_boundary_path_did_not(dense, k):
    """The carry reaches the decode program through ``_patch_carry`` on every
    dispatch, so traffic that runs ahead finds every program compiled by
    traffic that never did (a benchmark's warm-up is such traffic)."""
    step = eng_mod._decode_block if k > 1 else eng_mod._decode_step
    jits = (step, eng_mod._patch_carry, eng_mod._seat_first,
            eng_mod._prefill, eng_mod._splice_slot)
    eng = _engine(dense, decode_block=k, max_len=48)
    for p in PROMPTS[:3]:                       # one at a time: a slot free
        eng.submit(p, max_new_tokens=7)
        _drive(eng)
    assert eng.stats().blocks_run_ahead == 0
    before = [f._cache_size() for f in jits]
    handles = [eng.submit(p, max_new_tokens=30) for p in PROMPTS[:2]]
    _drive(eng)
    assert eng.stats().blocks_run_ahead > 0
    assert [f._cache_size() for f in jits] == before
    assert all(len(h.result(0)) == 30 for h in handles)


# -- a sticky per-slot vector changed from a caller's thread ------------------

def test_adapter_evicted_while_running_ahead_reaches_the_next_block(dense):
    """``unregister_adapter`` repoints a decoding slot at the base model from
    the caller's thread: the next block dispatched decodes it there, a block
    run ahead included and its position kept, and the stream is the one-step
    engine's that evicted after as many tokens had been dispatched."""
    from kubetorch_tpu.models.lora import LoraConfig, lora_init
    params, _cfg = dense
    lcfg = LoraConfig(rank=4, targets=("wq", "wv"))
    adapter = lora_init(jax.random.PRNGKey(7), params, lcfg)
    adapter["layers"] = {
        name: (v if name.endswith("__a") else 0.05 * jax.random.normal(
            jax.random.PRNGKey(i), v.shape, v.dtype))
        for i, (name, v) in enumerate(sorted(adapter["layers"].items()))}
    k = 2

    def serve(eng, evict_after):
        aid = eng.register_adapter(adapter, lcfg)
        handles = [eng.submit(PROMPTS[0], max_new_tokens=30, adapter_id=aid),
                   eng.submit(PROMPTS[1], max_new_tokens=30)]
        while (handles[0]._req.generated
               + eng.decode_block * len(eng._inflight)) < evict_after:
            eng.step()
        ahead = len(eng._inflight)
        dispatched = handles[0]._req.generated + eng.decode_block * ahead
        eng.unregister_adapter(aid)
        _drive(eng)
        return dispatched, ahead, [(h.result(0), h.logprobs) for h in handles]

    eng = _engine(dense, decode_block=k)
    dispatched, ahead, got = serve(eng, 1 + 4 * k)
    assert ahead == 1 and eng.stats().blocks_run_ahead > 0
    _, ahead, want = serve(_engine(dense, slots=3, decode_block=1), dispatched)
    assert ahead == 0 and got == want
    base = _reference(dense, [(PROMPTS[0], {"max_new_tokens": 30})])[0]
    assert got[0] != base                       # the adapter did steer it
