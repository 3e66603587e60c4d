"""Chunked prefill / prefix cache / batched admission: the ISSUE 4 parity
and blast-radius suite.

The load-bearing claim is EQUIVALENCE: chunked prefill (and a prefix-cache
hit mid-prompt) must agree with the whole prompt in one forward through the
model's own contiguous cache — the logits at ``true_len - 1`` to a few ulp
AND the full generated sequence byte for byte against ``generate()`` —
across chunk sizes, position schemes (ALiBi, RoPE, learned), and the int8
KV cache. The resilience interactions are pinned
too: a fault during a prefill chunk retires ONLY the mid-prefill slots
(decoding neighbors keep their exact trajectories), and a hot weight reload
flushes the prefix cache so stale K/V can never serve under new weights.

Everything runs the ``test`` zoo model on CPU in float32 (bitwise claims
need a deterministic backend).
"""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import (
    decode_model,
    generate,
    init_cache,
    prefill,
)
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.serving import engine as engine_mod
from zero_transformer_tpu.serving.slots import POOL_LEAVES, _leaf_name
from zero_transformer_tpu.serving import (
    PagedPrefixIndex,
    PagePool,
    ServeFault,
    ServingChaosMonkey,
    ServingEngine,
)

CACHE_LEN = 48
SAMPLING = SamplingConfig(temperature=0.9, top_k=20)


@pytest.fixture(scope="module", params=["alibi", "rope"])
def cfg(request):
    return model_config(
        "test", dropout=0.0, compute_dtype="float32", position=request.param
    )


@pytest.fixture(scope="module")
def params(cfg):
    # alibi and rope share a param structure (neither adds position params),
    # so one init per cfg keeps the module fast while covering both
    model = Transformer(cfg)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]


@pytest.fixture(scope="module")
def reference(cfg, params):
    model = decode_model(cfg, CACHE_LEN)

    def run(prompt, seed, max_new=8, p=params):
        toks = generate(
            model, p, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.PRNGKey(seed), SAMPLING,
        )
        return jax.device_get(toks)[0].tolist()

    return run


def make_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("page_size", 4)  # divides every chunk used here (4, 8, 16, 48)
    kw.setdefault("sampling", SAMPLING)
    return ServingEngine(cfg, params, **kw)


def _prompt(length, offset=0):
    return [(3 + offset + i) % 250 + 1 for i in range(length)]


def _drive_prefill_only(engine):
    """Admit + run chunk ticks WITHOUT any decode step, so the installed
    per-slot logits are exactly the prefill output."""
    engine._admit()
    ticks = 0
    while engine._prefilling:
        assert engine._prefill_tick()
        ticks += 1
        assert ticks < 1000, "chunked prefill failed to converge"


# ------------------------------------------------------------------- parity


@pytest.mark.parametrize("chunk", [8, 64, CACHE_LEN])
@pytest.mark.parametrize("length", [5, 9, 17, 31])
def test_chunk_prefill_logits_match_oneshot(cfg, params, chunk, length):
    """The logits at ``true_len - 1`` out of chunked prefill equal those of
    the whole prompt in one ``[1, length]`` forward through the model's own
    contiguous cache (``inference.generate.prefill``), for chunks from
    smaller-than-prompt up to (and past — 64 > cache clamps) the cache
    capacity.

    Equality bar: a few ulp, for every chunk size. A chunked ``[S, chunk]``
    window and the one-shot ``[1, length]`` forward are DIFFERENT XLA
    program shapes, and XLA:CPU tiles the attention reductions by shape:
    that is summation order, not an offset or padding fault (either would
    be off by the size of a logit, not of an ulp). The split itself is
    still proven exact where it matters: token-level decode outputs are
    asserted bit-identical for EVERY chunk size in
    ``test_chunked_sequences_match_generate``."""
    model = decode_model(cfg, CACHE_LEN)
    oneshot_logits, _ = prefill(
        model, params, jnp.asarray([_prompt(length)], jnp.int32),
        init_cache(model, 1),
    )
    oneshot = np.asarray(jax.device_get(oneshot_logits))[0]

    chunked = make_engine(cfg, params, prefill_chunk=chunk)
    handle = chunked.submit(_prompt(length), max_new_tokens=4, seed=0)
    _drive_prefill_only(chunked)
    assert handle.status == "running"
    slot = next(
        s for s, a in enumerate(chunked._active) if a is not None
    )
    got = np.asarray(jax.device_get(chunked._last_logits))[slot]
    np.testing.assert_allclose(got, oneshot, rtol=2e-6, atol=1e-6)
    assert got.argmax() == oneshot.argmax()


@pytest.mark.parametrize("chunk", [8, 64, CACHE_LEN])
def test_chunked_sequences_match_generate(cfg, params, reference, chunk):
    """Full-sequence parity under real contention: 5 requests with lengths
    crossing bucket boundaries into 2 slots, chunked engine vs
    single-request generate()."""
    engine = make_engine(cfg, params, prefill_chunk=chunk)
    prompts = [_prompt(n, offset=i) for i, n in enumerate((2, 5, 9, 17, 31))]
    handles = [
        engine.submit(p, max_new_tokens=8, seed=i)
        for i, p in enumerate(prompts)
    ]
    engine.run_until_idle()
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.status == "done", (h.status, h.error)
        assert h.tokens == reference(p, i), f"request {i} (len {len(p)}) garbled"


def test_chunk_window_clamp_near_capacity(cfg, params, reference):
    """A prompt whose final chunk window would overrun the cache: the
    engine clamps the window to ``cache_len - chunk`` and re-sends the
    overlap, whose K/V recompute bit-identically — the trajectory must
    still match generate() exactly."""
    engine = make_engine(cfg, params, n_slots=1, prefill_chunk=16)
    prompt = _prompt(39)  # fills 0/16/32 -> final window clamps to [32..48)
    handle = engine.submit(prompt, max_new_tokens=2, seed=3)
    engine.run_until_idle()
    assert handle.status == "done"
    assert handle.tokens == reference(prompt, 3, max_new=2)


def test_prefix_cache_hit_mid_prompt_is_bit_identical(cfg, params, reference):
    """Second request shares the first's 2-chunk system prefix: admission
    reuses the cached spans (hits > 0, fill lands mid-prompt) and the
    generated sequence is STILL byte-identical to single-request
    generate() — reused K/V equals recomputed K/V."""
    engine = make_engine(
        cfg, params, prefill_chunk=8, prefix_cache_chunks=16
    )
    prefix = _prompt(16, offset=40)
    a = engine.submit(prefix + _prompt(3, offset=7), max_new_tokens=6, seed=0)
    engine.run_until_idle()
    b = engine.submit(prefix + _prompt(4, offset=90), max_new_tokens=6, seed=1)
    engine.run_until_idle()
    assert a.status == "done" and b.status == "done"
    assert b.prefix_hit_tokens == 16  # both prefix chunks reused
    assert engine._prefix_cache.hits == 2
    assert a.tokens == reference(prefix + _prompt(3, offset=7), 0, max_new=6)
    assert b.tokens == reference(prefix + _prompt(4, offset=90), 1, max_new=6)
    snap = engine.metrics_snapshot()
    assert snap["prefix_hits"] == 2 and snap["prefix_hit_rate"] > 0


def test_int8_kv_cache_chunked_parity(params):
    """Chunked prefill through the int8 KV cache (quantized spans + scale
    leaves ride the same slot rows) stays token-identical to generate()."""
    qcfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", kv_cache_dtype="int8"
    )
    model = decode_model(qcfg, CACHE_LEN)
    prompt = _prompt(11)
    ref = jax.device_get(
        generate(model, params, jnp.asarray([prompt], jnp.int32), 8,
                 jax.random.PRNGKey(3), SAMPLING)
    )[0].tolist()
    engine = make_engine(qcfg, params, prefill_chunk=4, prefix_cache_chunks=8)
    handle = engine.submit(prompt, max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert handle.status == "done" and handle.tokens == ref
    # and a prefix hit over int8 spans stays exact too
    again = engine.submit(prompt, max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert again.prefix_hit_tokens > 0
    assert again.tokens == ref


def test_learned_positions_chunked_parity():
    """Learned absolute positions thread the per-slot decode_pos vector
    through chunked prefill too."""
    lcfg = model_config(
        "test", dropout=0.0, compute_dtype="float32", position="learned"
    )
    lparams = Transformer(lcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    model = decode_model(lcfg, lcfg.max_seq_len)
    prompt = _prompt(13)
    ref = jax.device_get(
        generate(model, lparams, jnp.asarray([prompt], jnp.int32), 6,
                 jax.random.PRNGKey(5), SAMPLING)
    )[0].tolist()
    engine = ServingEngine(
        lcfg, lparams, n_slots=2, cache_len=lcfg.max_seq_len,
        sampling=SAMPLING, prefill_chunk=4, page_size=4,
    )
    handle = engine.submit(prompt, max_new_tokens=6, seed=5)
    engine.run_until_idle()
    assert handle.status == "done" and handle.tokens == ref


# -------------------------------------------------- batched admission


def test_batched_admission_single_install_dispatch(cfg, params, reference):
    """N free slots + N queued prompts admit as ONE batch: every prompt
    progresses through the same ticks' chunk dispatches (two slots to a
    dispatch) and a dispatch's completion installs coalesce — and each
    trajectory still matches generate()."""
    engine = make_engine(cfg, params, n_slots=4, prefill_chunk=8)
    prompts = [_prompt(9, offset=i * 11) for i in range(4)]
    handles = [
        engine.submit(p, max_new_tokens=6, seed=i)
        for i, p in enumerate(prompts)
    ]
    _drive_prefill_only(engine)
    # all four admitted together and completed prefill in the SAME two
    # chunk dispatches (9 tokens / chunk 8 -> 2 chunks), not 4x2
    assert engine.stats["prefill_chunks"] == 8  # 4 slots x 2 ticks, batched
    assert all(h.status == "running" for h in handles)
    engine.run_until_idle()
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.tokens == reference(p, i, max_new=6)


SEEDS = [0, 1, 2**31 - 1, 2**31, 2**32, 2**32 + 7, 2**63 - 1,
         -1, -(2**31), -(2**31) - 1, -(2**63)]


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("seed", SEEDS)
def test_host_key_is_prngkey_bit_for_bit(seed, x64):
    """The install's host-built key is what ``PRNGKey(seed)`` gives on the
    device, for every seed a ``Request`` takes: past 2**31 and 2**32, and
    negative, under 32- and 64-bit JAX."""
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
        got = engine_mod._host_key(seed)
    assert got.dtype == np.uint32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_host_key_follows_another_prng_impl():
    """Under another PRNG implementation the key has another width: the
    helper gives that implementation's key, not a threefry one."""
    with jax.default_prng_impl("rbg"):
        want = np.asarray(jax.random.key_data(jax.random.PRNGKey(2**32 + 7)))
        got = engine_mod._host_key(2**32 + 7)
    assert want.shape == (4,)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [2**63, -(2**63) - 1])
def test_host_key_refuses_what_prngkey_refuses(seed):
    with pytest.raises(OverflowError):
        jax.random.PRNGKey(seed)
    with pytest.raises(OverflowError):
        engine_mod._host_key(seed)


@jax.jit
def _install_rows_per_slot_keys(last_logits, gen_mask, rngs, mask, logits_rows, keys):
    m = mask[:, None]
    return (
        jnp.where(m, logits_rows, last_logits),
        jnp.where(m, jnp.zeros_like(gen_mask), gen_mask),
        jnp.where(m, keys, rngs),
    )


def _install_per_slot_device_keys(engine):
    """The install this one replaced, kept as the reference: one device
    key a completed slot, ``jnp.stack``ed over all ``n_slots`` rows."""

    def install(completed, last_rows):
        mask = [False] * engine.n_slots
        keys = [jnp.zeros((2,), jnp.uint32)] * engine.n_slots
        for slot, job in completed:
            mask[slot] = True
            keys[slot] = jax.random.PRNGKey(job.handle.request.seed)
        engine._last_logits, engine._gen_mask, engine._rngs = (
            _install_rows_per_slot_keys(
                engine._last_logits, engine._gen_mask, engine._rngs,
                jnp.asarray(mask, jnp.bool_), last_rows, jnp.stack(keys),
            )
        )
        t_done = engine.now()
        for slot, job in completed:
            del engine._prefilling[slot]
            job.handle.prefill_done_at = t_done
            engine._active[slot] = engine_mod._ActiveSlot(job.handle)
            engine._bank_prefix(slot, job.handle)

    return install


@pytest.mark.parametrize("greedy", [False, True], ids=["sampled", "greedy"])
def test_install_is_one_launch_of_host_arrays_at_128_slots(
    cfg, params, greedy, monkeypatch
):
    """Several requests finish prefill in one tick of a 128-slot engine:
    each ``_install_completed`` makes ONE ``_install_rows`` call, handed
    its mask and keys as numpy arrays, with no device key made on the
    way; the token streams equal the per-slot device-key install's."""
    sampling = SamplingConfig(greedy=True) if greedy else SAMPLING
    prompts = [_prompt(5 + i % 7, offset=13 * i) for i in range(7)]
    seeds = [3, 2**31, 2**32 + 7, 2**63 - 1, -5, 11, 2**40]

    def serve(engine):
        handles = [
            engine.submit(p, max_new_tokens=6, seed=s)
            for p, s in zip(prompts, seeds)
        ]
        engine.run_until_idle()
        assert all(h.status == "done" for h in handles)
        return [h.tokens for h in handles]

    reference = make_engine(cfg, params, n_slots=128, prefill_chunk=8,
                            sampling=sampling)
    reference._install_completed = _install_per_slot_device_keys(reference)
    want = serve(reference)

    engine = make_engine(cfg, params, n_slots=128, prefill_chunk=8,
                         sampling=sampling)
    calls, installs = [], []
    real_rows, real_install = engine_mod._install_rows, engine._install_completed

    def rows(*args):
        calls.append(args)
        return real_rows(*args)

    def install(completed, last_rows):
        installs.append((len(calls), len(completed)))
        real_install(completed, last_rows)
        assert len(calls) == installs[-1][0] + 1

    def no_device_key(*args, **kw):
        raise AssertionError("a device key was made in the install")

    monkeypatch.setattr(engine_mod, "_install_rows", rows)
    monkeypatch.setattr(engine, "_install_completed", install)
    monkeypatch.setattr(jax.random, "PRNGKey", no_device_key)
    monkeypatch.setattr(jnp, "stack", no_device_key)
    got = serve(engine)
    monkeypatch.undo()

    assert got == want
    assert len(installs) == len(calls) == 4  # two rows a chunk dispatch
    assert sorted(n for _, n in installs) == [1, 2, 2, 2]
    for (_, n), args in zip(installs, calls):
        mask, keys = args[4], args[6]
        assert isinstance(mask, np.ndarray) and mask.dtype == np.bool_
        assert isinstance(keys, np.ndarray) and keys.dtype == np.uint32
        assert mask.shape == (128,) and keys.shape == (128, 2)
        assert mask.sum() == n and not keys[~mask].any()


def _chunk_spans(engine):
    return [
        attrs for _, _, name, _, _, attrs in engine.tracer.spans()
        if name == "prefill_chunk"
    ]


@pytest.mark.parametrize("n", [1, 3, 5, 16])
def test_simultaneous_admissions_prefill_two_rows_to_a_dispatch(cfg, params, reference, n):
    """The chunk program computes the rows that prefill: ``n`` prompts
    admitted in one tick of a 16-slot engine go through the ONE
    [PREFILL_ROWS, chunk] program two at a time (1, 2, 3 and 8 dispatches),
    every trajectory still matches generate(), and the dispatch site has
    seen one signature however many slots prefilled."""
    engine = make_engine(cfg, params, n_slots=16, prefill_chunk=8)
    assert engine.prefill_rows == 2
    prompts = [_prompt(4 + i % 5, offset=7 * i) for i in range(n)]
    handles = [
        engine.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)
    ]
    engine.run_until_idle()
    dispatches = -(-n // 2)
    assert _chunk_spans(engine) == [
        {"tick": 0, "slots": min(2, n - 2 * i), "rows": 2} for i in range(dispatches)
    ]
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.status == "done", (h.status, h.error)
        assert h.tokens == reference(p, i, max_new=4), f"request {i} garbled"
    snap = engine.metrics_snapshot()
    assert snap["prefill_rows_live"] == n == snap["prefill_chunks"]
    assert snap["prefill_rows_computed"] == 2 * dispatches
    site = engine._ds_prefill.snapshot()
    assert site["distinct"] == site["max_entries"] == 1 and site["violations"] == 0
    assert site["calls"] == dispatches


def test_one_chunk_program_in_flight_at_a_time(cfg, params):
    """Prefill-only ticks (a long prompt at an idle engine) and a burst's
    later dispatches wait for nothing else: the engine itself waits for
    the chunk program before, so the host runs at most one chunk program
    ahead of the device (the cache is donated, so it is the run-ahead this
    bounds, no longer a stack of pool copies)."""
    engine = make_engine(cfg, params, n_slots=4, prefill_chunk=4)
    program, ready = engine._paged_chunk, []

    def watched(*args):
        out = program(*args)
        ready.append([last.is_ready() for last in previous])
        previous.append(out[1])
        return out

    previous = []
    engine._paged_chunk = watched
    for i in range(3):
        engine.submit(_prompt(13, offset=i), max_new_tokens=2, seed=i)
    _drive_prefill_only(engine)  # 4 chunks x 2 dispatches a tick, no decode
    assert len(ready) == 8 and all(all(r) for r in ready)


def test_one_slot_engine_keeps_its_one_row(cfg, params, reference):
    """The program never has more rows than the engine has slots."""
    engine = make_engine(cfg, params, n_slots=1, prefill_chunk=8)
    assert engine.prefill_rows == 1
    handle = engine.submit(_prompt(11), max_new_tokens=4, seed=2)
    engine.run_until_idle()
    assert handle.tokens == reference(_prompt(11), 2, max_new=4)
    assert [(a["slots"], a["rows"]) for a in _chunk_spans(engine)] == [(1, 1)] * 2


def test_itl_attribution_excludes_prefill_ticks(cfg, params):
    """ITL samples from ticks that ran prefill work are excluded from the
    pure-decode split: with staggered budgets (so retires — and therefore
    admissions — desynchronize), some inter-token gap coincides with a
    neighbor's chunk prefill and itl_decode_ms sees fewer samples."""
    engine = make_engine(cfg, params, prefill_chunk=8)
    for i in range(8):
        engine.submit(_prompt(3, offset=i), max_new_tokens=6 + (i * 5) % 11, seed=i)
    engine.run_until_idle()
    assert len(engine._itl_decode) < len(engine._itl)
    snap = engine.metrics_snapshot()
    assert "itl_decode_ms_p99" in snap and "itl_ms_p99" in snap


# ------------------------------------------------------- resilience paths


@pytest.mark.chaos
def test_prefill_fault_retires_only_the_chunk_slots(cfg, params, reference):
    """A fault during a prefill chunk, raised BEFORE the program is handed
    the cache it donates (the chaos hook), fails ONLY the mid-prefill slot
    (retryably): the decoding neighbor's trajectory is byte-identical to an
    undisturbed run, the breaker never opens, and the freed slot serves a
    retry cleanly."""
    chaos = ServingChaosMonkey([ServeFault("prefill_fault", step=4, duration=1)])
    engine = make_engine(cfg, params, prefill_chunk=4, chaos=chaos)
    neighbor = engine.submit(_prompt(3), max_new_tokens=12, seed=1)
    for _ in range(4):
        engine.step()
    victim = engine.submit(_prompt(13, offset=50), max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert victim.status == "failed" and victim.retryable
    assert "prefill chunk" in victim.error
    assert victim.tokens == []  # failed before its first token
    assert neighbor.status == "done"
    assert neighbor.tokens == reference(_prompt(3), 1, max_new=12)
    assert engine.stats["prefill_faults"] == 1
    assert engine.stats["tick_faults"] == 0
    assert not engine._breaker.open
    retry = engine.submit(_prompt(13, offset=50), max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert retry.status == "done"
    assert retry.tokens == reference(_prompt(13, offset=50), 3)


@pytest.mark.chaos
def test_prefill_fault_in_a_bursts_second_dispatch_keeps_the_last_pool(cfg, params, reference):
    """Three slots prefill in one tick of a 16-slot engine: two dispatches.
    The SECOND faults before it is handed the cache (which the first
    consumed and replaced): every slot still mid-prefill fails retryably (the
    first dispatch's two, whose chunk ran, and the third), the cache the
    engine holds is the one the first dispatch returned (its pools are not
    the pre-tick arrays, and the fault replaced nothing), and the decoding
    neighbours finish byte-identical to an undisturbed run."""
    engine = make_engine(cfg, params, n_slots=16, prefill_chunk=4)
    neighbors = [
        engine.submit(_prompt(3, offset=9 * i), max_new_tokens=12, seed=i)
        for i in range(2)
    ]
    for _ in range(4):
        engine.step()
    assert [(a["slots"], a["rows"]) for a in _chunk_spans(engine)] == [(2, 2)]
    victims = [
        engine.submit(_prompt(13, offset=50 + i), max_new_tokens=8, seed=3 + i)
        for i in range(3)
    ]
    engine._admit()

    def pools():
        return [
            leaf for path, leaf in jax.tree_util.tree_leaves_with_path(engine.slots.cache)
            if _leaf_name(path) in POOL_LEAVES
        ]

    before, program, calls = pools(), engine._paged_chunk, []

    def second_call_faults(*args):
        calls.append(pools())
        if len(calls) == 2:
            raise RuntimeError("injected: the burst's second dispatch")
        return program(*args)

    engine._paged_chunk = second_call_faults
    assert engine._prefill_tick()
    engine._paged_chunk = program
    assert len(calls) == 2 and all(a is b for a, b in zip(calls[0], before))
    after_first = calls[1]
    assert not any(a is b for a, b in zip(after_first, before))
    assert all(a is b for a, b in zip(pools(), after_first))
    assert engine.stats["prefill_rows_computed"] == 2 + 2  # the tick's first dispatch
    assert engine.stats["prefill_faults"] == 1 and not engine._prefilling
    for victim in victims:
        assert victim.status == "failed" and victim.retryable and victim.tokens == []
        assert "prefill chunk" in victim.error
    engine.run_until_idle()
    for i, neighbor in enumerate(neighbors):
        assert neighbor.status == "done"
        assert neighbor.tokens == reference(_prompt(3, offset=9 * i), i, max_new=12)
    assert engine.stats["tick_faults"] == 0 and not engine._breaker.open
    retry = engine.submit(_prompt(13, offset=50), max_new_tokens=8, seed=3)
    engine.run_until_idle()
    assert retry.status == "done"
    assert retry.tokens == reference(_prompt(13, offset=50), 3)
    assert [a["slots"] for a in _chunk_spans(engine)][-4:] == [1, 1, 1, 1]


def _pools(cache):
    return [
        leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
        if _leaf_name(path) in POOL_LEAVES
    ]


def test_chunk_dispatch_consumes_the_cache_it_is_handed(cfg, params, reference):
    """The chunk program is jitted with its cache donated: after every
    dispatch the pools the engine held before it are DELETED (nothing
    pool-sized is kept beside the program's output; the table and cursor
    leaves, which the program overwrites without reading, are not handed
    over at all), the engine holds the program's output, and what is served
    is byte-identical to ``generate()``."""
    engine = make_engine(cfg, params, n_slots=4, prefill_chunk=4)
    prompts = [_prompt(13, offset=7 * i) for i in range(3)]
    handles = [engine.submit(p, max_new_tokens=6, seed=i) for i, p in enumerate(prompts)]
    engine._admit()
    program, handed = engine._paged_chunk, []

    def watched(*args):
        handed.append(_pools(args[2]))
        return program(*args)

    engine._paged_chunk = watched
    held = _pools(engine.slots.cache)
    assert engine._prefill_tick()  # three slots: two dispatches
    assert len(handed) == 2 and all(a is b for a, b in zip(handed[0], held))
    for pools in handed:
        assert len(pools) == 2 and all(leaf.is_deleted() for leaf in pools)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(engine.slots.cache))
    engine._paged_chunk = program
    engine.run_until_idle()
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert h.status == "done", (h.status, h.error)
        assert h.tokens == reference(p, i, max_new=6)
    assert engine.stats["prefill_faults"] == engine.stats["tick_faults"] == 0


@pytest.mark.chaos
def test_prefill_fault_after_the_hand_over_is_the_ticks(cfg, params, reference):
    """A chunk dispatch that raises AFTER its program consumed the donated
    cache: the pools the engine holds are gone, so the fault is the tick's.
    Every decoding and every prefilling request fails retryably, the
    breaker is fed, the device state is rebuilt, ``prefill_faults_escalated``
    counts it, and a retry of each request is byte-identical to an
    undisturbed run."""
    engine = make_engine(cfg, params, n_slots=4, prefill_chunk=4)
    asks = [(_prompt(3, offset=9 * i), 12, i) for i in range(2)]
    asks += [(_prompt(13, offset=50 + i), 8, 3 + i) for i in range(2)]
    decoding = [engine.submit(p, max_new_tokens=n, seed=s) for p, n, s in asks[:2]]
    for _ in range(4):
        engine.step()
    assert all(h.status == "running" and h.tokens for h in decoding)
    prefilling = [engine.submit(p, max_new_tokens=n, seed=s) for p, n, s in asks[2:]]
    program = engine._paged_chunk

    def faults_after_the_call(*args):
        program(*args)
        raise RuntimeError("injected: after the hand-over")

    engine._paged_chunk = faults_after_the_call
    held = _pools(engine.slots.cache)
    assert engine.step()
    engine._paged_chunk = program
    assert all(leaf.is_deleted() for leaf in held)
    for handle in decoding + prefilling:
        assert handle.status == "failed" and handle.retryable
        assert "after the hand-over" in handle.error
    assert all(h.tokens == [] for h in prefilling)
    snap = engine.metrics_snapshot()
    assert snap["tick_faults"] == 1 and snap["prefill_faults_escalated"] == 1
    assert snap["prefill_faults"] == 1
    assert "serve_prefill_faults_escalated_total 1" in engine.prometheus_text()
    events = [(name, fields) for _, name, fields in engine.flight.events()]
    names = [name for name, _ in events]
    assert names.count("engine_rebuilt") == 1 and names.count("tick_fault") == 1
    (fault,) = [f for name, f in events if name == "prefill_fault"]
    assert fault["escalated"] is True and fault["slots_failed"] == 4
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(engine.slots.cache))
    assert not engine._prefilling and engine.active_count == 0
    retries = [engine.submit(p, max_new_tokens=n, seed=s) for p, n, s in asks]
    engine.run_until_idle()
    for (p, n, s), retry in zip(asks, retries):
        assert retry.status == "done", (retry.status, retry.error)
        assert retry.tokens == reference(p, s, max_new=n)
    assert engine.stats["tick_faults"] == 1 and not engine._breaker.open


def test_decode_fault_mid_chunk_fails_prefilling_retryably(cfg, params, reference):
    """A DECODE tick fault while a prompt is mid-chunked-prefill: the
    device rebuild invalidates the half-filled rows too, so the prefilling
    handle fails retryably (never hangs), and the engine serves
    byte-identical output afterwards."""
    chaos = ServingChaosMonkey([ServeFault("tick_fault", step=4, duration=1)])
    engine = make_engine(cfg, params, prefill_chunk=4, chaos=chaos)
    decoding = engine.submit(_prompt(3), max_new_tokens=12, seed=1)
    for _ in range(4):
        engine.step()
    midway = engine.submit(_prompt(17, offset=60), max_new_tokens=8, seed=2)
    engine.step()  # tick 4: chunk 1 of `midway`, then the faulted decode
    assert decoding.status == "failed" and decoding.retryable
    assert midway.status == "failed" and midway.retryable
    engine.run_until_idle()
    after = engine.submit(_prompt(17, offset=60), max_new_tokens=8, seed=2)
    engine.run_until_idle()
    assert after.status == "done"
    assert after.tokens == reference(_prompt(17, offset=60), 2)


def test_reload_mid_prefill_restarts_under_new_weights(cfg, params, reference):
    """A hot reload landing while a prompt is MID-chunked-prefill: the job
    restarts from token zero under the new weights — its output is
    byte-identical to generate() with the new tree, and the spans it banks
    afterwards are pure new-weight K/V (a later shared-prefix request
    reusing them stays exact). Without the restart, positions [0, fill)
    keep old-weight K/V: the output mixes weights and the poisoned spans
    land in the just-flushed prefix cache."""
    params2 = Transformer(cfg).init(
        jax.random.PRNGKey(9), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = make_engine(
        cfg, params, n_slots=1, prefill_chunk=4, prefix_cache_chunks=16
    )
    prompt = _prompt(17, offset=25)  # 5 chunks of 4
    mid = engine.submit(prompt, max_new_tokens=6, seed=2)
    engine._admit()
    engine._prefill_tick()  # chunks 1-2 computed under the OLD weights
    engine._prefill_tick()
    assert engine._prefilling and next(iter(engine._prefilling.values())).fill == 8
    engine.reload_params(params2)
    engine.run_until_idle()  # swap -> restart -> full prefill on params2
    assert mid.status == "done"
    new_ref = reference(prompt, 2, max_new=6, p=params2)
    assert mid.tokens == new_ref and mid.tokens != reference(prompt, 2, max_new=6)
    # the banked spans are new-weight: a shared-prefix follow-up that HITS
    # them must still be byte-identical to generate() on the new tree
    follow = engine.submit(prompt[:12] + _prompt(3, offset=70), max_new_tokens=6, seed=5)
    engine.run_until_idle()
    assert follow.prefix_hit_tokens > 0
    assert follow.tokens == reference(
        prompt[:12] + _prompt(3, offset=70), 5, max_new=6, p=params2
    )


def test_reload_flushes_prefix_cache(cfg, params, reference):
    """Hot weight reload invalidates the prefix cache at the swap tick:
    post-reload shared-prefix requests re-prefill under the NEW weights
    (bit-identical to generate() with them) instead of reusing stale K/V."""
    params2 = Transformer(cfg).init(
        jax.random.PRNGKey(9), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = make_engine(cfg, params, prefill_chunk=8, prefix_cache_chunks=16)
    prefix = _prompt(16, offset=30)
    warm = engine.submit(prefix + _prompt(2), max_new_tokens=4, seed=0)
    engine.run_until_idle()
    assert warm.status == "done" and len(engine._prefix_cache) > 0
    engine.reload_params(params2)
    engine.step()  # the swap tick flushes
    assert len(engine._prefix_cache) == 0
    after = engine.submit(prefix + _prompt(3, offset=80), max_new_tokens=6, seed=4)
    engine.run_until_idle()
    assert after.status == "done"
    assert after.prefix_hit_tokens == 0  # cold again: nothing stale to hit
    new_ref = reference(prefix + _prompt(3, offset=80), 4, max_new=6, p=params2)
    assert after.tokens == new_ref
    assert after.tokens != reference(prefix + _prompt(3, offset=80), 4, max_new=6)


# ------------------------------------------------------------ prefix cache


def test_prefix_cache_lru_unit():
    """Host-side LRU semantics: chunk-aligned keys, last-chunk exclusion,
    eviction order, flush — and the index's one reference per page."""
    pool = PagePool(5)
    pc = PagedPrefixIndex(chunk_tokens=4, capacity=2, pool=pool)
    p1 = list(range(1, 11))  # 10 tokens: chunks at 4 and 8
    fill, entries = pc.lookup(p1)
    assert fill == 0 and entries == [] and pc.misses == 2
    page1, page2, page3 = ((pool.alloc(),) for _ in range(3))
    pc.store_pages(p1, 1, page1)
    pc.store_pages(p1, 2, page2)
    fill, entries = pc.lookup(p1)
    assert fill == 8 and entries == [page1, page2] and pc.hits == 2
    # a full-prompt-aligned lookup never consumes the final chunk: a
    # 8-token prompt sharing p1's first 8 tokens may only reuse chunk 1
    fill, entries = pc.lookup(p1[:8])
    assert fill == 4 and entries == [page1]
    # divergent prefix: chunk 1 differs -> no hit, and a deeper stored
    # chunk alone is unreachable without its predecessors
    other = [99] + p1[1:]
    fill, entries = pc.lookup(other)
    assert fill == 0 and entries == []
    # a duplicate store hands the extra reference straight back
    pool.incref(page1)
    pc.store_pages(p1, 1, page1)
    assert pool.refs[page1[0]] == 1 and pc.stores == 2
    # eviction: capacity 2, storing a third entry evicts the LRU leaf and
    # frees its page
    pc.store_pages(other, 1, page3)
    assert pc.evictions == 1 and len(pc) == 2 and pool.in_use == 2
    assert pc.flush() == 2 and len(pc) == 0 and pool.in_use == 0
