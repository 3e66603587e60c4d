"""Recurrent state beside the K/V pages (``serving/slots.py``'s third class
of cache leaf) and the decode state-update kernel (``ops/pallas/
ssm_update.py``): page operations leave state leaves alone, a released slot
is reusable, and the kernel, in interpret mode, is its plain ``jax.numpy``
twin: the new state to 2 float32 ulps at its own scale (the same operations
in the same order; a compiler may contract a multiply-add in one and not the
other), ``y`` to 4 (the lane reduction sums in another order), and a row
that does not decode to the last bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import decode_model
from zero_transformer_tpu.ops.pallas import ssm_update as su
from zero_transformer_tpu.serving import slots as slots_mod
from zero_transformer_tpu.serving.slots import PagedKVCache, STATE_LEAVES


@pytest.fixture()
def kv():
    cfg = model_config("granite_hybrid_test", param_dtype="float32", compute_dtype="float32")
    cache = PagedKVCache(decode_model(cfg, 32, kv_pages=(17, 4)), n_slots=3)
    # every state value distinct, so a move of any of them would show
    cache.cache = {
        k: (jnp.arange(v.size, dtype=jnp.float32).reshape(v.shape).astype(v.dtype)
            if k in STATE_LEAVES else v)
        for k, v in cache.cache.items()
    }
    return cache


def _state(kv):
    return {k: np.asarray(v) for k, v in kv.cache.items() if k in STATE_LEAVES}


def test_state_leaves_are_a_class_of_their_own(kv):
    assert set(STATE_LEAVES) == {"ssm_state", "conv_state"}
    assert not set(STATE_LEAVES) & (set(slots_mod.POOL_LEAVES) | set(slots_mod.INDEX_LEAVES))
    # stacked over the mamba layers, a row a SLOT, no page or position axis
    assert kv.cache["ssm_state"].shape == (6, 3, 4, 32, 16)
    assert kv.cache["conv_state"].shape == (6, 3, 3 * 160)
    assert kv.state_pool_bytes == 6 * 3 * (4 * 32 * 16 + 3 * 160) * 4
    assert not any("state" in key for key in kv.wire_leaves)


@pytest.mark.parametrize("op", ["copy_page", "gather_pages", "release_acquire",
                                "reset_slot_pages", "set_cursor", "sync_tables"])
def test_page_and_slot_operations_leave_the_state_alone(kv, op):
    before = _state(kv)
    slot = kv.acquire()
    assert kv.ensure(slot, 10)
    if op == "copy_page":
        kv.pool.incref([int(kv.table[slot, 0])])  # shared: the write copies it
        assert kv.cow(slot, 0) and kv.cow_copies == 1
    elif op == "gather_pages":
        out = slots_mod._gather_pages_impl(kv.cache, jnp.asarray([1, 2], jnp.int32))
        assert out and not any("state" in key for key in out)
    elif op == "release_acquire":
        kv.release([slot])
        assert kv.acquire() is not None  # reusable: the chunk program zeroes on read
    elif op == "reset_slot_pages":
        kv.reset_slot_pages(slot)
    elif op == "set_cursor":
        kv.set_cursor(slot, 7)
    else:
        kv.sync_tables()
    after = _state(kv)
    assert all(np.array_equal(before[k], after[k]) for k in STATE_LEAVES)


def test_a_span_of_a_model_with_state_is_refused(kv):
    slot = kv.acquire()
    kv.ensure(slot, 8)
    with pytest.raises(ValueError, match="export_page_span is refused"):
        kv.export_page_span(slot, 8)
    with pytest.raises(ValueError, match="import_page_span is refused"):
        kv.import_page_span(slot, {})


# ---- the kernel -------------------------------------------------------------


def _inputs(S, H, P, N, L=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (S, H, P, N) if L is None else (L, S, H, P, N)
    state = jax.random.normal(ks[0], shape)
    x = jax.random.normal(ks[1], (S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (S, H)))
    A = -jnp.exp(jax.random.normal(ks[3], (H,)))
    Bm, Cm = jax.random.normal(ks[4], (S, N)), jax.random.normal(ks[5], (S, N))
    D = jax.random.normal(ks[6], (H,))
    live = jax.random.bernoulli(ks[7], 0.6, (S,))
    return state, x, dt, A, Bm, Cm, D, live


def test_gate():
    ok = dict(heads=64, head_dim=64, d_state=128)
    assert not su.supported(**ok)  # a CPU, no interpret mode asked for
    assert su.supported(**ok, interpret=True)
    assert not su.supported(**ok, dtype=jnp.bfloat16, interpret=True)
    assert su.head_block(64, 64, 128) == 32  # 1 MiB tiles
    assert su.head_block(4, 32, 16) == 4 and su.head_block(24, 64, 128) == 24


@pytest.mark.parametrize("S,H,P,N,L", [
    (3, 4, 32, 16, None), (4, 8, 8, 128, 3), (2, 64, 64, 128, 2), (5, 16, 16, 128, None)],
    ids=["test_size", "stacked", "published_widths", "odd_rows"])
def test_interpret_kernel_is_the_jnp_path_in_place(S, H, P, N, L):
    state, x, dt, A, Bm, Cm, D, live = _inputs(S, H, P, N, L, seed=S)
    layer = None if L is None else jnp.int32(L - 1)
    y_ref, new_ref = su.ssm_update_reference(state, x, dt, A, Bm, Cm, D, live, layer)
    y, new = jax.jit(
        lambda st, lyr: su.ssm_update(st, x, dt, A, Bm, Cm, D, live, lyr, interpret=True),
        donate_argnums=(0,),
    )(state + 0.0, layer)
    assert new.shape == state.shape

    def ulps(a, b):
        return float(jnp.max(jnp.abs(a - b))) / np.spacing(np.float32(np.max(np.abs(b))))

    assert ulps(new, new_ref) <= 2 and ulps(y, y_ref) <= 4
    rows = np.asarray(live)
    at = (slice(None),) if L is None else (L - 1,)
    assert np.array_equal(np.asarray(new)[at][~rows], np.asarray(state)[at][~rows])
    assert not np.any(np.asarray(y)[~rows])
    if L is not None:  # the other layers of the stack: untouched
        assert bool(jnp.all(new[: L - 1] == state[: L - 1]))


def test_every_row_is_live_without_a_mask():
    state, x, dt, A, Bm, Cm, D, _ = _inputs(3, 4, 8, 128, seed=11)
    y_ref, new_ref = su.ssm_update_reference(state, x, dt, A, Bm, Cm, D)
    y, new = su.ssm_update(state, x, dt, A, Bm, Cm, D, interpret=True)
    assert float(jnp.max(jnp.abs(new - new_ref))) < 1e-5 < float(jnp.max(jnp.abs(new - state)))
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4
