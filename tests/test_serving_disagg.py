"""Disaggregated prefill/decode fleet + live KV migration (ISSUE 12).

Three load-bearing claims:

- **Page spans are bit-exact transferable objects.** Exporting a slot's
  leading pages and importing them elsewhere reproduces every K/V byte
  (int8 scale leaves included) exactly, conserves page refcounts, and the
  imported pages are ordinary CoW-protected pool pages — a post-import
  write to a shared page copies first.
- **Migration replays ZERO tokens.** A stream moved between engines —
  mid-decode, mid-prefill, or as a prefill-role handoff — continues
  byte-identical to the uninterrupted ``generate()`` run, with the
  destination doing no prefill work for the consumed prefix
  (``prefill_chunks == 0`` on a decode import) and the router's
  ``resume_replayed_tokens`` counter pinned at 0 (the recompute fallback
  is what pays O(tokens)).
- **The fleet composes.** A router over one prefill-role + one decode-role
  replica splits requests by phase (DistServe-style) and the client stream
  is byte-identical to a single replica's; ``/admin/migrate`` moves a live
  routed stream with the client none the wiser; the autoscaler acts on the
  scraped load signals through the cordon/drain machinery and aborts a
  scale-down rather than drop a stream.

Chaos scenarios (``make disagg-chaos``): SIGKILL a prefill replica under a
long-prompt flood, and kill a migration mid-transfer — both degrade to the
recompute fallback with ``dropped_streams == 0``.
"""
import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zero_transformer_tpu.config import model_config
from zero_transformer_tpu.inference.generate import decode_model, generate
from zero_transformer_tpu.inference.sampling import SamplingConfig
from zero_transformer_tpu.models import Transformer
from zero_transformer_tpu.serving import (
    PagedKVCache,
    Replica,
    RouterServer,
    ServingEngine,
    ServingServer,
    page_span_from_wire,
    page_span_to_wire,
    pick_decode_replica,
)
from zero_transformer_tpu.serving.resilience import READY

REPO = Path(__file__).resolve().parent.parent
CACHE_LEN = 48
SAMPLING = SamplingConfig(temperature=0.9, top_k=20)
GREEDY = SamplingConfig(greedy=True)


@pytest.fixture(scope="module")
def cfg():
    return model_config("test", dropout=0.0, compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def reference(cfg, params):
    model = decode_model(cfg, CACHE_LEN)

    def run(prompt, seed, max_new=8, sampling=SAMPLING):
        toks = generate(
            model, params, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.PRNGKey(seed), sampling,
        )
        return jax.device_get(toks)[0].tolist()

    return run


def make_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("sampling", SAMPLING)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 4)
    return ServingEngine(cfg, params, **kw)


def direct_shipper(dest_engine, captured):
    """An in-process page shipper: 'ship' by importing straight into the
    destination engine — the engine-level migration proofs need no HTTP."""

    def ship(payload, target, on_done):
        handle = dest_engine.import_stream(payload)
        captured.append(handle)
        if handle.status in ("queued", "running"):
            on_done(None)
        else:
            on_done(handle.error or handle.status)

    return ship


def _prompt(length, offset=0):
    return [(3 + offset + i) % 250 + 1 for i in range(length)]


# ------------------------------------------------- page spans: bitwise moves


def _synthetic_payload(kv, n_blocks, rng):
    """A random page-span payload matching ``kv``'s pool leaf geometry —
    roundtrip fidelity without paying a model forward."""
    leaves = {}
    # the WIRE geometry: per page [(L,) page, KVH, D | 1] under the leaf's
    # per-layer Attention path, whatever layout the pool itself has
    for key, (_, per_page, dtype) in kv.wire_leaves.items():
        dt = np.dtype(dtype)
        if dt.kind == "f":
            arr = rng.standard_normal((n_blocks,) + per_page).astype(dt)
        elif dt.kind == "V":
            # extension dtype (bf16/fp8): FINITE random values — real K/V
            # is finite by invariant (the non-finite guard retires poisoned
            # rows), and XLA canonicalizes NaN payload bits in data
            # movement, so random-bit NaNs would fail bitwise compares that
            # no real transfer ever faces
            arr = rng.standard_normal((n_blocks,) + per_page).astype(dt)
        else:
            info = np.iinfo(dt)
            arr = rng.integers(
                info.min, info.max, size=(n_blocks,) + per_page, dtype=dt
            )
        leaves[key] = arr
    return {"page_size": kv.page_size, "n_blocks": n_blocks,
            "n_tokens": n_blocks * kv.page_size, "leaves": leaves}


@pytest.mark.parametrize("page_size,int8", [(8, False), (8, True), (64, False)])
def test_page_span_roundtrip_bitwise(page_size, int8):
    """Import -> export reproduces every byte exactly, across page sizes
    {8, 64}, float and int8-scale pools, with refcounts conserved and the
    free list fully restored on release."""
    kw = {"dropout": 0.0, "compute_dtype": "float32"}
    if int8:
        kw["kv_cache_dtype"] = "int8"
    pcfg = model_config("test", **kw)
    cache_len = max(2 * page_size, 16)
    n_pages = (cache_len * 2) // page_size + 1
    model = decode_model(pcfg, cache_len, kv_pages=(n_pages, page_size))
    kv = PagedKVCache(model, n_slots=2)
    rng = np.random.default_rng(0)
    payload = _synthetic_payload(kv, n_blocks=2, rng=rng)
    if int8:
        assert any("scale" in k for k in payload["leaves"]), (
            "int8 pools must carry scale leaves"
        )

    free0 = kv.pool.free_count
    slot = kv.acquire()
    assert kv.import_page_span(slot, payload)
    out = kv.export_page_span(slot, payload["n_tokens"])
    assert out["n_blocks"] == payload["n_blocks"]
    for key, arr in payload["leaves"].items():
        got = out["leaves"][key]
        assert got.dtype == arr.dtype and got.shape == arr.shape
        assert np.array_equal(
            got.view(np.uint8), arr.view(np.uint8)
        ), f"leaf {key} not bit-exact"
    # wire codec: bytes -> payload -> bytes, extras preserved
    blob = page_span_to_wire({**out, "kind": "decode", "veto": -1})
    back = page_span_from_wire(blob)
    assert back["kind"] == "decode" and back["veto"] == -1
    for key, arr in out["leaves"].items():
        assert np.array_equal(
            back["leaves"][key].view(np.uint8), arr.view(np.uint8)
        )
    # refcount conservation: the import held exactly one ref per page
    assert kv.pool.free_count == free0 - payload["n_blocks"]
    kv.release([slot])
    assert kv.pool.free_count == free0
    assert all(r == 0 for r in kv.pool.refs[1:])


def test_page_span_ragged_tables_and_trash_padding(cfg):
    """Slots with different span lengths (ragged block tables) move
    independently; the power-of-two gather padding routes through the
    trash page and is sliced off — never exported."""
    model = decode_model(cfg, 32, kv_pages=(17, 4))
    kv = PagedKVCache(model, n_slots=3)
    rng = np.random.default_rng(1)
    payloads = {}
    for slot, blocks in ((0, 1), (1, 3), (2, 5)):
        payloads[slot] = _synthetic_payload(kv, n_blocks=blocks, rng=rng)
        assert kv.import_page_span(slot, payloads[slot])
    for slot, payload in payloads.items():
        out = kv.export_page_span(slot, payload["n_tokens"])
        assert out["n_blocks"] == payload["n_blocks"]
        for key, arr in payload["leaves"].items():
            assert np.array_equal(
                out["leaves"][key].view(np.uint8), arr.view(np.uint8)
            ), (slot, key)
    # exporting more than the slot maps is a loud error, not garbage
    with pytest.raises(ValueError, match="maps"):
        kv.export_page_span(0, 3 * kv.page_size)


def test_imported_pages_are_cow_protected(cfg):
    """The CoW guard fires on a post-import write to a SHARED imported
    page: the writer gets a private copy, the original bytes survive for
    the other holder, and ``cow_copies`` counts it."""
    model = decode_model(cfg, 32, kv_pages=(17, 4))
    kv = PagedKVCache(model, n_slots=2)
    rng = np.random.default_rng(2)
    payload = _synthetic_payload(kv, n_blocks=2, rng=rng)
    slot = kv.acquire()
    assert kv.import_page_span(slot, payload)
    # share the imported pages (what banking them in a prefix index does)
    pages = kv.bank(slot, 2)
    assert all(kv.pool.refs[p] == 2 for p in pages)
    assert kv.cow_copies == 0
    assert kv.cow(slot, 0)  # about to write block 0: must copy first
    assert kv.cow_copies == 1
    assert int(kv.table[slot, 0]) != pages[0], "writer must hold a copy"
    # the copy carries the same bytes, and the original is untouched
    out = kv.export_page_span(slot, payload["n_tokens"])
    for key, arr in payload["leaves"].items():
        assert np.array_equal(
            out["leaves"][key].view(np.uint8), arr.view(np.uint8)
        )
    assert kv.pool.refs[pages[0]] == 1  # only the bank's hold remains


def test_wire_codec_preserves_bfloat16_pools():
    """Extension dtypes (kind 'V') stringify to opaque void — the wire
    format must ship them by NAME or a bf16 serving fleet (the CLI
    default) rejects every import with a dtype mismatch. Found by the
    end-to-end CLI drive; pinned here."""
    import ml_dtypes

    pcfg = model_config("test", dropout=0.0)  # compute_dtype bf16 default
    model = decode_model(pcfg, 32, kv_pages=(17, 4))
    kv = PagedKVCache(model, n_slots=2)
    leaf_dtypes = {
        str(leaf.dtype)
        for _, leaf in jax.tree_util.tree_leaves_with_path(kv.cache)
    }
    assert "bfloat16" in leaf_dtypes, "the default pool must be bf16"
    arr = np.frombuffer(
        np.random.default_rng(3).integers(
            0, 2**16, size=32, dtype=np.uint16
        ).tobytes(),
        dtype=ml_dtypes.bfloat16,
    ).reshape(2, 16)
    blob = page_span_to_wire({"page_size": 4, "n_blocks": 2, "n_tokens": 8,
                              "leaves": {"x": arr}})
    back = page_span_from_wire(blob)
    assert back["leaves"]["x"].dtype == arr.dtype
    assert np.array_equal(
        back["leaves"]["x"].view(np.uint16), arr.view(np.uint16)
    )
    # and a real bf16 pool roundtrips through import/export
    rng = np.random.default_rng(4)
    payload = _synthetic_payload(kv, n_blocks=2, rng=rng)
    slot = kv.acquire()
    wired = page_span_from_wire(page_span_to_wire(payload))
    assert kv.import_page_span(slot, wired)
    out = kv.export_page_span(slot, payload["n_tokens"])
    for key, a in payload["leaves"].items():
        assert np.array_equal(
            out["leaves"][key].view(np.uint8), a.view(np.uint8)
        ), key


def test_wire_codec_rejects_torn_blobs():
    with pytest.raises(ValueError):
        page_span_from_wire(b"not a span")
    blob = page_span_to_wire({
        "page_size": 4, "n_blocks": 1, "n_tokens": 4,
        "leaves": {"x": np.arange(8, dtype=np.int8)},
    })
    with pytest.raises(ValueError):
        page_span_from_wire(blob[:-3])  # truncated mid-buffer


# ----------------------------------------------- migration parity (engines)


def test_decode_migration_is_byte_identical_and_replays_zero(
    cfg, params, reference
):
    """A stream migrated mid-decode continues the EXACT trajectory: the
    concatenated tokens equal the uninterrupted ``generate()`` run, and
    the destination did zero prefill work (the zero-recompute counter)."""
    captured = []
    dst = make_engine(cfg, params, role="decode")
    src = make_engine(
        cfg, params, page_shipper=direct_shipper(dst, captured)
    )
    prompt = _prompt(13)
    expect = reference(prompt, seed=5, max_new=10)
    handle = src.submit(prompt, max_new_tokens=10, seed=5)
    while len(handle.tokens) < 4:
        src.step()
    assert src.request_migration(handle.rid, "peer://dst")
    src.step()
    assert handle.status == "migrated", (handle.status, handle.error)
    assert handle.migrated_to == "peer://dst"
    cont = captured[0]
    dst.run_until_idle()
    assert cont.status == "done", (cont.status, cont.error)
    assert handle.tokens + cont.tokens == expect
    # zero-recompute, counter-asserted: no prefill work on the destination,
    # and the import-replay counter stays 0 (recompute fallback is what
    # would pay O(tokens))
    assert dst.stats["prefill_chunks"] == 0
    assert dst.stats["import_replayed_tokens"] == 0
    assert dst.stats["migrations_in"] == 1
    assert src.stats["migrations_out"] == 1
    assert src.migrations_in_flight == 0
    # continuation id is preserved for cross-tier correlation
    assert cont.rid == handle.rid


def test_midprefill_migration_is_byte_identical(cfg, params, reference):
    """Migrating DURING chunked prefill ships the finished chunks' pages;
    the destination completes the remaining chunks bit-identically (the
    deterministic forward recomputes nothing that moved)."""
    captured = []
    dst = make_engine(cfg, params, role="decode")
    src = make_engine(
        cfg, params, page_shipper=direct_shipper(dst, captured)
    )
    prompt = _prompt(30, offset=4)
    expect = reference(prompt, seed=9, max_new=6)
    handle = src.submit(prompt, max_new_tokens=6, seed=9)
    src.step()  # one 8-token chunk of the 30-token prompt
    assert handle.tokens == []
    assert src.request_migration(handle.rid, "peer://dst")
    src.step()
    assert handle.status == "migrated", (handle.status, handle.error)
    cont = captured[0]
    dst.run_until_idle()
    assert cont.status == "done", (cont.status, cont.error)
    assert cont.tokens == expect
    # the destination only prefilled the REMAINING chunks
    assert 0 < dst.stats["prefill_chunks"] < -(-len(prompt) // 8)


def test_spec_engine_migration_keeps_greedy_identity(cfg, params, reference):
    """Speculative engines migrate too: the veto/rng carry moves, and the
    migrated greedy stream still equals plain ``generate()``."""
    captured = []
    dst = make_engine(cfg, params, role="decode", draft_k=2, sampling=GREEDY)
    src = make_engine(
        cfg, params, draft_k=2, sampling=GREEDY,
        page_shipper=direct_shipper(dst, captured),
    )
    prompt = _prompt(11, offset=7)
    expect = reference(prompt, seed=1, max_new=10, sampling=GREEDY)
    handle = src.submit(prompt, max_new_tokens=10, seed=1)
    while len(handle.tokens) < 3:
        src.step()
    assert src.request_migration(handle.rid, "x")
    src.step()
    assert handle.status == "migrated", (handle.status, handle.error)
    cont = captured[0]
    dst.run_until_idle()
    assert cont.status == "done", (cont.status, cont.error)
    assert (handle.tokens + cont.tokens)[: len(expect)] == expect


def test_draft_k_mismatch_degrades_to_recompute(cfg, params):
    """A fleet-config mismatch (draft_k differs) must reject the import
    RETRYABLY — the source stream fails over to recompute, never corrupts."""
    captured = []
    dst = make_engine(cfg, params, role="decode", draft_k=0)
    src = make_engine(
        cfg, params, draft_k=2, sampling=GREEDY,
        page_shipper=direct_shipper(dst, captured),
    )
    handle = src.submit(_prompt(9), max_new_tokens=6, seed=0)
    while len(handle.tokens) < 2:
        src.step()
    assert src.request_migration(handle.rid, "x")
    src.step()
    assert handle.status == "failed" and handle.retryable, (
        handle.status, handle.error,
    )
    assert src.stats["migration_failures"] == 1
    assert captured[0].status == "rejected" and captured[0].retryable


def test_prefill_handoff_and_role_contracts(cfg, params, reference):
    """A prefill-role engine ships every finished prefill to the decode
    target the request names; the continuation equals ``generate()``. Role
    contracts: prefill-role requires ``prefill_to``; prefill-role rejects
    imports; non-mixed roles require the paged layout."""
    captured = []
    dst = make_engine(cfg, params, role="decode")
    pre = make_engine(
        cfg, params, role="prefill",
        page_shipper=direct_shipper(dst, captured),
    )
    prompt = _prompt(13)
    expect = reference(prompt, seed=3, max_new=8)
    handle = pre.submit(
        prompt, max_new_tokens=8, seed=3, prefill_to="http://dst"
    )
    pre.run_until_idle()
    assert handle.status == "migrated" and handle.migrated_to == "http://dst"
    cont = captured[0]
    dst.run_until_idle()
    assert cont.status == "done" and cont.tokens == expect
    assert pre.stats["prefill_handoffs"] == 1
    assert dst.stats["prefill_chunks"] == 0  # decode never re-prefilled
    # the hand-off is the prefill replica's ``install`` span, and says so
    installs = [s[5] for s in pre.tracer.by_track("engine") if s[2] == "install"]
    assert [(a["slots"], a.get("shipped")) for a in installs] == [(1, 1)]

    bare = pre.submit(prompt, max_new_tokens=4)
    assert bare.status == "rejected" and "prefill_to" in bare.error
    carry = {
        "carry/last_logits": np.zeros((cfg.vocab_size,), np.float32),
        "carry/gen_mask": np.zeros((cfg.vocab_size,), np.bool_),
        "carry/rng": np.zeros((2,), np.uint32),
    }
    back = pre.import_stream({
        "prompt": prompt, "max_new_tokens": 4, "kind": "decode",
        "page_size": 4, "n_blocks": 0, "leaves": carry,
    })
    assert back.status == "rejected" and "prefill-role" in back.error
    # a structurally torn payload (version skew) rejects retryably instead
    # of KeyError-ing the tick thread
    torn = dst.import_stream({"kind": "decode", "leaves": {}})
    assert torn.status == "rejected" and torn.retryable
    assert "bad import payload" in torn.error


def test_migration_failure_dumps_flight_and_fails_retryably(
    cfg, params, tmp_path
):
    """A failed ship finishes the stream retryably (the router's recompute
    fallback key) and dumps the flight recorder for the post-mortem."""

    def broken_shipper(payload, target, on_done):
        on_done("target unreachable (chaos)")

    src = make_engine(
        cfg, params, page_shipper=broken_shipper, obs_dir=str(tmp_path)
    )
    handle = src.submit(_prompt(9), max_new_tokens=6, seed=0)
    while len(handle.tokens) < 2:
        src.step()
    assert src.request_migration(handle.rid, "dead://")
    src.step()
    assert handle.status == "failed" and handle.retryable
    assert "migration failed" in handle.error
    assert src.stats["migration_failures"] == 1
    dumps = list((tmp_path / "flightrec").glob("*migration_failed*"))
    assert dumps, "migration failure must dump the flight recorder"


# ----------------------------------------------------- HTTP fleet (sockets)


class _Tok:
    eos_token_id = None

    def encode(self, text):
        return [1 + (b % 250) for b in text.encode()]

    def decode(self, ids, **kw):
        return "".join(f"<{t}>" for t in ids)

    def convert_ids_to_tokens(self, ids):
        return [f"<{t}>" for t in ids]

    def convert_tokens_to_string(self, toks):
        return "".join(toks)


def _server(cfg, params, role, **kw):
    engine = make_engine(cfg, params, role=role, **kw)
    server = ServingServer(engine, _Tok(), port=0)
    server.start()
    return engine, server


def _sse(port, path, body, timeout=240.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if "text/event-stream" not in (resp.getheader("Content-Type") or ""):
            return resp.status, [], json.loads(resp.read() or b"{}")
        ids, done = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[6:])
            if event.get("done"):
                done = event
                break
            if "token" in event:
                ids.append(int(event["token"]))
        return resp.status, ids, done
    finally:
        conn.close()


def _wait(pred, timeout=120.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def test_router_disaggregates_and_stream_is_byte_identical(
    cfg, params, reference
):
    """The fleet proof: router over {prefill-role, decode-role} splits the
    request by phase — prefill dispatch, page ship, attach — and the
    client's SSE is byte-identical to a single replica's, with ZERO
    replayed tokens. /healthz advertises the roles; the router's /metrics
    mirrors per-replica free_pages."""
    ed, sd = _server(cfg, params, "decode")
    ep, sp = _server(cfg, params, "prefill")
    router = RouterServer(
        [f"127.0.0.1:{sp.port}", f"127.0.0.1:{sd.port}"],
        probe_interval=0.05, chunk_tokens=8, stream_timeout=240.0,
    )
    try:
        router.start()
        assert router.wait_ready(30)
        _wait(
            lambda: any(
                r.role == "prefill" for r in router.registry.routable()
            ),
            msg="role scrape",
        )
        prompt = _prompt(13)
        expect = reference(prompt, seed=3, max_new=8)
        status, ids, done = _sse(
            router.port, "/generate",
            {"tokens": prompt, "max_new_tokens": 8, "seed": 3},
        )
        assert done and done.get("status") == "done", done
        assert ids == expect
        assert router.stats["disagg_dispatches"] == 1
        assert router.stats["resume_replayed_tokens"] == 0
        assert router.stats["dropped_streams"] == 0
        assert ep.stats["prefill_handoffs"] == 1
        assert ed.stats["migrations_in"] == 1 and ed.stats["prefill_chunks"] == 0
        # non-stream JSON rides the classic path to the decode replica
        status, _, doc = _sse(
            router.port, "/generate",
            {"tokens": prompt, "max_new_tokens": 8, "seed": 3,
             "stream": False},
        )
        assert doc.get("status") == "done" and doc.get("tokens") == expect
        # per-replica page-pool mirrors on the router's text exposition
        conn = http.client.HTTPConnection("127.0.0.1", router.port)
        conn.request("GET", "/metrics", headers={"Accept": "text/plain"})
        text = conn.getresponse().read().decode()
        conn.close()
        assert "router_replica_free_pages" in text
        assert "router_replica_migrations_in_flight" in text
    finally:
        router.stop()
        sd.stop()
        sp.stop()


def test_admin_migrate_moves_live_routed_stream_with_zero_replay(
    cfg, params, reference
):
    """Live migration through the fleet: /admin/migrate on the serving
    replica mid-stream; the router follows the ``migrated`` done event
    with an attach hop and the client's stream is byte-identical, zero
    tokens replayed, zero drops."""
    e1, s1 = _server(cfg, params, "mixed")
    e2, s2 = _server(cfg, params, "mixed")
    router = RouterServer(
        [f"127.0.0.1:{s1.port}", f"127.0.0.1:{s2.port}"],
        probe_interval=0.05, chunk_tokens=8, stream_timeout=240.0,
    )
    try:
        router.start()
        assert router.wait_ready(30)
        prompt = _prompt(13)
        expect = reference(prompt, seed=7, max_new=24)
        got = {}

        def client():
            got["r"] = _sse(
                router.port, "/generate",
                {"tokens": prompt, "max_new_tokens": 24, "seed": 7,
                 "request_id": "live-mig-1"},
            )

        t = threading.Thread(target=client, daemon=True)
        t.start()
        src = {}

        def find_src():
            for e, s, other in ((e1, s1, s2), (e2, s2, s1)):
                for act in e._active:
                    if (
                        act is not None
                        and act.handle.rid == "live-mig-1"
                        and len(act.handle.tokens) >= 3
                    ):
                        src["server"], src["target"] = s, other
                        return True
            return False

        _wait(find_src, msg="stream decoding on a replica")
        conn = http.client.HTTPConnection(
            "127.0.0.1", src["server"].port, timeout=30
        )
        conn.request(
            "POST", "/admin/migrate",
            json.dumps({"request_id": "live-mig-1",
                        "target": f"http://127.0.0.1:{src['target'].port}"}),
            {"Content-Type": "application/json"},
        )
        assert conn.getresponse().status == 202
        conn.close()
        t.join(timeout=240)
        assert not t.is_alive(), "migrated stream hung"
        _, ids, done = got["r"]
        assert done and done.get("status") == "done", done
        assert ids == expect
        assert router.stats["migration_resumes"] == 1
        assert router.stats["resume_replayed_tokens"] == 0
        assert router.stats["dropped_streams"] == 0
    finally:
        router.stop()
        s1.stop()
        s2.stop()


# -------------------------------------------------------------- autoscaler


class _StubScaler:
    def __init__(self, urls):
        self.urls = list(urls)
        self.spawned = []
        self.retired = []

    def spawn(self):
        url = self.urls.pop(0)
        self.spawned.append(url)
        return url

    def retire(self, url):
        self.retired.append(url)


def _fake_router(urls, scaler, **kw):
    kw.setdefault("autoscale_interval", 3600.0)  # tick driven by hand
    kw.setdefault("scale_patience", 2)
    router = RouterServer(urls, scaler=scaler, **kw)
    return router


def _prime(router, rid, state=READY, **fields):
    rep = router.registry.get(rid)
    rep.state = state
    for k, v in fields.items():
        setattr(rep, k, v)
    return rep


def test_pick_decode_replica_prefers_pages_then_itl():
    a = Replica(id="a", url="a", host="a", port=1, state=READY,
                free_pages=10, itl_ewma_ms=5.0)
    b = Replica(id="b", url="b", host="b", port=2, state=READY,
                free_pages=40, itl_ewma_ms=9.0)
    c = Replica(id="c", url="c", host="c", port=3, state=READY,
                free_pages=40, itl_ewma_ms=2.0)
    assert pick_decode_replica([a, b, c]).id == "c"
    assert pick_decode_replica([a, b]).id == "b"
    assert pick_decode_replica([]) is None


def test_autoscaler_scales_up_on_queue_and_down_when_idle():
    """Control-loop logic, socket-free: queue pressure past the patience
    window spawns; a sustained idle fleet retires the least-loaded replica
    (never below min_replicas), and every decision lands as an obs event."""
    scaler = _StubScaler(["127.0.0.1:7991"])
    router = _fake_router(
        ["127.0.0.1:7901", "127.0.0.1:7902"], scaler,
        scale_up_queue=4.0, scale_down_active=0, min_replicas=1,
        max_replicas=3,
    )
    for rid in list(router.registry.replicas):
        _prime(router, rid, queue_depth=8)
    router._autoscale_tick()
    assert not scaler.spawned  # patience: one breach is not a trend
    router._autoscale_tick()
    assert scaler.spawned == ["127.0.0.1:7991"]
    assert router.stats["autoscale_ups"] == 1
    assert "127.0.0.1:7991" in router.registry.replicas
    events = [name for _, name, _ in router.flight.events()]
    assert "autoscale_up" in events

    # now idle: everyone empty -> retire back down (the new replica never
    # probed READY, so the victim comes from the primed pool)
    for rid in list(router.registry.replicas):
        if rid != "127.0.0.1:7991":
            _prime(router, rid, queue_depth=0, active_slots=0)
    router._autoscale_tick()
    router._autoscale_tick()
    assert len(scaler.retired) == 1
    assert router.stats["autoscale_downs"] == 1
    assert len(router.registry) == 2
    events = [name for _, name, _ in router.flight.events()]
    assert "autoscale_down" in events


def test_autoscaler_aborts_scale_down_with_live_streams():
    """A victim with relays that will not drain keeps serving: the
    scale-down ABORTS (uncordons) instead of dropping streams."""
    scaler = _StubScaler([])
    router = _fake_router(
        ["127.0.0.1:7903", "127.0.0.1:7904"], scaler,
        scale_drain_timeout_s=0.1, min_replicas=1, migrate_drain=False,
    )
    _prime(router, "127.0.0.1:7903", queue_depth=0, active_slots=0)
    _prime(router, "127.0.0.1:7904", queue_depth=0, active_slots=0,
           active_relays=1)
    victim = router._pick_retire_victim()
    assert victim.id == "127.0.0.1:7903"  # least-loaded
    _prime(router, "127.0.0.1:7903", active_relays=2)
    router._scale_down(router._load_signals())
    assert router.stats["autoscale_aborts"] == 1
    assert not scaler.retired
    assert not router.registry.get("127.0.0.1:7903").cordoned
    events = [name for _, name, _ in router.flight.events()]
    assert "autoscale_down_aborted" in events


def test_autoscaler_never_retires_the_last_of_a_role():
    scaler = _StubScaler([])
    router = _fake_router(
        ["127.0.0.1:7905", "127.0.0.1:7906"], scaler, min_replicas=1,
    )
    _prime(router, "127.0.0.1:7905", role="prefill")
    _prime(router, "127.0.0.1:7906", role="decode")
    assert router._pick_retire_victim() is None


# ------------------------------------------------------------ chaos lane


def _spawn_worker(role, extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [
            sys.executable, str(REPO / "scripts" / "serve_router.py"),
            "--replica-worker", "--port", "0", "--greedy",
            "--cache-len", "64", "--slots", "2", "--prefill-chunk", "8",
            "--page-size", "4", "--role", role,
            *extra,
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=str(REPO),
    )
    return proc


def _worker_port(proc, timeout=300.0):
    deadline = time.monotonic() + timeout
    port: dict = {}

    def read():
        for line in proc.stdout:
            if line.startswith("REPLICA_PORT="):
                port["n"] = int(line.strip().split("=", 1)[1])
                break
        for _ in proc.stdout:
            pass

    threading.Thread(target=read, daemon=True).start()
    while time.monotonic() < deadline and "n" not in port:
        if proc.poll() is not None:
            raise AssertionError(f"worker died rc={proc.returncode}")
        time.sleep(0.1)
    assert "n" in port, "worker never reported its port"
    return port["n"]


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_sigkill_prefill_replica_under_flood(tmp_path):
    """SIGKILL the prefill replica mid-long-prompt-flood: every stream
    finishes token-exact (greedy, resumable token prompts) or ends with a
    retryable terminal event; dropped_streams == 0; the fleet keeps
    serving through the surviving decode-capable replicas."""
    procs = [
        _spawn_worker("prefill"),
        _spawn_worker("mixed"),
        _spawn_worker("mixed", ("--init-seed", "0")),
    ]
    router = None
    try:
        ports = [_worker_port(p) for p in procs]
        router = RouterServer(
            [f"http://127.0.0.1:{p}" for p in ports],
            probe_interval=0.1, chunk_tokens=8, stream_timeout=300,
            max_attempts=4, obs_dir=str(tmp_path),
        )
        router.start()
        _wait(lambda: len(router.registry.routable()) == 3,
              timeout=300, msg="fleet ready")
        _wait(
            lambda: any(
                r.role == "prefill" for r in router.registry.routable()
            ),
            timeout=60, msg="role scrape",
        )
        # warm compiles with one short request per replica class
        _sse(router.port, "/generate",
             {"tokens": [5] * 9, "max_new_tokens": 2}, timeout=600)

        results = []
        lock = threading.Lock()

        def client(i):
            prompt = [(11 + i + j) % 250 + 1 for j in range(24)]  # long
            status, ids, done = _sse(
                router.port, "/generate",
                {"tokens": prompt, "max_new_tokens": 12, "seed": 0},
                timeout=600,
            )
            with lock:
                results.append((prompt, ids, done))

        flood = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(4)
        ]
        for t in flood:
            t.start()
        # kill the prefill replica while the flood is in flight
        time.sleep(0.5)
        os.kill(procs[0].pid, signal.SIGKILL)
        for t in flood:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in flood), "stream HUNG after kill"
        assert len(results) == 4
        done_count = 0
        for prompt, ids, done in results:
            assert done is not None and done.get("done"), (prompt, done)
            if done["status"] == "done":
                done_count += 1
                assert len(ids) == 12
            else:
                assert done.get("retryable") is True, done
        assert done_count >= 1, results
        assert router.stats["dropped_streams"] == 0
        # the fleet keeps serving without its prefill tier
        status, ids, done = _sse(
            router.port, "/generate",
            {"tokens": [1, 3, 5, 7, 9, 11, 13, 15, 17], "max_new_tokens": 4},
            timeout=600,
        )
        assert done and done["status"] == "done"
    finally:
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_kill_migration_mid_transfer(tmp_path):
    """Kill the migration TARGET so the ship dies mid-transfer: the source
    fails the stream retryably, the router's recompute fallback resumes it
    token-exact on a survivor, dropped_streams == 0."""
    procs = [_spawn_worker("mixed") for _ in range(3)]
    router = None
    try:
        ports = [_worker_port(p) for p in procs]
        router = RouterServer(
            [f"http://127.0.0.1:{p}" for p in ports],
            probe_interval=0.1, chunk_tokens=8, stream_timeout=300,
            max_attempts=4, obs_dir=str(tmp_path),
        )
        router.start()
        _wait(lambda: len(router.registry.routable()) == 3,
              timeout=300, msg="fleet ready")
        _sse(router.port, "/generate",
             {"tokens": [5] * 9, "max_new_tokens": 2}, timeout=600)

        got = {}

        def client():
            got["r"] = _sse(
                router.port, "/generate",
                {"tokens": [2, 4, 6, 8, 10, 12, 14, 16, 18],
                 "max_new_tokens": 24, "seed": 0,
                 "request_id": "mid-transfer-1"},
                timeout=600,
            )

        tokens_base = router.stats["tokens_relayed"]
        t = threading.Thread(target=client, daemon=True)
        t.start()
        src = {}

        def find_src():
            # per-replica tokens_relayed only lands at hop END; the live
            # signal is the router's global token counter + the replica
            # holding the active relay
            if router.stats["tokens_relayed"] < tokens_base + 3:
                return False
            for i, port in enumerate(ports):
                rep = router.registry.get(f"127.0.0.1:{port}")
                if rep.active_relays >= 1:
                    src["i"], src["port"] = i, port
                    return True
            return False

        _wait(find_src, timeout=300, msg="stream decoding")
        # the target dies FIRST, then the source is told to migrate there:
        # the ship hits a dead peer mid-transfer and must fall back
        target_i = (src["i"] + 1) % 3
        os.kill(procs[target_i].pid, signal.SIGKILL)
        conn = http.client.HTTPConnection(
            "127.0.0.1", src["port"], timeout=30
        )
        conn.request(
            "POST", "/admin/migrate",
            json.dumps({"request_id": "mid-transfer-1",
                        "target": f"http://127.0.0.1:{ports[target_i]}"}),
            {"Content-Type": "application/json"},
        )
        assert conn.getresponse().status == 202
        conn.close()
        t.join(timeout=600)
        assert not t.is_alive(), "stream hung after mid-transfer kill"
        _, ids, done = got["r"]
        assert done is not None and done.get("done"), done
        # the recompute fallback resumed it: token-exact end to end (greedy)
        assert done["status"] == "done", done
        assert len(ids) == 24
        assert router.stats["dropped_streams"] == 0
        assert router.stats["resume_replayed_tokens"] > 0, (
            "the fallback path replays; that is what the counter proves"
        )
    finally:
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
