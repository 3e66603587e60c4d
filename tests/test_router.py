"""Fleet router: registry state machine, routing policy, failover, reload.

Three tiers of evidence, cheapest first:

- **pure logic** (no sockets): the replica registry's probe-outcome state
  machine (ejection after consecutive failures, exponential-backoff
  re-probe, recovery), and the routing policy (READY over DEGRADED, prefix
  affinity with longest-match, least-loaded tie-break) — the satellite's
  sockets-free unit tests;
- **stub replicas** (HTTP, no jax compute): paced fake replicas from
  ``scripts/serve_router.py`` prove the relay mechanics on the wire —
  X-Request-Id propagation, mid-stream failover that resumes the token
  sequence exactly, graceful degradation to a retryable terminal event,
  rolling reload with zero dropped streams, ejection flight dumps;
- **real engines** (in-process ``ServingServer`` fleet on the test zoo
  model): routed responses byte-identical to single-request ``generate()``,
  greedy mid-stream failover resuming the EXACT trajectory, fleet-wide
  rolling reload under live streams.

The SIGKILL chaos scenario (3 subprocess replicas, one killed mid-load,
then a rolling reload) is slow+chaos-marked: ``make router-chaos``.
"""
import http.client
import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from zero_transformer_tpu.serving.resilience import DEGRADED, DRAINING, READY
from zero_transformer_tpu.serving.router import (
    EJECTED,
    UNKNOWN,
    PrefixAffinity,
    Replica,
    ReplicaRegistry,
    RouterServer,
    chunk_prefix_key,
    pick_replica,
)

REPO = Path(__file__).resolve().parent.parent


def _load_serve_router():
    spec = importlib.util.spec_from_file_location(
        "serve_router", REPO / "scripts" / "serve_router.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------ registry (pure)


def _ids(replicas):
    return [r.id for r in replicas]


def test_registry_probe_failure_ejection_backoff_and_recovery():
    clk = FakeClock()
    reg = ReplicaRegistry(
        ["http://h:1", "http://h:2"], clock=clk, probe_interval=1.0,
        eject_threshold=3, backoff_base_s=2.0, backoff_max_s=8.0,
    )
    r1 = "h:1"
    # never probed: everyone is due immediately, nobody routable
    assert set(_ids(reg.due())) == {"h:1", "h:2"}
    assert reg.routable() == []

    assert reg.observe_probe(r1, True, 200, {"state": READY}) == []
    assert _ids(reg.routable()) == [r1]
    assert reg.get(r1).next_probe_at == 1.0  # probe_interval from now

    # two failures: suspicious but still in rotation (relay failover covers
    # the window); the third consecutive failure ejects
    assert reg.observe_probe(r1, False) == []
    assert reg.observe_probe(r1, False) == []
    assert _ids(reg.routable()) == [r1]
    assert reg.observe_probe(r1, False) == [("ejected", r1)]
    rep = reg.get(r1)
    assert rep.state == EJECTED and rep.backoff_s == 2.0
    assert reg.routable() == []

    # backoff honored: not due again until 2 s elapse, then each failed
    # re-probe doubles the wait up to the cap
    clk.t += 1.0
    assert r1 not in _ids(reg.due())
    clk.t += 1.1
    assert r1 in _ids(reg.due())
    assert reg.observe_probe(r1, False) == []  # still dead
    assert reg.get(r1).backoff_s == 4.0
    reg.observe_probe(r1, False)
    assert reg.get(r1).backoff_s == 8.0
    reg.observe_probe(r1, False)
    assert reg.get(r1).backoff_s == 8.0  # capped

    # one good probe recovers it completely
    events = reg.observe_probe(r1, True, 200, {"state": READY})
    assert ("recovered", r1) in events
    rep = reg.get(r1)
    assert rep.state == READY and rep.backoff_s == 0.0
    assert rep.consecutive_failures == 0
    assert _ids(reg.routable()) == [r1]


def test_registry_honors_replica_lifecycle_states():
    clk = FakeClock()
    reg = ReplicaRegistry(["http://h:1"], clock=clk)
    r1 = "h:1"
    # a 503 that ANSWERS with a draining/stopped body leaves rotation
    # without the ejection machinery (it may come back READY after restart)
    reg.observe_probe(r1, True, 503, {"state": DRAINING})
    assert reg.get(r1).state == DRAINING and reg.routable() == []
    reg.observe_probe(r1, True, 503, {"state": "stopped"})
    assert reg.get(r1).state == DRAINING
    # DEGRADED answers stay routable (deprioritized by the policy)
    reg.observe_probe(r1, True, 503, {"state": DEGRADED})
    assert reg.get(r1).state == DEGRADED and _ids(reg.routable()) == [r1]
    # STARTING is not routable yet
    reg.observe_probe(r1, True, 503, {"state": "starting"})
    assert reg.get(r1).state == UNKNOWN and reg.routable() == []
    # the probe scrapes the admission inputs from the body
    reg.observe_probe(r1, True, 200, {
        "state": READY, "itl_ewma_ms": 3.5, "queue_depth": 7,
        "active_slots": 2, "free_pages": 11,
    })
    rep = reg.get(r1)
    assert rep.itl_ewma_ms == 3.5 and rep.queue_depth == 7
    assert rep.active_slots == 2 and rep.free_pages == 11
    # cordon removes from rotation without touching probed state
    reg.cordon(r1)
    assert reg.routable() == [] and reg.get(r1).state == READY
    reg.uncordon(r1)
    assert _ids(reg.routable()) == [r1]


def test_registry_reregister_replace_does_not_resurrect_stale_cordon():
    """A SIGKILLed process that re-registers under the same id must get a
    FRESH row: inheriting the dead predecessor's cordon (or its tripped
    breaker) would keep the new, healthy process out of rotation forever.
    The training fleet's re-admission path rides exactly this seam."""
    clk = FakeClock()
    reg = ReplicaRegistry(["http://h:1"], clock=clk, eject_threshold=3)
    r1 = "h:1"
    reg.observe_probe(r1, True, 200, {"state": READY})
    # the old incarnation dies: failures trip the breaker, ops cordons it
    for _ in range(3):
        reg.observe_probe(r1, False)
    reg.cordon(r1)
    assert reg.get(r1).state == EJECTED and reg.routable() == []

    # default add() is the idempotent admin path: same id short-circuits,
    # stale state intentionally preserved (re-adding a draining live
    # replica must not silently uncordon it)
    assert reg.add("http://h:1") == r1
    assert reg.get(r1).cordoned and reg.get(r1).state == EJECTED

    # replace=True is the reincarnation path: clean slate
    assert reg.add("http://h:1", replace=True) == r1
    rep = reg.get(r1)
    assert not rep.cordoned
    assert rep.state == UNKNOWN  # fresh rows still earn routability
    assert rep.consecutive_failures == 0
    assert reg.routable() == []  # not routable on trust alone
    reg.observe_probe(r1, True, 200, {"state": READY})
    assert _ids(reg.routable()) == [r1]


def test_registry_probe_for_removed_replica_dropped_not_readded():
    """Late health data from a removed member (probe completing mid-retire,
    a worker heartbeat arriving after eviction) is DROPPED: re-admission is
    an explicit add(), never a side effect of stale telemetry."""
    clk = FakeClock()
    reg = ReplicaRegistry(["http://h:1"], clock=clk)
    r1 = "h:1"
    reg.observe_probe(r1, True, 200, {"state": READY})
    reg.remove(r1)
    assert reg.observe_probe(r1, True, 200, {"state": READY}) == []
    assert r1 not in reg.replicas and reg.routable() == []
    # failure-shaped stragglers equally inert
    assert reg.observe_probe(r1, False) == []
    assert r1 not in reg.replicas


def test_registry_relay_failure_feeds_breaker_and_reprobes_now():
    clk = FakeClock()
    reg = ReplicaRegistry(
        ["http://h:1"], clock=clk, probe_interval=5.0, eject_threshold=3,
    )
    r1 = "h:1"
    reg.observe_probe(r1, True, 200, {"state": READY})
    clk.t = 1.0
    assert reg.due() == []  # next probe is 5 s out
    assert reg.observe_relay_failure(r1, "connect refused") == []
    # the relay failure counts toward ejection AND forces an immediate probe
    assert reg.get(r1).consecutive_failures == 1
    assert _ids(reg.due()) == [r1]
    reg.observe_relay_failure(r1, "x")
    events = reg.observe_relay_failure(r1, "x")
    assert ("ejected", r1) in events


# ------------------------------------------------------------- policy (pure)


def _mk(rid, state=READY, q=0, itl=1.0, slots=0, relays=0):
    r = Replica(id=rid, url=f"http://h/{rid}", host="h", port=1)
    r.state = state
    r.queue_depth = q
    r.itl_ewma_ms = itl
    r.active_slots = slots
    r.active_relays = relays
    return r


def test_chunk_prefix_key_alignment():
    assert chunk_prefix_key(None, 4) is None
    assert chunk_prefix_key([1, 2, 3], 4) is None  # under one chunk
    assert chunk_prefix_key([1, 2, 3, 4], 4) == (1, 2, 3, 4)
    assert chunk_prefix_key([1, 2, 3, 4, 5, 6], 4) == (1, 2, 3, 4)
    assert chunk_prefix_key(list(range(8)), 4) == tuple(range(8))


def test_affinity_longest_match_and_forget():
    aff = PrefixAffinity(chunk_tokens=4, capacity=8)
    prompt_a = [1, 2, 3, 4, 5, 6, 7, 8, 9]  # levels [:8] and [:4]
    aff.record(prompt_a, "r1")
    # shares only the first chunk -> matched at the [:4] level
    assert aff.lookup([1, 2, 3, 4, 99, 98, 97, 96]) == "r1"
    # full deeper prefix -> matched at the [:8] level
    assert aff.lookup(prompt_a) == "r1"
    assert aff.lookup([9, 9, 9, 9]) is None
    # a later route claims every level of ITS prompt (the new replica now
    # holds the shared chunks too) — deepest-first lookup follows it
    aff.record([1, 2, 3, 4, 5, 6, 7, 8], "r2")
    assert aff.lookup(prompt_a) == "r2"
    assert aff.lookup([1, 2, 3, 4, 50]) == "r2"
    # forgetting a replica (ejection, reload) drops all its entries
    aff.forget_replica("r2")
    assert aff.lookup(prompt_a) is None
    # LRU bound holds
    for i in range(20):
        aff.record([i] * 4, "rX")
    assert len(aff) <= 8


def test_pick_replica_policy():
    # empty pool
    assert pick_replica([]) is None
    assert pick_replica([_mk("a", state=EJECTED)]) is None
    # READY beats DEGRADED even when the degraded one is idle
    ready_busy = _mk("busy", q=10, itl=5.0)
    degraded_idle = _mk("idle", state=DEGRADED)
    assert pick_replica([degraded_idle, ready_busy]).id == "busy"
    # DEGRADED serves when it is all there is
    assert pick_replica([degraded_idle]).id == "idle"
    # least-loaded: smaller backlog-x-ITL wins
    slow = _mk("slow", q=2, itl=10.0)
    fast = _mk("fast", q=2, itl=1.0)
    empty = _mk("empty", q=0, itl=10.0)
    assert pick_replica([slow, fast]).id == "fast"
    assert pick_replica([slow, fast, empty]).id == "empty"
    # the router's own in-flight relays count as load
    assert pick_replica([_mk("a", relays=3), _mk("b", relays=1)]).id == "b"
    # affinity wins within the healthy pool even against a lighter replica
    assert pick_replica([slow, fast], affinity_id="slow").id == "slow"
    # ...but never drags traffic to a DEGRADED replica while READY exists
    assert pick_replica(
        [degraded_idle, fast], affinity_id="idle"
    ).id == "fast"
    # deterministic id tie-break
    assert pick_replica([_mk("b"), _mk("a")]).id == "a"


# ----------------------------------------------------- stub fleet (HTTP, fast)


def _sse_post(port, body, headers=None, timeout=30.0):
    """Minimal SSE client: returns (status, events, json_doc). For 200
    streams, events is every parsed ``data:`` event through the done
    event; for JSON responses/rejections, json_doc is the parsed body."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/generate", json.dumps(body),
            {"Content-Type": "application/json", **(headers or {})},
        )
        resp = conn.getresponse()
        ctype = resp.getheader("Content-Type", "")
        if "text/event-stream" not in ctype:
            return resp, [], json.loads(resp.read() or b"{}")
        events = []
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[6:])
            events.append(event)
            if event.get("done"):
                break
        return resp, events, None
    finally:
        conn.close()


def _get(port, path, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


def _wait(pred, timeout=10.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(scope="module")
def serve_router_mod():
    return _load_serve_router()


def _stub_fleet(serve_router_mod, n=2, **kw):
    stubs = [serve_router_mod.StubReplica(**kw).start() for _ in range(n)]
    return stubs


def test_router_rejects_with_retry_after_when_no_replica_routable(
    serve_router_mod,
):
    # the fleet exists but is unreachable (stopped stub = connect refused):
    # requests must fail fast with 503 + Retry-After, not hang
    dead = serve_router_mod.StubReplica().start()
    dead.stop()
    router = RouterServer([dead.url], probe_interval=0.02, max_attempts=2)
    router.start()
    try:
        status, body, headers = _get(router.port, "/healthz")
        assert status == 503
        resp, events, doc = _sse_post(
            router.port, {"tokens": [1, 2, 3], "max_new_tokens": 4}
        )
        assert resp.status == 503
        assert doc["status"] == "rejected"
        assert int(resp.getheader("Retry-After")) >= 1
        assert resp.getheader("X-Request-Id")
        assert router.stats["rejected_no_replica"] == 1
        assert router.stats["dropped_streams"] == 0
    finally:
        router.stop()


def test_router_relays_stream_and_propagates_request_id(serve_router_mod):
    stubs = _stub_fleet(serve_router_mod, n=2, itl_s=0.001)
    router = RouterServer(
        [s.url for s in stubs], probe_interval=0.02, chunk_tokens=4,
    )
    router.start()
    try:
        assert router.wait_ready(5.0)
        tokens = [1, 2, 3, 4, 5]
        resp, events, _ = _sse_post(
            router.port,
            {"tokens": tokens, "max_new_tokens": 6},
            headers={"X-Request-Id": "client-id-042"},
        )
        assert resp.getheader("X-Request-Id") == "client-id-042"
        done = events[-1]
        assert done["done"] and done["status"] == "done"
        assert done["request_id"] == "client-id-042"
        assert done["failovers"] == 0
        ids = [e["token"] for e in events if "token" in e]
        # the stub's arithmetic sequence: base + prompt_len, +1, ...
        assert ids == list(range(1005, 1011))
        assert done["text"] == "".join(f"<{t}>" for t in ids)
        # the replica saw the SAME correlation id the client sent
        served = [s for s in stubs if s.requests]
        assert len(served) == 1
        assert served[0].seen_request_ids == ["client-id-042"]
        # and the span tree names the replica that served the hop
        relay_spans = [
            s for s in router.tracer.by_track("client-id-042")
            if s[2] == "relay"
        ]
        assert len(relay_spans) == 1
        srv_id = f"127.0.0.1:{served[0].port}"
        assert relay_spans[0][5]["replica"] == srv_id
        route_spans = [
            s for s in router.tracer.by_track("client-id-042")
            if s[2] == "route"
        ]
        assert route_spans and route_spans[0][5]["outcome"] == "done"
        assert router.stats["tokens_relayed"] == 6
        assert router.registry.get(srv_id).tokens_relayed == 6

        # JSON (non-stream) relay carries the id and the serving replica
        resp2, _, doc = _sse_post(
            router.port,
            {"tokens": tokens, "max_new_tokens": 3, "stream": False},
        )
        assert resp2.status == 200 and doc["status"] == "done"
        assert doc["tokens"] == list(range(1005, 1008))
        assert doc["replica"] in {f"127.0.0.1:{s.port}" for s in stubs}
    finally:
        router.stop()
        for s in stubs:
            s.stop()


def test_midstream_failover_resumes_token_sequence_on_survivor(
    serve_router_mod,
):
    # replica A dies (connection cut, no done event) after 3 tokens; the
    # router must re-dispatch prompt+generated to B and the CLIENT's stream
    # must be the uninterrupted arithmetic sequence
    a = serve_router_mod.StubReplica(itl_s=0.005, die_after_tokens=3).start()
    b = serve_router_mod.StubReplica(itl_s=0.005).start()
    router = RouterServer(
        [a.url, b.url], probe_interval=0.02, chunk_tokens=4, max_attempts=3,
    )
    # probes off, registry hand-fed: the stub that cuts ONE stream is still
    # alive on /healthz, so a live probe loop would legitimately clear the
    # relay failure's consecutive_failures before the assertions run
    router.start(probe=False)
    try:
        a_id, b_id = f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"
        router.registry.observe_probe(a_id, True, 200, {"state": READY})
        router.registry.observe_probe(b_id, True, 200, {"state": READY})
        tokens = [7, 8, 9, 10]
        router.affinity.record(tokens, a_id)  # deterministic first hop
        resp, events, _ = _sse_post(
            router.port, {"tokens": tokens, "max_new_tokens": 6},
            headers={"X-Request-Id": "failover-1"},
        )
        done = events[-1]
        assert done["done"] and done["status"] == "done", done
        assert done["failovers"] == 1
        ids = [e["token"] for e in events if "token" in e]
        # A emitted 1004..1006 (prompt len 4), died; B resumed with prompt
        # len 7 -> 1007..1009. One continuous sequence, no gap, no repeat.
        assert ids == [1004, 1005, 1006, 1007, 1008, 1009]
        assert done["text"] == "".join(f"<{t}>" for t in ids)
        assert a.died and b.tokens_emitted == 3
        # B's resumed request carried prompt + generated-so-far and the
        # reduced budget
        resumed = b.seen_bodies[-1]
        assert resumed["tokens"] == tokens + [1004, 1005, 1006]
        assert resumed["max_new_tokens"] == 3
        assert b.seen_request_ids[-1] == "failover-1"
        assert router.stats["failovers"] == 1
        assert router.stats["resumed_streams"] == 1
        assert router.stats["dropped_streams"] == 0
        # the failed hop fed the victim's breaker and the affinity moved
        assert router.registry.get(a_id).consecutive_failures >= 1
        assert router.affinity.lookup(tokens) == b_id
        # span tree shows both hops, tagged with their replicas
        relays = [
            s for s in router.tracer.by_track("failover-1")
            if s[2] == "relay"
        ]
        assert [s[5]["replica"] for s in relays] == [a_id, b_id]
        assert relays[0][5]["resumed"] is False
        assert relays[1][5]["resumed"] is True
    finally:
        router.stop()
        for s in (a, b):
            s.stop()


def test_nonresumable_text_prompt_degrades_to_retryable_error(
    serve_router_mod,
):
    # a TEXT prompt cannot be re-dispatched once tokens were relayed (the
    # router never saw the replica's tokenization): the stream must end
    # with a retryable terminal error event — never a hang, never a drop
    a = serve_router_mod.StubReplica(itl_s=0.005, die_after_tokens=2).start()
    b = serve_router_mod.StubReplica(itl_s=0.005).start()
    router = RouterServer(
        [a.url, b.url], probe_interval=0.02, chunk_tokens=4,
    )
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 2, msg="fleet ready")
        # force the doomed replica: no tokens -> no affinity, so pin by load
        a_id = f"127.0.0.1:{a.port}"
        b_id = f"127.0.0.1:{b.port}"
        router.registry.get(b_id).queue_depth = 99  # scraped load, stale ok
        resp, events, _ = _sse_post(
            router.port, {"prompt": "hello world", "max_new_tokens": 6},
        )
        assert a.died
        done = events[-1]
        assert done["done"] and done["status"] == "failed"
        assert done["retryable"] is True
        assert "resumable" in done["error"]
        assert done["failovers"] == 1
        # the two tokens that made it through are in the accumulated text
        assert done["text"] == "".join(
            f"<{e['token']}>" for e in events if "token" in e
        )
        assert router.stats["aborted_streams"] == 1
        assert router.stats["dropped_streams"] == 0
    finally:
        router.stop()
        for s in (a, b):
            s.stop()


def test_connect_failure_fails_over_before_first_token(serve_router_mod):
    # replica believed-READY but gone (crash between probes): the router
    # must fail over silently — the client sees one clean stream
    dead = serve_router_mod.StubReplica().start()
    dead_id = f"127.0.0.1:{dead.port}"
    dead.stop()
    b = serve_router_mod.StubReplica(itl_s=0.002).start()
    b_id = f"127.0.0.1:{b.port}"
    router = RouterServer([dead.url, b.url], chunk_tokens=4, max_attempts=3)
    router.start(probe=False)  # registry state is hand-fed, probes off
    try:
        router.registry.observe_probe(dead_id, True, 200, {"state": READY})
        router.registry.observe_probe(b_id, True, 200, {"state": READY})
        tokens = [5, 5, 5, 5]
        router.affinity.record(tokens, dead_id)
        resp, events, _ = _sse_post(
            router.port, {"tokens": tokens, "max_new_tokens": 4}
        )
        done = events[-1]
        assert done["status"] == "done" and done["failovers"] == 1
        ids = [e["token"] for e in events if "token" in e]
        assert ids == [1004, 1005, 1006, 1007]  # all from B, from token 0
        assert router.stats["resumed_streams"] == 0  # nothing was relayed
        assert router.registry.get(dead_id).consecutive_failures >= 1
    finally:
        router.stop()
        b.stop()  # `dead` was already stopped by the scenario itself


def test_prestream_5xx_fails_over_with_suspicion(serve_router_mod):
    # a replica answering 500 BEFORE any stream bytes is alive-but-broken:
    # the router must silently try the next replica (module docstring's
    # pre-stream promise) and feed the victim's breaker — without
    # forgetting its affinity (its prefix cache is intact)
    sick = serve_router_mod.StubReplica(fail_5xx_requests=2).start()
    sick_id = f"127.0.0.1:{sick.port}"
    b = serve_router_mod.StubReplica(itl_s=0.002).start()
    b_id = f"127.0.0.1:{b.port}"
    router = RouterServer([sick.url, b.url], chunk_tokens=4, max_attempts=3)
    router.start(probe=False)
    try:
        router.registry.observe_probe(sick_id, True, 200, {"state": READY})
        router.registry.observe_probe(b_id, True, 200, {"state": READY})
        tokens = [6, 6, 6, 6]
        other = [9, 9, 9, 9]
        router.affinity.record(tokens, sick_id)
        router.affinity.record(other, sick_id)
        resp, events, _ = _sse_post(
            router.port, {"tokens": tokens, "max_new_tokens": 4}
        )
        done = events[-1]
        assert done["status"] == "done" and done["failovers"] == 1
        ids = [e["token"] for e in events if "token" in e]
        assert ids == [1004, 1005, 1006, 1007]  # served whole by B
        assert router.stats["failovers"] == 1
        assert router.stats["dropped_streams"] == 0
        assert router.registry.get(sick_id).consecutive_failures >= 1
        # the served prompt's affinity moved with the request; but unlike a
        # dead socket, a 5xx answer does NOT forget the replica's OTHER
        # affinities (the replica — and its prefix cache — is alive)
        assert router.affinity.lookup(tokens) == b_id
        assert router.affinity.lookup(other) == sick_id
        # JSON path: `other` is still affine to sick, whose second armed
        # 500 must hit the same retry-elsewhere semantics
        resp2, _, doc = _sse_post(
            router.port,
            {"tokens": other, "max_new_tokens": 3, "stream": False},
        )
        assert resp2.status == 200 and doc["status"] == "done"
        assert doc["replica"] == b_id
        assert router.stats["failovers"] == 2
    finally:
        router.stop()
        sick.stop()
        b.stop()


def test_malformed_numeric_fields_rejected_400_not_dropped(serve_router_mod):
    # a client typo in max_new_tokens must be a clean 400 — never an
    # uncaught ValueError tearing the socket and polluting dropped_streams
    stub = serve_router_mod.StubReplica().start()
    router = RouterServer([stub.url], probe_interval=0.02)
    router.start()
    try:
        assert router.wait_ready(5.0)
        resp, _, doc = _sse_post(
            router.port, {"tokens": [1, 2], "max_new_tokens": "ten"}
        )
        assert resp.status == 400
        assert "max_new_tokens" in doc["error"]
        resp2, _, doc2 = _sse_post(
            router.port,
            {"tokens": [1, 2], "max_new_tokens": 4, "timeout": "soon"},
        )
        assert resp2.status == 400
        assert router.stats["rejected_invalid"] == 2
        assert router.stats["dropped_streams"] == 0
    finally:
        router.stop()
        stub.stop()


def test_retry_after_header_propagates_from_replicas(serve_router_mod):
    # the replica advertises its backoff as an HTTP Retry-After HEADER (no
    # body field): a fleet that is all-draining must surface the largest
    # advertised wait on the router's 503, not a hardcoded 1s
    stubs = _stub_fleet(
        serve_router_mod, n=2, backpressure_retry_after=30.0
    )
    router = RouterServer(
        [s.url for s in stubs], probe_interval=0.02, max_attempts=3,
    )
    router.start()
    try:
        assert router.wait_ready(5.0)  # stubs probe READY, then 503 relays
        resp, _, doc = _sse_post(
            router.port, {"tokens": [1, 2, 3], "max_new_tokens": 4}
        )
        assert resp.status == 503 and doc["status"] == "rejected"
        assert int(resp.getheader("Retry-After")) >= 30
        # stream and JSON paths share the plumbing
        resp2, _, doc2 = _sse_post(
            router.port,
            {"tokens": [1, 2, 3], "max_new_tokens": 4, "stream": False},
        )
        assert resp2.status == 503
        assert int(resp2.getheader("Retry-After")) >= 30
    finally:
        router.stop()
        for s in stubs:
            s.stop()


def test_death_after_last_token_finishes_done_not_failed(serve_router_mod):
    # the replica emits every budgeted token then dies before its done
    # event, with NO retry budget left: the client holds the complete
    # generation, so the terminal event must say done — not push the client
    # into retrying (and regenerating) a finished response
    a = serve_router_mod.StubReplica(itl_s=0.002, die_after_tokens=4).start()
    router = RouterServer([a.url], probe_interval=0.02, max_attempts=1)
    router.start()
    try:
        assert router.wait_ready(5.0)
        resp, events, _ = _sse_post(
            router.port, {"tokens": [1, 2, 3], "max_new_tokens": 4}
        )
        done = events[-1]
        assert done["done"] and done["status"] == "done", done
        assert "error" not in done
        ids = [e["token"] for e in events if "token" in e]
        assert len(ids) == 4 and a.died
        assert router.stats["aborted_streams"] == 0
        assert router.stats["dropped_streams"] == 0
    finally:
        router.stop()
        a.stop()


def test_rolling_reload_under_load_drops_nothing(serve_router_mod):
    stubs = _stub_fleet(serve_router_mod, n=2, itl_s=0.005, slots=4)
    router = RouterServer(
        [s.url for s in stubs], probe_interval=0.02, chunk_tokens=4,
    )
    router.start()
    results = []
    try:
        _wait(lambda: len(router.registry.routable()) == 2, msg="fleet ready")

        def client(i):
            for j in range(3):
                resp, events, doc = _sse_post(
                    router.port,
                    {"tokens": [i, j, 1, 2], "max_new_tokens": 20},
                    timeout=60,
                )
                results.append(events[-1] if events else doc)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        time.sleep(0.05)  # streams in flight
        ok, steps = router.rolling_reload(drain_timeout_s=30.0,
                                          ready_timeout_s=30.0)
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "client hung"
        assert ok, steps
        assert [s["ok"] for s in steps] == [True, True]
        assert all(s.reloads == 1 for s in stubs)
        assert len(results) == 12
        assert all(r.get("status") == "done" for r in results), results
        assert router.stats["dropped_streams"] == 0
        assert router.stats["reload_steps"] == 2
    finally:
        router.stop()
        for s in stubs:
            s.stop()


def test_rolling_reload_refuses_concurrent_runs(serve_router_mod):
    stubs = _stub_fleet(serve_router_mod, n=2, reload_delay_s=0.3)
    router = RouterServer([s.url for s in stubs], probe_interval=0.02)
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 2, msg="fleet ready")
        first: dict = {}

        def run_first():
            first["result"] = router.rolling_reload()

        t = threading.Thread(target=run_first, daemon=True)
        t.start()
        time.sleep(0.1)  # first reload is mid-flight (0.3 s per replica)
        conn = http.client.HTTPConnection("127.0.0.1", router.port, timeout=10)
        conn.request("POST", "/admin/reload", b"{}",
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        assert resp.status == 409 and "in progress" in body["error"]
        with pytest.raises(RuntimeError):
            router.rolling_reload()
        t.join(timeout=30)
        assert first["result"][0] is True
    finally:
        router.stop()
        for s in stubs:
            s.stop()


def test_ejection_dumps_flight_recorder_and_recovers(
    serve_router_mod, tmp_path
):
    stub = serve_router_mod.StubReplica().start()
    port = stub.port
    rid = f"127.0.0.1:{port}"
    router = RouterServer(
        [stub.url], probe_interval=0.02, eject_threshold=3,
        backoff_base_s=0.05, backoff_max_s=0.2, obs_dir=str(tmp_path),
    )
    router.start()
    try:
        assert router.wait_ready(5.0)
        stub.stop()
        _wait(lambda: router.registry.get(rid).state == EJECTED,
              timeout=10, msg="ejection")
        assert router.stats["ejections"] == 1
        dumps = list((tmp_path / "flightrec").glob("*replica_ejected*.json"))
        assert len(dumps) == 1
        doc = json.loads(dumps[0].read_text())
        assert doc["extra"]["replica"] == rid
        assert rid in doc["extra"]["registry"]
        status, body, _ = _get(router.port, "/healthz")
        assert status == 503
        health = json.loads(body)
        assert health["replicas"][rid]["state"] == EJECTED
        # a replacement process on the same address recovers the replica
        # on the next backed-off probe — no operator action needed
        stub2 = serve_router_mod.StubReplica(port=port).start()
        try:
            _wait(lambda: router.registry.get(rid).state == READY,
                  timeout=10, msg="recovery")
            assert router.stats["recoveries"] == 1
            status, _, _ = _get(router.port, "/healthz")
            assert status == 200
        finally:
            stub2.stop()
    finally:
        router.stop()


def test_router_metrics_json_and_prometheus(serve_router_mod):
    stub = serve_router_mod.StubReplica(itl_s=0.001).start()
    router = RouterServer([stub.url], probe_interval=0.02, chunk_tokens=4)
    router.start()
    try:
        assert router.wait_ready(5.0)
        _sse_post(router.port, {"tokens": [1, 2, 3, 4], "max_new_tokens": 2})
        status, body, _ = _get(router.port, "/metrics")
        snap = json.loads(body)
        assert status == 200
        assert snap["requests"] == 1 and snap["tokens_relayed"] == 2
        assert snap["routable_replicas"] == 1
        assert f"127.0.0.1:{stub.port}" in snap["replicas"]
        assert 0.0 <= snap["affinity_hit_rate"] <= 1.0
        status, text, headers = _get(
            router.port, "/metrics", headers={"Accept": "text/plain"}
        )
        assert status == 200
        assert "text/plain" in headers.get("Content-Type", "")
        exposition = text.decode()
        assert "router_requests_total 1" in exposition
        assert "router_tokens_relayed_total 2" in exposition
        assert "router_routable_replicas 1" in exposition
        assert 'router_replica_up{replica="127.0.0.1:' in exposition
    finally:
        router.stop()
        stub.stop()


# ------------------------------------------------- real-engine fleet (jax)


CACHE_LEN = 48


@pytest.fixture(scope="module")
def cfg():
    from zero_transformer_tpu.config import model_config

    return model_config("test", dropout=0.0, compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.models import Transformer

    return Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def reference(cfg, params):
    import jax
    import jax.numpy as jnp

    from zero_transformer_tpu.inference.generate import decode_model, generate
    from zero_transformer_tpu.inference.sampling import SamplingConfig

    model = decode_model(cfg, CACHE_LEN)
    sampling = SamplingConfig(greedy=True)

    def run(prompt, max_new=8, seed=0):
        toks = generate(
            model, params, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.PRNGKey(seed), sampling,
        )
        return jax.device_get(toks)[0].tolist()

    return run


class ByteTokenizer:
    eos_token_id = None

    def encode(self, text):
        return list(text.encode("utf-8"))

    def decode(self, ids, **kw):
        return bytes(int(i) % 256 for i in ids).decode("utf-8", errors="replace")


def _make_replica(cfg, params, chaos=None, reload_source=None):
    from zero_transformer_tpu.inference.sampling import SamplingConfig
    from zero_transformer_tpu.serving import ServingEngine, ServingServer

    engine = ServingEngine(
        cfg, params, n_slots=2, cache_len=CACHE_LEN,
        sampling=SamplingConfig(greedy=True), chaos=chaos,
    )
    server = ServingServer(
        engine, ByteTokenizer(), port=0, reload_source=reload_source
    )
    server.start()
    return server


def test_replica_healthz_carries_router_admission_inputs(cfg, params):
    server = _make_replica(cfg, params)
    try:
        status, body, _ = _get(server.port, "/healthz")
        assert status == 200
        health = json.loads(body)
        # pre-existing fields intact
        for key in ("state", "uptime_s", "reloads", "breaker_open", "slots",
                    "active", "prefilling", "queued"):
            assert key in health, key
        # the router's admission inputs ride the same poll
        assert health["itl_ewma_ms"] == 0.0  # no samples yet
        assert health["queue_depth"] == 0
        assert health["active_slots"] == 0
        # free pool pages: 2 slots x 48 positions at 16 a page
        assert health["free_pages"] == 2 * CACHE_LEN // 16
    finally:
        server.stop()


def test_fleet_parity_and_prefix_affinity(cfg, params, reference):
    servers = [_make_replica(cfg, params) for _ in range(2)]
    urls = [f"http://127.0.0.1:{s.port}" for s in servers]
    router = RouterServer(urls, probe_interval=0.05, chunk_tokens=4)
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 2,
              timeout=15, msg="fleet ready")
        groups = [
            [3, 5, 7, 9, 11, 13],
            [4, 6, 8, 10, 12, 14],
        ]
        tails = [[17, 19], [21, 23], [25, 27]]
        routed_to = {0: set(), 1: set()}
        for g, prefix in enumerate(groups):
            for tail in tails:
                prompt = prefix + tail
                resp, events, _ = _sse_post(
                    router.port,
                    {"tokens": prompt, "max_new_tokens": 8, "seed": 0},
                    timeout=120,
                )
                done = events[-1]
                assert done["status"] == "done", done
                ids = [e["token"] for e in events if "token" in e]
                # routed generation byte-identical to single-request
                # generate() — the fleet adds zero numerical surface
                assert ids == reference(prompt, 8), prompt
                aff = router.affinity.lookup(prompt)
                routed_to[g].add(aff)
        # each group stuck to ONE replica after its first request (the
        # distributed-prefix-cache property), 2 hits per group
        assert all(len(v) == 1 for v in routed_to.values()), routed_to
        assert router.stats["affinity_hits"] == 4
        assert router.stats["failovers"] == 0
        assert router.stats["dropped_streams"] == 0
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_fleet_midstream_failover_resumes_exact_greedy_trajectory(
    cfg, params, reference
):
    from zero_transformer_tpu.serving import ServeFault, ServingChaosMonkey

    # replica A's engine faults one decode tick mid-generation: its stream
    # ends with a retryable failed event after ~2 tokens; the router must
    # resume on B and the CLIENT-visible trajectory must equal the
    # uninterrupted greedy reference exactly
    chaos = ServingChaosMonkey([ServeFault("tick_fault", step=2, duration=1)])
    a = _make_replica(cfg, params, chaos=chaos)
    b = _make_replica(cfg, params)
    a_id, b_id = f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"
    router = RouterServer(
        [f"http://{a_id}", f"http://{b_id}"],
        probe_interval=0.05, chunk_tokens=4, stream_timeout=120,
    )
    router.start()
    try:
        _wait(lambda: len(router.registry.routable()) == 2,
              timeout=15, msg="fleet ready")
        prompt = [9, 11, 13, 15, 17, 19]
        router.affinity.record(prompt, a_id)  # pin the first hop on A
        resp, events, _ = _sse_post(
            router.port,
            {"tokens": prompt, "max_new_tokens": 10, "seed": 0},
            headers={"X-Request-Id": "fleet-failover"},
            timeout=240,
        )
        done = events[-1]
        assert done["status"] == "done", done
        assert done["failovers"] == 1
        ids = [e["token"] for e in events if "token" in e]
        assert ids == reference(prompt, 10)
        assert router.stats["resumed_streams"] == 1
        relays = [
            s for s in router.tracer.by_track("fleet-failover")
            if s[2] == "relay"
        ]
        assert [s[5]["replica"] for s in relays] == [a_id, b_id]
    finally:
        router.stop()
        for s in (a, b):
            s.stop()


def test_fleet_rolling_reload_with_live_stream(cfg, params, reference):
    servers = [
        _make_replica(cfg, params, reload_source=lambda path=None: params)
        for _ in range(2)
    ]
    urls = [f"http://127.0.0.1:{s.port}" for s in servers]
    router = RouterServer(urls, probe_interval=0.05, chunk_tokens=4,
                          stream_timeout=120)
    router.start()
    out: dict = {}
    try:
        _wait(lambda: len(router.registry.routable()) == 2,
              timeout=15, msg="fleet ready")
        prompt = [2, 4, 6, 8]

        def client():
            out["resp"], out["events"], _ = _sse_post(
                router.port,
                {"tokens": prompt, "max_new_tokens": 32, "seed": 0},
                timeout=240,
            )

        t = threading.Thread(target=client, daemon=True)
        t.start()
        time.sleep(0.05)
        ok, steps = router.rolling_reload(drain_timeout_s=120.0,
                                          ready_timeout_s=120.0)
        t.join(timeout=240)
        assert not t.is_alive(), "stream hung across the rolling reload"
        assert ok, steps
        assert [s["ok"] for s in steps] == [True, True]
        done = out["events"][-1]
        assert done["status"] == "done"
        ids = [e["token"] for e in out["events"] if "token" in e]
        assert ids == reference(prompt, 32)
        assert router.stats["dropped_streams"] == 0
        for s in servers:
            _, body, _ = _get(s.port, "/healthz")
            assert json.loads(body)["reloads"] == 1
    finally:
        router.stop()
        for s in servers:
            s.stop()


# ------------------------------------------------------- chaos (subprocess)


def _spawn_worker(extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [
            sys.executable, str(REPO / "scripts" / "serve_router.py"),
            "--replica-worker", "--port", "0", "--greedy",
            "--cache-len", "64", "--slots", "2", "--prefill-chunk", "0",
            *extra,
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=str(REPO),
    )
    return proc


def _worker_port(proc, timeout=240.0):
    deadline = time.monotonic() + timeout
    port: dict = {}

    def read():
        for line in proc.stdout:
            if line.startswith("REPLICA_PORT="):
                port["n"] = int(line.strip().split("=", 1)[1])
                break
        # keep draining so the worker never blocks on a full stdout pipe
        for _ in proc.stdout:
            pass

    t = threading.Thread(target=read, daemon=True)
    t.start()
    while time.monotonic() < deadline and "n" not in port:
        if proc.poll() is not None:
            raise AssertionError(f"worker died rc={proc.returncode}")
        time.sleep(0.1)
    assert "n" in port, "worker never reported its port"
    return port["n"]


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_sigkill_replica_midload_then_rolling_reload(
    cfg, params, reference, tmp_path
):
    """The acceptance chaos proof: a 3-replica fleet under live streaming
    load; one replica is SIGKILLed mid-stream — every in-flight stream
    either resumes on a survivor (token-exact, greedy) or ends with a
    retryable terminal event, zero hangs, zero drops; the dead replica is
    ejected with a flight-recorder dump. Before the kill, a rolling fleet
    reload completes under load with ``dropped_streams == 0``."""
    from zero_transformer_tpu.checkpoint import export_params_msgpack
    from zero_transformer_tpu.parallel.sharding import unbox

    procs = [_spawn_worker() for _ in range(3)]
    router = None
    try:
        ports = [_worker_port(p) for p in procs]
        rids = [f"127.0.0.1:{p}" for p in ports]
        router = RouterServer(
            [f"http://{r}" for r in rids], probe_interval=0.1,
            eject_threshold=3, backoff_base_s=0.2, chunk_tokens=4,
            stream_timeout=300, max_attempts=4, obs_dir=str(tmp_path),
        )
        router.start()
        _wait(lambda: len(router.registry.routable()) == 3,
              timeout=120, msg="3 replicas ready")

        # warm every replica's compile OUTSIDE the measured scenario: three
        # concurrent requests spread by least-loaded (active_relays)
        warm_threads = [
            threading.Thread(
                target=_sse_post,
                args=(router.port,
                      {"tokens": [40 + i] * 4, "max_new_tokens": 2}),
                kwargs={"timeout": 600}, daemon=True,
            )
            for i in range(3)
        ]
        for t in warm_threads:
            t.start()
        for t in warm_threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in warm_threads), "warmup hung"

        # ---- phase 1: rolling reload under live load, zero drops
        ckpt = tmp_path / "reload.msgpack"
        export_params_msgpack(unbox(params), ckpt)
        results: list = []

        def client(prompt, max_new):
            resp, events, doc = _sse_post(
                router.port,
                {"tokens": prompt, "max_new_tokens": max_new, "seed": 0},
                timeout=600,
            )
            results.append((prompt, max_new, events[-1] if events else doc,
                            [e["token"] for e in events if "token" in e]))

        load1 = [
            threading.Thread(
                target=client, args=([2, 4, 6, 8, 10 + i], 16), daemon=True
            )
            for i in range(3)
        ]
        for t in load1:
            t.start()
        time.sleep(0.2)
        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=600)
        conn.request(
            "POST", "/admin/reload",
            json.dumps({"params": str(ckpt), "drain_timeout": 300,
                        "ready_timeout": 300}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        reload_doc = json.loads(resp.read())
        conn.close()
        assert resp.status == 200, reload_doc
        assert reload_doc["reloaded"] is True
        assert reload_doc["dropped_streams"] == 0
        assert [s["ok"] for s in reload_doc["replicas"]] == [True] * 3
        for t in load1:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in load1), "stream hung in reload"
        assert all(r[2].get("status") == "done" for r in results), results
        assert router.stats["dropped_streams"] == 0

        # ---- phase 2: SIGKILL the replica that owns the shared prefix
        results.clear()
        shared = [9, 9, 9, 9]  # affinity concentrates these on one replica
        load2 = [
            threading.Thread(
                target=client, args=(shared + [30 + i], 24), daemon=True
            )
            for i in range(4)
        ]
        load2[0].start()
        _wait(lambda: router.affinity.lookup(shared) is not None,
              timeout=300, msg="first stream routed")
        victim_rid = router.affinity.lookup(shared)
        victim = procs[rids.index(victim_rid)]
        for t in load2[1:]:
            t.start()
        # let streams reach the victim mid-generation, then kill -9
        _wait(
            lambda: router.registry.get(victim_rid).active_relays >= 1
            and router.registry.get(victim_rid).tokens_relayed > 0,
            timeout=300, msg="victim streaming",
        )
        os.kill(victim.pid, signal.SIGKILL)
        for t in load2:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in load2), "stream HUNG after kill"
        assert len(results) == 4
        for prompt, max_new, done, ids in results:
            # token prompts are always resumable: every stream must END,
            # and a completed one must be token-exact vs the uninterrupted
            # greedy reference (same params everywhere after the reload)
            assert done.get("done"), done
            if done["status"] == "done":
                assert ids == reference(prompt, max_new), (prompt, ids)
            else:
                assert done.get("retryable") is True, done
        assert any(r[2]["status"] == "done" for r in results), results
        assert router.stats["failovers"] >= 1
        assert router.stats["dropped_streams"] == 0
        _wait(lambda: router.registry.get(victim_rid).state == EJECTED,
              timeout=60, msg="victim ejected")
        dumps = list((tmp_path / "flightrec").glob("*replica_ejected*"))
        assert dumps, "ejection must dump the flight recorder"
        # the fleet keeps serving on the survivors
        resp, events, _ = _sse_post(
            router.port, {"tokens": [1, 3, 5, 7], "max_new_tokens": 8},
            timeout=600,
        )
        assert events[-1]["status"] == "done"
    finally:
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
