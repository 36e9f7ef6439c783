"""Fleet control-plane tier (PR 17): prefix advertisement digests,
affinity routing, autoscaler hysteresis, drain ledger, the wire
protocol, and the router's zero-loss re-admission paths.

Two test families:

  * pure/fake — digest math, AffinityIndex, Autoscaler, DrainLedger,
    wire framing, plus FleetRouter driven by in-process FAKE replicas
    that speak the wire protocol with a deterministic token function
    (tok(prompt, p) is pure in (prompt, position) — the counter-based
    sampling property, minus jax), so routing/death/deadline semantics
    are tested in milliseconds;
  * jax — a tiny real decoder proves the end-to-end properties the
    fakes cannot: drain handoff and death rebuild re-admission are
    BIT-IDENTICAL to an uninterrupted decode (ci/check_fleet.sh gates
    the same properties cross-process).
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import decoding as dec, fleet
from mxnet_tpu.decoding.blocks import BlockAllocator
from mxnet_tpu.decoding.prefix import PrefixCache, page_digests
from mxnet_tpu.serving import ModelServer
from mxnet_tpu.serving.batcher import (DeadlineExceededError,
                                       ServerClosedError, ServingError)


# ------------------------------------------------------ digest chain
def test_page_digests_chain_and_alignment():
    toks = list(range(1, 13))              # 3 full pages of 4
    d3 = page_digests(toks, 4)
    assert len(d3) == 3
    assert all(isinstance(e, str) and len(e) == 16 for e in d3)
    # partial trailing page is ignored
    assert page_digests(toks + [99], 4) == d3
    # a longer prompt extends the chain without rewriting it:
    # digest equality IS prefix equality
    d4 = page_digests(toks + [13, 14, 15, 16], 4)
    assert d4[:3] == d3 and len(d4) == 4
    # changing ONE early token changes every digest from that page on
    other = page_digests([7] + toks[1:], 4)
    assert all(a != b for a, b in zip(other, d3))
    # same tokens, different page size: different chain
    assert page_digests(toks, 2)[1] != d3[0]
    assert page_digests([], 4) == []


def test_cached_prefixes_round_trip_and_cover():
    a = BlockAllocator(32, 4)
    c = PrefixCache(a)
    toks = list(range(2, 14))              # 3 pages
    pages = a.alloc(3)
    c.insert(toks, pages)
    adv = c.cached_prefixes()
    # JSON round-trip (the heartbeat payload) is lossless
    assert json.loads(json.dumps(adv)) == adv
    # every page-aligned prefix of the inserted prompt is advertised —
    # exactly what the router matches page_digests(prompt) against
    assert set(page_digests(toks, 4)) <= set(adv)
    assert set(page_digests(toks + [50, 51, 52, 53], 4)) - set(adv)
    # the cap keeps the hottest entries
    assert c.cached_prefixes(max_entries=2) != []
    assert len(c.cached_prefixes(max_entries=2)) == 2
    a.free(pages)


def test_cache_digest_tracks_content_not_stamps():
    a = BlockAllocator(32, 4)
    c = PrefixCache(a)
    empty = c.cache_digest()
    pages = a.alloc(2)
    c.insert(list(range(8)), pages)
    d1 = c.cache_digest()
    assert d1 != empty
    # a read (stamp churn) must not change the digest — heartbeats
    # only re-advertise when content changes
    got, _ = c.match(list(range(8)) + [77], max_pages=2)
    a.free(got)
    assert c.cache_digest() == d1
    a.free(pages)


# --------------------------------------------------------- affinity
def test_affinity_longest_prefix_wins():
    idx = fleet.AffinityIndex(4)
    prompt = list(range(16))               # 4 pages
    d = page_digests(prompt, 4)
    idx.update("r0", d[:1])                # covers 1 page
    idx.update("r1", d[:3])                # covers 3 pages
    idx.update("r2", page_digests([9] * 16, 4))  # covers nothing
    rid, cover = idx.best(prompt, ["r0", "r1", "r2"])
    assert (rid, cover) == ("r1", 3)
    # candidates filter applies (r1 draining/dead -> r0 wins)
    rid, cover = idx.best(prompt, ["r0", "r2"])
    assert (rid, cover) == ("r0", 1)
    # coverage must be a LEADING run: advertising pages 2-3 without
    # page 1 covers nothing (the replica cannot skip prefill mid-way)
    idx.update("r3", d[1:])
    assert idx.best(prompt, ["r3"]) == (None, 0)
    idx.remove("r1")
    assert idx.advertised("r1") == set()


def test_affinity_no_cover_returns_none():
    idx = fleet.AffinityIndex(4)
    idx.update("r0", [])
    assert idx.best(list(range(8)), ["r0"]) == (None, 0)
    # short prompt (under one page) can never have affinity
    idx.update("r0", page_digests(list(range(8)), 4))
    assert idx.best([1, 2], ["r0"]) == (None, 0)


# -------------------------------------------------------- autoscale
def test_autoscaler_patience_and_hysteresis():
    a = fleet.Autoscaler(min_replicas=1, max_replicas=4,
                         queue_high=8, queue_low=1, patience=3)
    # needs `patience` CONSECUTIVE hot observations
    assert a.observe(10, 2) == 0
    assert a.observe(10, 2) == 0
    assert a.observe(10, 2) == 1           # third strike: grow
    assert a.observe(10, 2) == 0           # streak reset after acting
    # the hysteresis band (low < depth < high) resets both streaks
    assert a.observe(10, 2) == 0
    assert a.observe(4, 2) == 0
    assert a.observe(10, 2) == 0
    assert a.observe(10, 2) == 0
    assert a.observe(10, 2) == 1
    # cold side mirrors
    assert a.observe(0, 2) == 0
    assert a.observe(0, 2) == 0
    assert a.observe(0, 2) == -1


def test_autoscaler_bounds_and_validation():
    a = fleet.Autoscaler(min_replicas=2, max_replicas=3,
                         queue_high=4, queue_low=1, patience=1)
    assert a.observe(9, 3) == 0            # at max: never grow
    assert a.observe(0, 2) == 0            # at min: never shrink
    assert a.observe(9, 2) == 1
    assert a.observe(0, 3) == -1
    with pytest.raises(ValueError):
        fleet.Autoscaler(queue_high=2, queue_low=2)
    # p99 pressure alone can trigger growth
    b = fleet.Autoscaler(queue_high=100, queue_low=1, patience=1,
                         p99_high_ms=50.0)
    assert b.observe(2, 1, p99_ms=80.0) == 1


# ------------------------------------------------------ drain ledger
def test_drain_ledger_lifecycle():
    led = fleet.DrainLedger()
    assert led.begin("r0", 100.0, 5.0)
    assert not led.begin("r0", 100.0, 5.0)   # already draining
    assert led.draining("r0") and not led.draining("r1")
    led.note_handoff("r0")
    led.note_handoff("r0")
    assert led.expired(104.0) == []
    assert led.expired(106.0) == ["r0"]
    assert led.finish("r0") == 2
    assert led.finish("r0") is None          # second finish: no-op
    led.begin("r1", 0.0, 1.0)
    led.finish("r1", escalated=True)
    snap = led.snapshot()
    assert snap["drains_started"] == 2
    assert snap["drains_completed"] == 1     # escalations count apart
    assert snap["drains_escalated"] == 1
    assert snap["drains_active"] == 0


def test_check_handoff_state_rejects_garbage():
    ok = fleet.check_handoff_state(
        {"prompt": [1, 2], "generated": ["3"],
         "max_new_tokens": 4, "sampling": {"seed": 1}})
    assert ok["generated"] == [3]            # int coercion
    for bad in (
        "nope",
        {"generated": [1]},                          # no prompt
        {"prompt": [], "max_new_tokens": 4},         # empty prompt
        {"prompt": [1], "max_new_tokens": 2,
         "generated": [5, 6]},                       # already complete
        {"prompt": [1], "max_new_tokens": 2, "sampling": "hot"},
    ):
        with pytest.raises(ServingError):
            fleet.check_handoff_state(bad)


# ------------------------------------------------------------- wire
def test_wire_frames_and_channel():
    a, b = socket.socketpair()
    fleet.send_frame(a, {"x": [1, 2], "s": "héllo"})
    assert fleet.recv_frame(b) == {"x": [1, 2], "s": "héllo"}
    with pytest.raises(fleet.WireError):
        fleet.send_frame(a, {"blob": "x" * (fleet.MAX_FRAME + 16)})
    chan = fleet.Channel(a, name="t")
    for i in range(50):
        chan.send({"i": i})                  # never blocks
    assert chan.flush(timeout=5)
    got = [fleet.recv_frame(b) for _ in range(50)]
    assert got == [{"i": i} for i in range(50)]
    chan.close()
    chan.close()                             # idempotent
    assert chan.closed
    assert fleet.recv_frame(b) is None       # clean EOF for the peer
    b.close()


# ------------------------------------------------- fake replica rig
def _tok(prompt, p):
    """Deterministic token at position p — pure in (prompt, p), the
    same property counter-based sampling gives the real engine, so a
    resumed decode must reproduce the uninterrupted stream exactly."""
    return (sum(prompt) + 7 * p + 3) % 97


class _FakeReplica:
    """Speaks the replica side of the wire protocol without jax."""

    def __init__(self, rid, port, page_size=4, delay=0.0,
                 prefixes=(), hb_auto=True, hb_ms=40):
        self.rid = rid
        self.delay = delay
        self.prefixes = list(prefixes)
        self.hb_ms = hb_ms
        self.depth = 0
        self.seen = []
        self._stop = threading.Event()
        sock = socket.create_connection(("127.0.0.1", port))
        self.chan = fleet.Channel(sock, name=rid)
        self.chan.send({"op": "hello", "id": rid, "pid": 0,
                        "model": "fake", "version": 1,
                        "kind": "decoded", "page_size": page_size,
                        "traces": 0, "compiles": 0})
        threading.Thread(target=self._loop, daemon=True).start()
        if hb_auto:
            threading.Thread(target=self._hb_loop, daemon=True).start()

    def hb(self):
        self.chan.send({"op": "hb", "id": self.rid, "draining": False,
                        "depth": self.depth, "digest": "d",
                        "prefixes": self.prefixes, "stats": {}})

    def _hb_loop(self):
        self.hb()
        while not self._stop.wait(self.hb_ms / 1e3):
            self.hb()

    def _loop(self):
        while True:
            msg = self.chan.recv()
            if msg is None or self._stop.is_set():
                return
            self.seen.append(msg)
            op = msg.get("op")
            if op in ("generate", "resume"):
                threading.Thread(target=self._serve, args=(msg,),
                                 daemon=True).start()
            elif op == "drain":
                # the fake is always idle when drained in these tests
                self.chan.send({"id": msg["id"],
                                "done": {"handoffs": 0}})
                self.chan.flush(timeout=2)
                self._stop.set()
                self.chan.close()
                return
            elif op == "stop":
                self._stop.set()
                self.chan.close()
                return

    def _serve(self, msg):
        if msg["op"] == "generate":
            prompt, start = msg["prompt"], 0
            max_new = msg["max_new_tokens"]
        else:
            st = msg["state"]
            prompt, start = st["prompt"], len(st["generated"])
            max_new = st["max_new_tokens"]
        for p in range(start, max_new):
            if self._stop.is_set() or self.chan.closed:
                return
            if self.delay:
                time.sleep(self.delay)
            self.chan.send({"id": msg["id"], "tok": _tok(prompt, p)})
        self.chan.send({"id": msg["id"],
                        "done": {"reason": "max_tokens"}})

    def kill(self):
        """SIGKILL analog: vanish mid-frame, no goodbye."""
        self._stop.set()
        self.chan.close()


def _fake_fleet(n=2, policy="affinity", hb_ms=40, **fake_kw):
    """Router + n fake replicas; spawn_fn keeps spawning fakes so
    heal-after-death works. Returns (router, fakes dict)."""
    fakes = {}

    def spawn(rid, port):
        fakes[rid] = _FakeReplica(rid, port, hb_ms=hb_ms, **fake_kw)
        return None

    router = fleet.FleetRouter(replicas=n, heartbeat_ms=hb_ms,
                               page_size=4, policy=policy,
                               spawn_fn=spawn, name=f"t{id(fakes)}",
                               seed=0)
    router.start(wait=True, timeout=30)
    return router, fakes


def _wait(pred, timeout=10, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# ----------------------------------------------------- router (fake)
def test_router_routes_by_affinity_then_least_loaded():
    router, fakes = _fake_fleet(2)
    try:
        prompt = list(range(16))
        fakes["r1"].prefixes = page_digests(prompt, 4)[:3]
        fakes["r1"].hb()
        _wait(lambda: router.affinity.advertised("r1"),
              msg="advertisement")
        toks = router.generate(prompt, max_new_tokens=4, timeout=10)
        assert toks == [_tok(prompt, p) for p in range(4)]
        assert any(m.get("op") == "generate"
                   for m in fakes["r1"].seen)
        assert not any(m.get("op") == "generate"
                       for m in fakes["r0"].seen)
        snap = router.stats.snapshot()
        assert snap["routed_affinity"] == 1
        assert snap["affinity_pages_covered"] == 3
        # an uncovered prompt falls back to least-loaded: r0 reports
        # depth 0 while r1 reports a deep queue
        fakes["r1"].depth = 9
        fakes["r1"].hb()
        _wait(lambda: router._load(router._handles["r1"]) >= 9,
              msg="depth heartbeat")
        other = [51, 52, 53]
        router.generate(other, max_new_tokens=2, timeout=10)
        assert any(m.get("op") == "generate"
                   for m in fakes["r0"].seen)
        assert router.stats.snapshot()["routed_least_loaded"] == 1
    finally:
        router.stop()


def test_router_death_rebuild_and_heal_parity():
    router, fakes = _fake_fleet(2, delay=0.02)
    try:
        prompt = [5, 6, 7]
        expect = [_tok(prompt, p) for p in range(12)]
        st = router.stream(prompt, max_new_tokens=12, timeout=20)
        pre = [next(st) for _ in range(3)]
        with router._lock:
            victim = next(p.replica_id
                          for p in router._pending.values())
        fakes[victim].kill()
        full = pre + list(st)
        # zero-loss AND bit-identical: rebuilt from the router's own
        # token record, resumed under the same pure token function
        assert full == expect
        snap = router.stats.snapshot()
        assert snap["replica_deaths"] == 1
        assert snap["readmissions"] >= 1
        # heal: the dead replica was replaced one-for-one
        _wait(lambda: len(router.status()["replicas"]) == 2,
              msg="replacement replica")
        assert "r2" in fakes
    finally:
        router.stop()


def test_router_stale_heartbeat_retires_silent_replica():
    router, fakes = _fake_fleet(2, hb_ms=30)
    try:
        # r0 goes silent but keeps its socket open: only the
        # staleness sweep (not EOF) can catch this failure mode
        fakes["r0"]._stop.set()
        _wait(lambda: router.stats.snapshot()["replica_deaths"] == 1,
              msg="staleness retirement")
        _wait(lambda: set(router.status()["replicas"]) >= {"r1", "r2"},
              msg="replacement replica")
        assert "r0" not in router.status()["replicas"]
    finally:
        router.stop()


def test_router_deadline_propagates_and_sweeps():
    router, fakes = _fake_fleet(1, hb_ms=30, delay=0.05)
    try:
        fut = router.submit([1, 2, 3], max_new_tokens=500,
                            deadline_ms=250.0)
        # the generate frame carried the remaining budget downstream
        _wait(lambda: any(m.get("op") == "generate"
                          for m in fakes["r0"].seen), msg="dispatch")
        gen = next(m for m in fakes["r0"].seen
                   if m.get("op") == "generate")
        assert 0 < gen["deadline_ms"] <= 250.0
        # the ROUTER enforces the deadline even though the fake
        # replica never would (a dead replica can't expire its queue)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=10)
    finally:
        router.stop()


def test_router_admin_protocol_and_cli():
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import mx_fleet

    router, fakes = _fake_fleet(2)
    try:
        addr = f"127.0.0.1:{router.port}"
        status = mx_fleet.admin_call(addr, "status")
        assert set(status["replicas"]) == {"r0", "r1"}
        assert status["policy"] == "affinity"
        # scale up through the admin plane
        out = mx_fleet.admin_call(addr, "scale", n=3)
        assert out["changed"] == ["r2"]
        _wait(lambda: len(router.status()["replicas"]) == 3,
              msg="scale-up")
        # drain one replica through the admin plane (idle -> 0
        # handoffs) and unknown-replica errors surface as SystemExit
        out = mx_fleet.admin_call(addr, "drain", replica="r2",
                                  timeout_ms=500)
        assert out["handoffs"] == 0
        with pytest.raises(SystemExit):
            mx_fleet.admin_call(addr, "nonsense")
        # the CLI entry point renders status JSON
        assert mx_fleet.main(["status", "--connect", addr]) == 0
    finally:
        router.stop()


def test_fleet_stats_view_registered():
    router, _ = _fake_fleet(1)
    try:
        from mxnet_tpu.fleet import fleet_stats

        view = fleet_stats()
        assert router.name in view
        snap = view[router.name]
        assert snap["replicas"] and "requests" in snap
        # prometheus render includes the fleet gauges
        from mxnet_tpu.telemetry import prometheus_text

        text = prometheus_text()
        assert "mxnet_tpu_fleet_replicas" in text
    finally:
        router.stop()
    assert router.name not in fleet.fleet_stats()


# ------------------------------------------------------------- jax
# real-model drain/handoff bit-identity: slow (tiny decoder warmup
# dominates) so, like the decode-tier model suites, they run in the
# dedicated gate (`make fleet-check` / ci/check_fleet.sh) rather
# than tier-1
CFG = dict(vocab=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
           max_len=128)
SAMP = {"temperature": 0.8, "seed": 7}


@pytest.fixture(scope="module")
def tiny():
    cfg = dec.DecoderConfig(**CFG)
    params = dec.init_decoder_params(cfg, seed=0)
    server = ModelServer()
    ref = server.load_decoder("ref", params, cfg, max_batch=2,
                              page_size=4, num_pages=64)
    yield cfg, params, ref
    server.stop()


def _load(server, name, params, cfg):
    return server.load_decoder(name, params, cfg, max_batch=2,
                               page_size=4, num_pages=64)


def _thread_replicas(params, cfg, started=None):
    """A FleetRouter `spawn_fn` whose replicas are threads of this
    process, each with a server and a paged decoder of its own;
    `started` collects (server, model) as they come up."""
    def spawn(rid, port):
        def run():
            server = ModelServer()
            model = _load(server, f"lm-{rid}", params, cfg)
            if started is not None:
                started.append((server, model))
            sock = socket.create_connection(("127.0.0.1", port))
            fleet.ReplicaWorker(server, model,
                                fleet.Channel(sock, name=rid), rid,
                                heartbeat_ms=50,
                                hello_extra={"traces": 0,
                                             "compiles": 0}).run()
        threading.Thread(target=run, daemon=True).start()

    return spawn


@pytest.mark.slow
def test_drain_handoff_resumes_bit_identical(tiny):
    cfg, params, ref_model = tiny
    prompt = list(range(1, 10))
    ref = ref_model.generate(prompt, max_new_tokens=16, sampling=SAMP)
    s1, s2 = ModelServer(), ModelServer()
    try:
        m1 = _load(s1, "lm1", params, cfg)
        m2 = _load(s2, "lm2", params, cfg)
        fut = m1.submit(prompt, max_new_tokens=16, sampling=SAMP)
        st = fut.stream(timeout=60)
        pre = [next(st) for _ in range(3)]
        handoffs = s1.drain(timeout=0)
        with pytest.raises(dec.RequestHandedOff):
            list(st)
        (states,) = handoffs.values()
        state = states[0]
        assert state["generated"][:3] == pre
        # resume on a DIFFERENT process's stand-in: same tokens as
        # the uninterrupted reference — counter-based sampling makes
        # position, not history, the randomness key
        fut2 = s2.admit_resumed("lm2", state)
        assert state["generated"] + list(
            fut2.stream(timeout=60)) == ref
        # the drained server admits nothing new
        with pytest.raises(ServerClosedError):
            m1.submit(prompt, max_new_tokens=2)
    finally:
        s1.stop()
        s2.stop()


@pytest.mark.slow
def test_drain_idle_and_strand_fix(tiny):
    cfg, params, _ = tiny
    s = ModelServer()
    m = _load(s, "lm", params, cfg)
    assert s.drain(timeout=0) == {}          # idle drain: no handoffs
    s.stop()
    # a persistently-raising engine during shutdown must FAIL queued
    # futures, not strand them (the pre-PR-17 infinite-spin bug)
    s2 = ModelServer()
    m2 = _load(s2, "lm", params, cfg)

    def boom(*a, **kw):
        raise RuntimeError("poisoned engine")

    m2.scheduler.engine.prefill = boom
    m2.scheduler.engine.step = boom
    fut = m2.submit([1, 2, 3], max_new_tokens=4)
    s2.stop(drain=True)
    assert isinstance(fut.exception(timeout=30), RuntimeError)


@pytest.mark.slow
def test_fleet_end_to_end_drain_over_wire(tiny):
    cfg, params, ref_model = tiny
    prompt = list(range(1, 10))
    # long enough that the drain always catches the decode LIVE (the
    # replica decodes ahead of the consumer; EOS may end it sooner —
    # parity is over whatever the reference run produced)
    ref = ref_model.generate(prompt, max_new_tokens=200, sampling=SAMP)
    assert len(ref) > 8

    router = fleet.FleetRouter(replicas=2, heartbeat_ms=50,
                               page_size=4,
                               spawn_fn=_thread_replicas(params, cfg),
                               name="e2e", seed=1)
    router.start(wait=True, timeout=60)
    try:
        st = router.stream(prompt, max_new_tokens=200, sampling=SAMP,
                           timeout=90)
        pre = [next(st)]
        with router._lock:
            victim = next(p.replica_id
                          for p in router._pending.values()
                          if p.kind == "decode")
        handoffs = router.drain_replica(victim, timeout_ms=0,
                                        wait=True)
        assert handoffs == 1
        # the stream NEVER saw the drain: handoff -> re-admission on
        # the surviving replica, tokens bit-identical throughout
        assert pre + list(st) == ref
        assert len(router.status()["replicas"]) == 1
        assert router.stats.snapshot()["handoffs"] == 1
    finally:
        router.stop()


def _routed_arm(policy, params, cfg, prompts, families):
    """One fleet of two thread-backed replicas (each its own server
    and paged decoder) serving `prompts` under `policy`: the fleet's
    prefix hits, misses and pages allocated, from the replicas' own
    stats."""
    started = []
    router = fleet.FleetRouter(replicas=2, heartbeat_ms=50, page_size=4,
                               policy=policy,
                               spawn_fn=_thread_replicas(params, cfg,
                                                         started),
                               name=f"ab-{policy}", seed=0)
    router.start(wait=True, timeout=120)
    try:
        # a wave is one request a family; the wait between waves lets
        # heartbeats advertise what the wave left cached
        for i in range(0, len(prompts), families):
            futs = [router.submit(p, max_new_tokens=2)
                    for p in prompts[i:i + families]]
            for f in futs:
                f.result(120)
            time.sleep(0.2)
    finally:
        router.stop()
    snaps = [model.stats.snapshot() for _, model in started]
    for server, _ in started:
        server.stop(drain=False)
    return {k: sum(s[k] for s in snaps)
            for k in ("prefix_hits", "prefix_misses", "pages_allocated")}


@pytest.mark.slow
def test_affinity_routing_beats_random_on_hits_and_pages(tiny):
    """Why the router routes by prefix: the same chat-shaped traffic
    (four families, each a shared three-page preamble and a short
    tail of its own) costs fewer prefix misses and fewer pages when a
    family keeps to the replica that cached it than when it is dealt
    at random."""
    cfg, params, _ = tiny
    rs = np.random.RandomState(0)
    families = 4
    heads = [rs.randint(2, cfg.vocab, size=12).tolist()
             for _ in range(families)]
    prompts = [heads[i % families]
               + rs.randint(2, cfg.vocab, size=int(rs.randint(2, 4)))
               .tolist() for i in range(6 * families)]
    aff = _routed_arm("affinity", params, cfg, prompts, families)
    rnd = _routed_arm("random", params, cfg, prompts, families)

    def hit_rate(arm):
        return arm["prefix_hits"] / (arm["prefix_hits"]
                                     + arm["prefix_misses"])

    assert hit_rate(aff) > hit_rate(rnd), (aff, rnd)
    assert aff["pages_allocated"] < rnd["pages_allocated"], (aff, rnd)
