"""Serving-fleet units (README "Serving fleet"): the failover proxy's
retry/affinity/canary routing, the restart policy's capped backoff,
the stagger protocol's >= 1-other-ready invariant, the reload
watcher's jittered cadence, the canary checkpoint pointer, and the
fmstat FLEET section — everything driven through the public seams
(ScoreProxy.forward_score, staggered_reload over fakes, RestartPolicy
over a fake clock) so no test spawns a replica child process."""

import http.server
import json
import os
import threading

import pytest

from fast_tffm_tpu.serve.fleet import RestartPolicy, staggered_reload
from fast_tffm_tpu.serve.proxy import (FleetView, FractionSplitter,
                                       Replica, ScoreProxy,
                                       rendezvous_choose)

pytestmark = pytest.mark.filterwarnings(
    "ignore::DeprecationWarning")


# --- back-end stubs ------------------------------------------------------


class _StubHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):  # noqa: N802 - http.server contract
        length = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(length)
        srv = self.server
        srv.hits += 1
        body = srv.body
        self.send_response(srv.status)
        self.send_header("Content-Type", "text/plain")
        self.send_header("Content-Length", str(len(body)))
        if srv.step is not None:
            self.send_header("X-FM-Step", str(srv.step))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):  # noqa: A003 - silence
        pass


class _Stub(http.server.ThreadingHTTPServer):
    """One fake replica back end: scripted status/body/step."""

    daemon_threads = True

    def __init__(self, status=200, body=b"0.500000\n", step=7):
        self.status, self.body, self.step = status, body, step
        self.hits = 0
        super().__init__(("127.0.0.1", 0), _StubHandler)
        self.thread = threading.Thread(target=self.serve_forever,
                                       daemon=True)
        self.thread.start()

    @property
    def port(self):
        return self.server_address[1]

    def close(self):
        self.shutdown()
        self.thread.join()
        self.server_close()


def _ready_replica(index, port, canary=False):
    r = Replica(index, "127.0.0.1", port, canary=canary)
    r.set_health(alive=True, ready=True, served_step=7)
    return r


def _dead_port():
    """A loopback port with nothing listening (bound then released)."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# --- proxy retry / failover ---------------------------------------------


def test_proxy_fails_over_on_connection_refused():
    good = _Stub()
    try:
        bad = _ready_replica(0, _dead_port())
        ok = _ready_replica(1, good.port)
        proxy = ScoreProxy(FleetView([bad, ok]), retry_budget=2,
                           backoff_seconds=0.0)
        # Force the first pick onto the dead replica: the round-robin
        # cursor is deterministic, so route by affinity instead and
        # pin the key to the dead one.
        key = next(k for k in (f"k{i}" for i in range(64))
                   if rendezvous_choose(k, [bad, ok]) is bad)
        status, body, extra = proxy.forward_score(b"1 0:1.0\n", key)
        assert status == 200 and body == b"0.500000\n"
        assert extra["X-FM-Replica"] == "1"
        assert extra["X-FM-Step"] == "7"
        snap = proxy.registry.snapshot()["counters"]
        assert snap["proxy/transport_errors"] == 1
        assert snap["proxy/retries"] == 1
        # Fast-path demotion: the dead replica is routed around NOW,
        # before any health poll.
        assert not bad.is_ready()
    finally:
        good.close()


def test_proxy_fails_over_on_upstream_5xx():
    sick = _Stub(status=500, body=b"boom\n", step=None)
    good = _Stub()
    try:
        r_sick = _ready_replica(0, sick.port)
        r_good = _ready_replica(1, good.port)
        proxy = ScoreProxy(FleetView([r_sick, r_good]), retry_budget=2,
                           backoff_seconds=0.0)
        key = next(k for k in (f"k{i}" for i in range(64))
                   if rendezvous_choose(k, [r_sick, r_good]) is r_sick)
        status, body, _ = proxy.forward_score(b"1 0:1.0\n", key)
        assert status == 200 and body == b"0.500000\n"
        snap = proxy.registry.snapshot()["counters"]
        assert snap["proxy/upstream_5xx"] == 1
        assert not r_sick.is_ready()
        assert sick.hits == 1 and good.hits == 1
    finally:
        sick.close()
        good.close()


def test_proxy_exhausted_budget_is_503_with_retry_after():
    replicas = [_ready_replica(i, _dead_port()) for i in range(3)]
    proxy = ScoreProxy(FleetView(replicas), retry_budget=2,
                       backoff_seconds=0.0)
    status, body, extra = proxy.forward_score(b"1 0:1.0\n", None)
    assert status == 503
    assert extra["Retry-After"] == "1"
    assert b"no replica could score" in body
    snap = proxy.registry.snapshot()["counters"]
    assert snap["proxy/unrouted_503"] == 1
    # budget + 1 attempts, each on a DIFFERENT replica
    assert snap["proxy/transport_errors"] == 3


def test_proxy_4xx_passes_through_unretried():
    """Client errors are not the replica's fault: resending a
    malformed request buys nothing and must not burn the budget."""
    bad_req = _Stub(status=400, body=b"parse error\n", step=None)
    try:
        proxy = ScoreProxy(
            FleetView([_ready_replica(0, bad_req.port)]),
            retry_budget=3, backoff_seconds=0.0)
        status, body, _ = proxy.forward_score(b"garbage\n", None)
        assert status == 400 and body == b"parse error\n"
        assert bad_req.hits == 1
        snap = proxy.registry.snapshot()["counters"]
        assert "proxy/retries" not in snap
    finally:
        bad_req.close()


def test_proxy_front_end_sheds_at_max_inflight():
    """Beyond serve_proxy_max_inflight the front door answers 503 +
    Retry-After immediately instead of queueing blocked threads."""
    import http.client
    good = _Stub()
    proxy = ScoreProxy(FleetView([_ready_replica(0, good.port)]),
                       max_inflight=1)
    port = proxy.start(0)
    try:
        assert proxy.inflight.acquire(blocking=False)  # fill the slot
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        try:
            conn.request("POST", "/score", body=b"1 0:1.0\n",
                         headers={"Content-Type": "text/plain"})
            resp = conn.getresponse()
            out = resp.read()
            assert resp.status == 503
            assert resp.getheader("Retry-After") == "1"
            assert b"max in-flight" in out
        finally:
            conn.close()
        snap = proxy.registry.snapshot()["counters"]
        assert snap["proxy/shed_503"] == 1
        proxy.inflight.release()
    finally:
        proxy.shutdown()
        good.close()


def test_proxy_healthz_aggregates_and_degrades():
    import http.client
    r0 = _ready_replica(0, 1)
    r1 = Replica(1, "127.0.0.1", 2)
    r1.set_health(alive=True, ready=False)
    proxy = ScoreProxy(FleetView([r0, r1]))
    port = proxy.start(0)

    def get_healthz():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    try:
        status, payload = get_healthz()
        assert status == 200 and payload["status"] == "ok"
        assert (payload["replicas"], payload["alive"],
                payload["ready"]) == (2, 2, 1)
        assert [row["ready"] for row in payload["per_replica"]] \
            == [True, False]
        r0.mark_failed()
        status, payload = get_healthz()
        assert status == 503 and payload["status"] == "degraded"
    finally:
        proxy.shutdown()


# --- rendezvous affinity -------------------------------------------------


def test_rendezvous_affinity_is_stable_and_minimal():
    """The HRW property the proxy buys over modulo hashing: removing
    one replica only remaps the keys that were ON it."""
    replicas = [_ready_replica(i, 9000 + i) for i in range(4)]
    keys = [f"user-{i}" for i in range(300)]
    before = {k: rendezvous_choose(k, replicas) for k in keys}
    # Deterministic: the same key always lands on the same replica.
    assert all(rendezvous_choose(k, replicas) is before[k]
               for k in keys)
    gone = replicas[2]
    survivors = [r for r in replicas if r is not gone]
    moved = 0
    for k in keys:
        after = rendezvous_choose(k, survivors)
        if before[k] is gone:
            moved += 1
            assert after is not gone
        else:
            assert after is before[k], (
                f"key {k} moved off a surviving replica")
    # The departed replica owned SOME keys (sanity: the test bites).
    assert moved > 0


def test_proxy_affinity_header_coalesces_bursts():
    good = _Stub()
    other = _Stub()
    try:
        replicas = [_ready_replica(0, good.port),
                    _ready_replica(1, other.port)]
        proxy = ScoreProxy(FleetView(replicas), retry_budget=0)
        hits = set()
        for _ in range(8):
            status, _, extra = proxy.forward_score(b"1 0:1.0\n",
                                                   "user-42")
            assert status == 200
            hits.add(extra["X-FM-Replica"])
        assert len(hits) == 1, f"affinity key split across {hits}"
    finally:
        good.close()
        other.close()


# --- canary routing ------------------------------------------------------


def test_fraction_splitter_is_exact():
    s = FractionSplitter(0.25)
    takes = sum(s.take() for _ in range(400))
    assert takes == 100
    assert sum(FractionSplitter(0.0).take() for _ in range(50)) == 0
    assert sum(FractionSplitter(1.0).take() for _ in range(50)) == 50


def test_canary_fraction_routes_exactly():
    """With a ready canary, pick() sends exactly the configured
    fraction of unkeyed traffic to it — deterministically."""
    primaries = [_ready_replica(i, 9100 + i) for i in range(2)]
    canary = _ready_replica(2, 9200, canary=True)
    proxy = ScoreProxy(FleetView(primaries + [canary]),
                       canary_fraction=0.25)
    chosen = [proxy.pick(None) for _ in range(200)]
    assert sum(1 for r in chosen if r is canary) == 50
    snap = proxy.registry.snapshot()["counters"]
    assert snap["proxy/canary_requests"] == 50


def test_canary_not_primary_routed_and_degraded_fallback():
    primaries = [_ready_replica(i, 9100 + i) for i in range(2)]
    canary = _ready_replica(2, 9200, canary=True)
    proxy = ScoreProxy(FleetView(primaries + [canary]),
                       canary_fraction=0.0)
    # fraction 0: unkeyed traffic never touches the canary...
    assert all(proxy.pick(None) is not canary for _ in range(50))
    # ...until every primary is down — then a ready canary beats an
    # outage.
    for r in primaries:
        r.mark_failed()
    assert proxy.pick(None) is canary


# --- restart backoff -----------------------------------------------------


def test_restart_policy_caps_and_resets():
    clock = [0.0]
    p = RestartPolicy(1.0, cap_factor=16.0, clock=lambda: clock[0])
    assert p.can_restart()
    delays = [p.record_death() for _ in range(6)]
    assert delays == [1.0, 2.0, 4.0, 8.0, 16.0, 16.0]  # capped
    assert p.failures == 6
    assert not p.can_restart()  # last death scheduled t+16
    clock[0] = 15.9
    assert not p.can_restart()
    clock[0] = 16.0
    assert p.can_restart()
    p.record_healthy()
    assert p.failures == 0
    assert p.record_death() == 1.0  # streak reset: back to base


def test_a_death_behind_a_failed_probe_is_still_counted(tmp_path):
    """The supervisor's poll of a replica whose probe failed once (a
    starved host; a kill between the exit check and the probe) and that
    has exited by the next poll: the death is counted once, the restart
    backs off, and the respawn is counted when the backoff is over."""
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.serve.fleet import FleetSupervisor
    cfg = FmConfig(vocabulary_size=16, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"),
                   serve_replicas=2, serve_port=1, serve_proxy_port=0,
                   serve_restart_backoff_seconds=100.0)
    sup = FleetSupervisor(cfg, str(tmp_path / "fleet.cfg"))
    r = sup.replicas[1]

    class Child:
        pid, returncode = 4242, None

        def poll(self):
            return self.returncode
    r.proc, spawned = Child(), []
    r.probe = lambda timeout: None
    r.spawn = lambda: spawned.append(r.proc.pid)

    def counters():
        c = sup._reg.snapshot()["counters"]
        return c.get("fleet/deaths", 0), c.get("fleet/restarts", 0)
    sup._poll_replica(r)                  # alive, its probe fails
    assert r.probe_failures == 1 and counters() == (0, 0)
    r.proc.returncode = -9                # SIGKILL lands
    sup._poll_replica(r)
    assert counters() == (1, 0) and r.policy.failures == 1
    assert not r.policy.can_restart() and spawned == []
    sup._poll_replica(r)                  # still inside the backoff
    assert counters() == (1, 0)
    r.policy._not_before = 0.0            # the backoff is over
    sup._poll_replica(r)
    assert counters() == (1, 1) and spawned == [4242]


# --- staggered reload ----------------------------------------------------


class _FakeHandle:
    """ReplicaProc's reload surface: reload() takes the handle
    not-ready (synchronously, like the real POST /reload) and a later
    is_ready() poll brings it back — with the test recording how many
    OTHER handles were ready at every reload instant."""

    def __init__(self, name, fleet, fail=False,
                 ready_after_polls=2):
        self.name = name
        self.fleet = fleet
        self.fail = fail
        self.ready = True
        self.step = 0
        self._polls_left = 0
        self._ready_after = ready_after_polls

    def is_ready(self):
        if not self.ready and self._polls_left > 0:
            self._polls_left -= 1
            if self._polls_left == 0:
                self.ready = True
        return self.ready

    def reload(self, step):
        others_ready = sum(1 for h in self.fleet
                           if h is not self and h.ready)
        self.fleet.observed_min = min(self.fleet.observed_min,
                                      others_ready)
        if self.fail:
            return False
        self.ready = False
        self._polls_left = self._ready_after
        self.step = step
        return True


class _Fleet(list):
    observed_min = 99


def test_staggered_reload_keeps_one_other_ready():
    fleet = _Fleet()
    fleet.extend(_FakeHandle(f"r{i}", fleet) for i in range(4))
    done = staggered_reload(fleet, step=11, sleep=lambda _s: None)
    assert done == 4
    assert all(h.step == 11 and h.ready for h in fleet)
    # The invariant: at every reload instant >= 1 OTHER replica ready.
    assert fleet.observed_min >= 1


def test_staggered_reload_counts_failures_and_continues():
    fleet = _Fleet()
    fleet.extend([_FakeHandle("r0", fleet),
                  _FakeHandle("r1", fleet, fail=True),
                  _FakeHandle("r2", fleet)])
    seen = []
    done = staggered_reload(fleet, step=5,
                            reloaded=lambda h, ok: seen.append(
                                (h.name, ok)),
                            sleep=lambda _s: None)
    assert done == 2
    assert seen == [("r0", True), ("r1", False), ("r2", True)]
    # The failed handle keeps serving its previous step — no outage.
    assert fleet[1].ready and fleet[1].step == 0


def test_staggered_reload_timeout_reloads_anyway():
    """A fleet whose OTHER replicas never come ready must not wedge
    forever serving stale state: past the wait budget the stagger
    logs and reloads anyway."""
    fleet = _Fleet()
    fleet.extend([_FakeHandle("r0", fleet), _FakeHandle("r1", fleet)])
    fleet[1].ready = False
    fleet[1]._polls_left = 0  # never recovers on its own
    clock = [0.0]

    def tick(_s):
        clock[0] += 1.0

    done = staggered_reload([fleet[0]], step=3, min_other_ready=1,
                            wait_seconds=5.0, sleep=tick,
                            clock=lambda: clock[0])
    # r1 stayed down, yet r0 still got its reload after the budget.
    assert done in (0, 1)
    assert fleet[0].step == 3


# --- reload watcher jitter ----------------------------------------------


def test_reload_watcher_jitter_bounds_and_determinism():
    from fast_tffm_tpu.serve.reload import ReloadWatcher
    a = ReloadWatcher(None, poll_seconds=10.0, jitter=0.2, seed=4242,
                      auto_reload=False)
    waits = [a.next_wait() for _ in range(200)]
    assert all(8.0 <= w <= 12.0 for w in waits)
    assert len(set(round(w, 6) for w in waits)) > 1  # actually jitters
    b = ReloadWatcher(None, poll_seconds=10.0, jitter=0.2, seed=4242,
                      auto_reload=False)
    assert [b.next_wait() for _ in range(200)] == waits  # per-seed
    c = ReloadWatcher(None, poll_seconds=10.0, jitter=0.2, seed=4243,
                      auto_reload=False)
    assert [c.next_wait() for _ in range(200)] != waits  # decorrelates
    z = ReloadWatcher(None, poll_seconds=10.0, jitter=0.0, seed=1,
                      auto_reload=False)
    assert z.next_wait() == 10.0


# --- canary pointer ------------------------------------------------------


def test_canary_pointer_round_trip(tmp_path):
    from fast_tffm_tpu.checkpoint import (read_canary, read_pointer,
                                          write_canary)
    d = str(tmp_path)
    assert read_canary(d) is None
    path = write_canary(d, 42)
    assert os.path.basename(path) == "published-canary"
    assert read_canary(d) == 42
    assert read_pointer(d, "canary") == 42
    assert read_pointer(d, "published") is None  # independent pointers
    write_canary(d, 43)  # atomic repoint
    assert read_canary(d) == 43


# --- fmstat FLEET section ------------------------------------------------


def _fleet_metrics_file(tmp_path, ready, total):
    recs = [
        {"event": "run_start", "meta": {"mode": "serve-fleet"}},
        {"event": "metrics", "run": {"process_index": 0},
         "counters": {"proxy/requests": 120, "proxy/retries": 4,
                      "fleet/restarts": 1, "fleet/deaths": 1},
         "gauges": dict(
             {"fleet/replicas": total, "fleet/alive": total,
              "fleet/ready": ready},
             **{f"fleet/replica{i}_alive": 1.0 for i in range(total)},
             **{f"fleet/replica{i}_ready":
                float(i < ready) for i in range(total)},
             **{f"fleet/replica{i}_step": 40.0 for i in range(total)},
             **{f"fleet/replica{i}_queue_depth": 0.0
                for i in range(total)})},
        {"event": "run_end"},
    ]
    p = tmp_path / "fleet_metrics.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(p)


def test_fmstat_fleet_degraded_verdict(tmp_path):
    from fast_tffm_tpu.obs.attribution import (fleet_degraded,
                                               health_verdict,
                                               summarize)
    s = summarize([_fleet_metrics_file(tmp_path, ready=2, total=3)])
    assert fleet_degraded(s) == (2, 3)
    assert health_verdict(s)["verdict"] == "FLEET DEGRADED (2/3 ready)"


def test_fmstat_fleet_full_strength_is_ok_with_rows(tmp_path):
    from fast_tffm_tpu.obs.attribution import (fleet_degraded,
                                               fleet_table,
                                               health_verdict, render,
                                               summarize)
    s = summarize([_fleet_metrics_file(tmp_path, ready=3, total=3)])
    assert fleet_degraded(s) is None
    assert health_verdict(s)["verdict"] == "OK"
    rows = fleet_table(s)
    assert len(rows) == 3
    assert rows[0].startswith("r0: ready")
    text = render(s)
    assert "FLEET (serve --replicas)" in text
    assert "r2:" in text
