"""Online serving subsystem units (README "Serving"): admission
batching, ladder padding, hot-reload swap, client round-trips over the
in-process and HTTP front ends, and the published-pointer edge cases
the reload loop leans on (garbled pointer heals, repoint is atomic
under a concurrent reader, a GC'd published step degrades to a counted
reload failure — never an outage)."""

import dataclasses
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.parser import ParseError, parse_lines

pytestmark = pytest.mark.filterwarnings(
    "ignore::DeprecationWarning")


def _corpus_lines(n, seed=0, vocab=200):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        y = int(rng.integers(0, 2))
        feats = sorted(rng.choice(vocab, size=4, replace=False))
        lines.append(f"{y} " + " ".join(f"{i}:1.0" for i in feats))
    return lines


def _serve_cfg(workdir, **overrides):
    base = dict(
        vocabulary_size=200, factor_num=4, batch_size=32, epoch_num=1,
        learning_rate=0.1, shuffle=True, seed=0, log_steps=0,
        save_steps=5,
        bucket_ladder=(8, 16), max_features_per_example=16,
        serve_max_batch=8, serve_max_wait_ms=2.0,
        serve_poll_seconds=0.02,
        model_file=os.path.join(workdir, "model", "fm"))
    base.update(overrides)
    return FmConfig(train_files=(os.path.join(workdir, "train.txt"),),
                    **base)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One trained model with several retained checkpoint steps; the
    first is published. Shared across the module — every test builds
    its servers against this directory."""
    from fast_tffm_tpu.checkpoint import CheckpointState, list_step_dirs
    from fast_tffm_tpu.train import train
    wd = str(tmp_path_factory.mktemp("serve"))
    lines = _corpus_lines(400, seed=3)
    with open(os.path.join(wd, "train.txt"), "w") as fh:
        fh.write("\n".join(lines) + "\n")
    cfg = _serve_cfg(wd, epoch_num=2)
    train(cfg)
    ckpt = CheckpointState(cfg.model_file)
    steps = list_step_dirs(ckpt.directory)
    assert len(steps) >= 2
    ckpt.publish_step(steps[0])
    ckpt.close()
    return cfg, steps, wd


def _server(cfg, **kw):
    from fast_tffm_tpu.serve import ScorerServer
    kw.setdefault("watch", False)
    return ScorerServer(cfg, **kw)


# --- pure helpers ----------------------------------------------------------


def test_batch_rung_ladder():
    from fast_tffm_tpu.serve.server import batch_rung_ladder
    assert batch_rung_ladder(1) == (1,)
    assert batch_rung_ladder(8) == (1, 2, 4, 8)
    assert batch_rung_ladder(100) == (1, 2, 4, 8, 16, 32, 64, 128)


def test_concat_blocks_roundtrip():
    from fast_tffm_tpu.serve.server import _concat_blocks
    a = parse_lines(["1 3:1.0 5:2.0", "0 7:1.0"], 200)
    b = parse_lines(["", "1 9:0.5"], 200, keep_empty=True)
    cat = _concat_blocks([a, b])
    assert cat.batch_size == 4
    assert list(cat.poses) == [0, 2, 3, 3, 4]
    assert list(cat.ids) == [3, 5, 7, 9]
    assert list(cat.sizes) == [2, 1, 0, 1]
    # Single block passes through untouched.
    assert _concat_blocks([a]) is a


# --- request path ----------------------------------------------------------


def test_score_matches_batch_predict(trained):
    """The serving contract: a request's scores are bit-identical to
    batch predict against the published step, whatever padded shapes
    the admission queue picked."""
    from fast_tffm_tpu.metrics import sigmoid
    from fast_tffm_tpu.predict import load_table, predict_scores
    cfg, steps, wd = trained
    server = _server(cfg)
    try:
        lines = _corpus_lines(7, seed=11)
        res = server.score_lines(lines, timeout=30)
        assert res.step == steps[0]
        assert len(res.scores) == len(lines)
        req = os.path.join(wd, "req_parity.txt")
        with open(req, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        table = load_table(cfg, step=steps[0])
        want = sigmoid(predict_scores(
            dataclasses.replace(cfg, metrics_file=""), table, [req]))
        assert np.array_equal(want, res.scores)
    finally:
        server.close()


def test_admission_coalesces_and_pads_to_rung(trained):
    """Concurrent submissions inside one wait window flush as ONE
    padded micro-batch; the padding is exactly rung - examples."""
    cfg, steps, _wd = trained
    server = _server(dataclasses.replace(cfg, serve_max_wait_ms=250.0))
    try:
        pendings = [server.submit([ln]) for ln in _corpus_lines(3, 7)]
        for p in pendings:
            p.result(timeout=30)
        st = server.stats()
        assert st["requests"] == 3
        assert st["examples"] == 3
        assert st["flushes"] == 1, "requests inside one admission " \
            "window must score as one micro-batch"
        # 3 examples pad to the 4-rung: 1 padded slot counted.
        c = server._reg.snapshot()["counters"]
        assert c.get("serve/padded_examples") == 1.0
    finally:
        server.close()


def test_flush_splits_at_max_batch(trained):
    """A window never exceeds serve_max_batch: 3 requests of 3 lines
    against max_batch=8 split 2+1 (the third becomes the next window's
    head — the carry path)."""
    cfg, steps, _wd = trained
    server = _server(dataclasses.replace(cfg, serve_max_wait_ms=250.0))
    try:
        lines = _corpus_lines(9, seed=13)
        pendings = [server.submit(lines[i:i + 3]) for i in (0, 3, 6)]
        got = [p.result(timeout=30) for p in pendings]
        assert all(len(r.scores) == 3 for r in got)
        assert server.stats()["flushes"] == 2
    finally:
        server.close()


def test_empty_and_blank_lines(trained):
    """Zero-line requests complete inline; blank lines keep predict's
    one-score-per-line alignment (they score as the model bias)."""
    cfg, steps, _wd = trained
    server = _server(cfg)
    try:
        empty = server.score_lines([], timeout=10)
        assert empty.scores.shape == (0,)
        assert empty.step == steps[0]
        lines = _corpus_lines(2, seed=17)
        res = server.score_lines([lines[0], "", lines[1]], timeout=30)
        assert len(res.scores) == 3
        blank = server.score_lines([""], timeout=30)
        assert res.scores[1] == blank.scores[0]
    finally:
        server.close()


def test_bad_request_fails_alone(trained):
    """A malformed line raises at submit, to that caller only — the
    server keeps serving the next request."""
    cfg, steps, _wd = trained
    server = _server(cfg)
    try:
        with pytest.raises(ParseError):
            server.submit(["1 not-a-feature"])
        with pytest.raises(ValueError, match="serve_max_batch"):
            server.submit(_corpus_lines(9, seed=23))
        res = server.score_lines(_corpus_lines(2, seed=19), timeout=30)
        assert len(res.scores) == 2
    finally:
        server.close()


def test_no_new_shapes_after_warmup(trained):
    """The no-recompile guarantee: every flushed device shape is a
    member of the pre-compiled [B rung, L rung] matrix, for request
    sizes spanning the whole ladder."""
    from fast_tffm_tpu.data.pipeline import _ladder_fit
    cfg, steps, _wd = trained
    server = _server(cfg)
    try:
        compiled = set(server.compiled_shapes)
        rng = np.random.default_rng(5)
        for k in (1, 2, 3, 5, 8):
            lines = _corpus_lines(k, seed=int(rng.integers(1 << 30)))
            server.score_lines(lines, timeout=30)
            rung = next(b for b in server._b_ladder if b >= k)
            block = server._parse(lines)
            L = _ladder_fit(max(int(block.sizes.max()), 1),
                            cfg.bucket_ladder)
            assert (rung, L) in compiled
    finally:
        server.close()


@pytest.mark.parametrize("n_features", [5, 17, 24, 39, 56])
def test_warmup_compiles_what_it_compiled_under_the_doubling_ladder(
        trained, n_features):
    """ISSUE 34: the pipeline's default ladder has 16 rungs where it
    had 6; the server keeps a doubling subset of whatever ladder it is
    given, so its warm-up compiles the programs it compiled before, and
    a request at a width between two kept rungs pads onto one of them
    (no shape beyond the warmed matrix)."""
    cfg, steps, _wd = trained
    doubling = (8, 16, 32, 64, 128, 256)
    assert FmConfig().bucket_ladder != doubling
    shapes = {}
    for ladder in (FmConfig().bucket_ladder, doubling):
        server = _server(dataclasses.replace(
            cfg, bucket_ladder=ladder, max_features_per_example=64))
        try:
            shapes[ladder] = server.compiled_shapes
            assert server._reg.snapshot()["gauges"][
                "serve/compiled_shapes"] == len(server.compiled_shapes)
            flushed = []
            score = server._scorer.score_batch
            server._scorer.score_batch = lambda table, batch: (
                flushed.append(batch.vals.shape), score(table, batch))[1]
            rng = np.random.default_rng(n_features)
            lines = ["1 " + " ".join(
                f"{i}:1.0" for i in sorted(rng.choice(
                    200, size=n_features, replace=False)))
                for _ in range(3)]
            assert len(server.score_lines(lines, timeout=30).scores) == 3
            assert flushed and set(flushed) <= set(server.compiled_shapes)
        finally:
            server.close()
    assert shapes[FmConfig().bucket_ladder] == shapes[doubling]
    assert len(shapes[doubling]) == 4 * 4   # B 1..8 x L 8, 16, 32, 64


# --- hot reload ------------------------------------------------------------


def test_reload_swaps_and_tags_responses(trained):
    from fast_tffm_tpu.checkpoint import write_published
    from fast_tffm_tpu.serve.reload import ReloadWatcher
    cfg, steps, _wd = trained
    s_old, s_new = steps[0], steps[-1]
    write_published(cfg.model_file + ".ckpt", s_old)
    server = _server(cfg)
    watcher = ReloadWatcher(server, poll_seconds=60)  # driven by hand
    try:
        lines = _corpus_lines(4, seed=29)
        before = server.score_lines(lines, timeout=30)
        assert before.step == s_old
        assert not watcher.poll_once()  # pointer unchanged: no reload
        write_published(cfg.model_file + ".ckpt", s_new)
        assert watcher.poll_once()
        assert server.served_step == s_new
        after = server.score_lines(lines, timeout=30)
        assert after.step == s_new
        # Different checkpoints genuinely score differently.
        assert not np.array_equal(before.scores, after.scores)
        assert server.stats()["reloads"] == 1
    finally:
        write_published(cfg.model_file + ".ckpt", s_old)
        server.close()


def test_reload_failure_keeps_serving(trained):
    """A published step that cannot be restored (GC'd, quarantined, or
    never existed) is a counted failure; the old table keeps serving
    and the next poll can heal."""
    from fast_tffm_tpu.checkpoint import write_published
    from fast_tffm_tpu.serve.reload import ReloadWatcher
    cfg, steps, _wd = trained
    write_published(cfg.model_file + ".ckpt", steps[0])
    server = _server(cfg)
    watcher = ReloadWatcher(server, poll_seconds=60)
    try:
        write_published(cfg.model_file + ".ckpt", 999999)  # gone step
        assert watcher.poll_once()
        st = server.stats()
        assert st["reload_failures"] == 1
        assert st["served_step"] == steps[0]  # unharmed
        assert st["published_step"] == 999999  # honest gauge: fmstat
        # reads this pair as STALE MODEL until the reload lands
        res = server.score_lines(_corpus_lines(2, seed=31), timeout=30)
        assert res.step == steps[0]
        # Heal: repoint at a real step, the next poll swaps.
        write_published(cfg.model_file + ".ckpt", steps[0])
        watcher.poll_once()
        assert server.stats()["published_step"] == steps[0]
    finally:
        write_published(cfg.model_file + ".ckpt", steps[0])
        server.close()


def test_server_requires_published_pointer(tmp_path, trained):
    from fast_tffm_tpu.serve import ScorerServer
    cfg, _steps, _wd = trained
    lonely = dataclasses.replace(
        cfg, model_file=str(tmp_path / "nothing" / "fm"))
    with pytest.raises(FileNotFoundError, match="published"):
        ScorerServer(lonely, watch=False)


# --- front ends ------------------------------------------------------------


def test_http_round_trip(trained):
    from fast_tffm_tpu.serve.frontend import make_http_server
    cfg, steps, _wd = trained
    server = _server(cfg)
    httpd = make_http_server(server, port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        host, port = httpd.server_address[:2]
        base = f"http://{host}:{port}"
        lines = _corpus_lines(3, seed=37)
        body = ("\n".join(lines) + "\n").encode()
        with urllib.request.urlopen(
                urllib.request.Request(f"{base}/score", data=body),
                timeout=30) as resp:
            assert resp.status == 200
            step = int(resp.headers["X-FM-Step"])
            text = resp.read().decode()
        assert step == steps[0]
        # The wire format is the .score file format: %.6f per line —
        # and matches the in-process client byte for byte.
        res = server.score_lines(lines, timeout=30)
        assert text == "".join(f"{v:.6f}\n" for v in res.scores)
        with urllib.request.urlopen(f"{base}/healthz",
                                    timeout=30) as resp:
            health = json.loads(resp.read().decode())
        assert health["served_step"] == steps[0]
        assert health["requests"] >= 2
        assert health["latency_p50_ms"] is not None
        # A malformed line is the CALLER's 400, not a server death.
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(f"{base}/score",
                                       data=b"1 nope\n"), timeout=30)
        assert ei.value.code == 400
        with urllib.request.urlopen(
                urllib.request.Request(f"{base}/score", data=body),
                timeout=30) as resp:
            assert resp.status == 200
        # Keep-alive stays in sync across a 404'd POST: the body must
        # be drained before the routing reply, or the SAME connection's
        # next request parses mid-body.
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=30)
        try:
            conn.request("POST", "/scores", body=body)
            r404 = conn.getresponse()
            assert r404.status == 404
            r404.read()  # consume so the connection can be reused
            conn.request("POST", "/score", body=body)
            resp2 = conn.getresponse()
            assert resp2.status == 200
            assert len(resp2.read().decode().splitlines()) == 3
        finally:
            conn.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.close()


def test_metrics_endpoint_prometheus(trained):
    """GET /metrics serves the obs registry in Prometheus text
    exposition format: counters/gauges bare, histograms as cumulative
    le-buckets + _sum/_count, correct content type — scrapeable
    without parsing JSONL."""
    from fast_tffm_tpu.serve.frontend import make_http_server
    cfg, steps, _wd = trained
    server = _server(cfg)
    httpd = make_http_server(server, port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        host, port = httpd.server_address[:2]
        server.score_lines(_corpus_lines(3, seed=53), timeout=30)
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=30) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            text = resp.read().decode()
        lines = text.splitlines()
        assert "# TYPE fm_serve_requests counter" in lines
        assert "fm_serve_requests 1" in lines
        assert "# TYPE fm_serve_served_step gauge" in lines
        assert f"fm_serve_served_step {steps[0]}" in lines
        # Histogram convention: cumulative buckets, +Inf, sum, count.
        assert ("# TYPE fm_serve_request_latency_ms histogram"
                in lines)
        buckets = [ln for ln in lines if ln.startswith(
            'fm_serve_request_latency_ms_bucket{le="')]
        assert buckets and buckets[-1].startswith(
            'fm_serve_request_latency_ms_bucket{le="+Inf"}')
        counts = [int(b.rsplit(" ", 1)[1]) for b in buckets]
        assert counts == sorted(counts)  # cumulative
        assert counts[-1] == 1
        assert any(ln.startswith("fm_serve_request_latency_ms_sum ")
                   for ln in lines)
        assert "fm_serve_request_latency_ms_count 1" in lines
        # The endpoint reflects the live registry: another request
        # bumps the counter on the next scrape.
        server.score_lines(_corpus_lines(2, seed=54), timeout=30)
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=30) as resp:
            assert "fm_serve_requests 2" in resp.read().decode()
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.close()


def test_close_is_clean_and_idempotent(trained):
    cfg, _steps, _wd = trained
    server = _server(cfg)
    server.score_lines(_corpus_lines(2, seed=41), timeout=30)
    server.close()
    server.close()
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(["1 3:1.0"])
    leaked = [t.name for t in threading.enumerate()
              if t.is_alive() and t.name.startswith("fm-serve")]
    assert not leaked, leaked


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_run_tffm_serve_process(trained):
    """The real `run_tffm.py serve` process end to end: starts against
    the published step, answers /score and /healthz over HTTP, and a
    SIGTERM drains to exit 0."""
    import signal
    import subprocess
    import sys
    cfg, steps, wd = trained
    port = _free_port()
    cfg_path = os.path.join(wd, "serve.cfg")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = {cfg.vocabulary_size}
factor_num = {cfg.factor_num}
model_file = {cfg.model_file}
[Train]
max_features_per_example = {cfg.max_features_per_example}
bucket_ladder = 8,16
[Serve]
serve_port = {port}
serve_max_batch = 8
serve_max_wait_ms = 2
serve_poll_seconds = 0.1
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "run_tffm.py"), "serve", cfg_path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        base = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 90
        health = None
        while time.monotonic() < deadline:
            assert proc.poll() is None, (
                f"serve process died: "
                f"{proc.stdout.read().decode()[-2000:]}")
            try:
                with urllib.request.urlopen(f"{base}/healthz",
                                            timeout=5) as resp:
                    health = json.loads(resp.read().decode())
                break
            except (urllib.error.URLError, ConnectionError):
                time.sleep(0.2)
        assert health is not None, "server never came up"
        assert health["served_step"] == steps[0]
        lines = _corpus_lines(3, seed=43)
        body = ("\n".join(lines) + "\n").encode()
        with urllib.request.urlopen(
                urllib.request.Request(f"{base}/score", data=body),
                timeout=30) as resp:
            assert resp.status == 200
            assert int(resp.headers["X-FM-Step"]) == steps[0]
            assert len(resp.read().decode().splitlines()) == 3
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0, (
            proc.stdout.read().decode()[-2000:])
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_sync_warmup_failure_is_fatal(trained):
    """`run_tffm.py serve` (one server) warms up synchronously: a shape
    ladder the device cannot compile ends the process non-zero with
    the compiler's error and never binds the port — it must not leave
    a server that is alive and never ready (that is the fleet
    replica's background mode, on purpose)."""
    import socket
    import subprocess
    import sys
    cfg, _steps, wd = trained
    port = _free_port()
    cfg_path = os.path.join(wd, "serve_warmup_fails.cfg")
    with open(cfg_path, "w") as fh:
        fh.write(f"""
[General]
vocabulary_size = {cfg.vocabulary_size}
factor_num = {cfg.factor_num}
model_file = {cfg.model_file}
[Train]
max_features_per_example = {cfg.max_features_per_example}
bucket_ladder = 8,16
[Serve]
serve_port = {port}
serve_max_batch = 8
""")
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    script = f"""
import runpy, sys
sys.path.insert(0, {repo!r})
from fast_tffm_tpu.serve.server import ScorerServer
def _no(self):
    raise RuntimeError("Mosaic failed to compile the serve ladder")
ScorerServer._warmup = _no
sys.argv = ["run_tffm.py", "serve", {cfg_path!r}]
runpy.run_path({os.path.join(repo, "run_tffm.py")!r}, run_name="__main__")
"""
    out = subprocess.run([sys.executable, "-c", script],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode not in (0, None), out.stdout[-2000:]
    assert "Mosaic failed to compile the serve ladder" in out.stderr
    assert "serving step" not in out.stdout + out.stderr
    with socket.socket() as s:            # the port was never bound
        assert s.connect_ex(("127.0.0.1", port)) != 0


# --- published-pointer edge cases (satellite) ------------------------------


def test_garbled_pointer_heals_on_next_poll(trained):
    from fast_tffm_tpu.checkpoint import read_published, write_published
    cfg, steps, _wd = trained
    d = cfg.model_file + ".ckpt"
    write_published(d, steps[0])
    with open(os.path.join(d, "published"), "w") as fh:
        fh.write("not a step")
    assert read_published(d) is None  # garbled reads as "nothing yet"
    server = None
    try:
        # ...and the reload poll treats it the same way: no crash, no
        # reload attempt, previous step keeps serving.
        write_published(d, steps[0])
        from fast_tffm_tpu.serve.reload import ReloadWatcher
        server = _server(cfg)
        with open(os.path.join(d, "published"), "w") as fh:
            fh.write("")
        watcher = ReloadWatcher(server, poll_seconds=60)
        assert not watcher.poll_once()
        assert server.served_step == steps[0]
        write_published(d, steps[0])  # heal
        assert read_published(d) == steps[0]
    finally:
        write_published(d, steps[0])
        if server is not None:
            server.close()


def test_repoint_is_atomic_under_concurrent_reader(tmp_path):
    """A reader polling the pointer during rapid repoints only ever
    sees complete values (the atomic-rename write): never a torn/empty
    read, never a step that was not written."""
    from fast_tffm_tpu.checkpoint import read_published, write_published
    d = str(tmp_path)
    write_published(d, 1)
    stop = threading.Event()
    seen = set()
    bad = []

    def reader():
        while not stop.is_set():
            v = read_published(d)
            if v is None:
                bad.append("torn/unreadable read")
            else:
                seen.add(v)

    t = threading.Thread(target=reader)
    t.start()
    for i in range(400):
        write_published(d, 1 if i % 2 else 2)
    stop.set()
    t.join()
    assert not bad, bad[:3]
    assert seen <= {1, 2}


def test_wait_for_published_blocks_until_flip(tmp_path):
    from fast_tffm_tpu.checkpoint import wait_for_published, \
        write_published
    d = str(tmp_path)
    assert wait_for_published(d, timeout=0.05,
                              poll_seconds=0.01) is None
    write_published(d, 7)
    assert wait_for_published(d, timeout=5, poll_seconds=0.01) == 7
    # ``last`` semantics: the current value does not count as news.
    assert wait_for_published(d, last=7, timeout=0.05,
                              poll_seconds=0.01) is None
    t = threading.Timer(0.05, write_published, args=(d, 9))
    t.start()
    try:
        assert wait_for_published(d, last=7, timeout=5,
                                  poll_seconds=0.01) == 9
    finally:
        t.join()


def test_retention_never_strands_reload(tmp_path):
    """The retention contract end to end: published_at_risk fires
    BEFORE max_to_keep would evict the published step, and a pointer
    that does dangle (the at-risk signal ignored) degrades to a
    counted reload failure on the server — staleness, not an outage.
    """
    from fast_tffm_tpu.checkpoint import (CheckpointState,
                                          list_step_dirs,
                                          read_published)
    cfg = FmConfig(vocabulary_size=256, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    ckpt = CheckpointState(cfg.model_file, max_to_keep=2)
    ckpt.save(1, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    ckpt.publish_step(1)
    assert not ckpt.published_at_risk()
    ckpt.save(2, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    # One more save evicts step 1: the stream driver must republish
    # FIRST (train.py's publish_due) — at_risk is that signal.
    assert ckpt.published_at_risk()
    ckpt.save(3, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    assert 1 not in list_step_dirs(ckpt.directory)  # evicted
    assert read_published(ckpt.directory) == 1      # dangling pointer
    assert ckpt.published_at_risk()  # still firing: republish heals
    ckpt.publish_step(3)
    assert not ckpt.published_at_risk()
    ckpt.close()


# --- fmckpt publish (satellite) --------------------------------------------


def test_fmckpt_publish_cli(trained, capsys):
    from fast_tffm_tpu.checkpoint import read_published
    from tools.fmckpt import main as fmckpt_main
    cfg, steps, _wd = trained
    d = cfg.model_file + ".ckpt"
    assert fmckpt_main(["publish", cfg.model_file,
                        str(steps[-1])]) == 0
    assert read_published(d) == steps[-1]
    out = capsys.readouterr().out
    assert "verified" in out
    # A missing step never moves the pointer.
    assert fmckpt_main(["publish", cfg.model_file, "424242"]) == 1
    assert read_published(d) == steps[-1]
    # Restore the module fixture's published step for later tests.
    assert fmckpt_main(["publish", cfg.model_file,
                        str(steps[0])]) == 0


def test_fmckpt_publish_refuses_torn_step(tmp_path, capsys):
    from fast_tffm_tpu.checkpoint import CheckpointState, read_published
    from fast_tffm_tpu.testing.faults import truncate_checkpoint
    from tools.fmckpt import cmd_publish
    cfg = FmConfig(vocabulary_size=256, factor_num=2,
                   model_file=str(tmp_path / "m" / "fm"))
    table = np.zeros((cfg.ckpt_rows, cfg.row_dim), np.float32)
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(1, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    ckpt.save(2, table, table, vocabulary_size=cfg.vocabulary_size,
              wait=True)
    ckpt.close()
    d = cfg.model_file + ".ckpt"
    assert cmd_publish(d, 1) == 0
    truncate_checkpoint(cfg.model_file, step=2)
    assert cmd_publish(d, 2) == 1
    assert read_published(d) == 1  # pointer still names verified bytes


# --- fmstat SERVING --------------------------------------------------------


def test_stale_model_verdict():
    from fast_tffm_tpu.obs.attribution import health_verdict, stale_model
    base = {"counters": {"serve/requests": 10}, "hists": {},
            "health_events": [], "crash_events": [],
            "run_starts": 1, "run_ends": 1}
    fresh = dict(base, gauges={"serve/served_step": 26.0,
                               "serve/published_step": 26.0})
    assert stale_model(fresh) is None
    assert health_verdict(fresh)["verdict"] == "OK"
    lagging = dict(base, gauges={"serve/served_step": 20.0,
                                 "serve/published_step": 26.0})
    assert stale_model(lagging) == (20.0, 26.0)
    hv = health_verdict(lagging)
    assert hv["verdict"] == "STALE MODEL"
    assert "reload" in hv["detail"]
    # No serve gauges at all: not a serving stream, no verdict.
    assert stale_model(dict(base, gauges={})) is None


def test_serving_render_section():
    from fast_tffm_tpu.obs.attribution import render
    from fast_tffm_tpu.obs.registry import Histogram
    lat = Histogram(bounds=(1.0, 5.0, 50.0))
    for v in (0.5, 2.0, 2.5, 40.0):
        lat.observe(v)
    summary = {
        "meta": {"kind": "serve"}, "metas": [], "runs": 1,
        "events": 5, "spans": 0, "run_starts": 1, "run_ends": 1,
        "health_events": [], "crash_events": [], "scalars": [],
        "counters": {"serve/requests": 4, "serve/examples": 9,
                     "serve/flushes": 3, "serve/reloads": 1},
        "hists": {"serve/request_latency_ms": lat.summary()},
        "gauges": {"serve/served_step": 26.0,
                   "serve/published_step": 26.0},
        "gauges_by_process": {},
    }
    text = render(summary)
    assert "SERVING (run_tffm.py serve):" in text
    assert "request latency p50 / p99" in text
    assert "hot reloads (failed)" in text
    assert "served / published step" in text
