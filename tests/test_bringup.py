"""Bring-up guards (ISSUE 21): the pieces between ``run_tffm.py`` and
the first device step must never hide which device, parser, kernel
mode or compile cache a run is actually on."""

import json
import logging
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Records every jax.config.update the helper makes, then reports what
# jax ended up with — run in a child because the cache directory is
# process-global jax config.
_CACHE_PROBE = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), real(k, v))[1]
from fast_tffm_tpu.compile_cache import enable_compilation_cache
path = enable_compilation_cache()
from jax._src import xla_bridge
print(json.dumps({{
    "returned": path, "calls": calls,
    "dir": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "backend_initialised": xla_bridge.backends_are_initialized()}}))
"""


def _cache_probe(cwd, env_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(repo=REPO)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_policy(tmp_path):
    """Env set -> the code sets no directory; unset -> one fixed path
    inside the checkout whatever the cwd; the cache-everything
    threshold holds in both cases; no backend is initialised."""
    a = _cache_probe(str(tmp_path))
    b = _cache_probe(REPO)
    want = os.path.join(REPO, ".jax_cache")
    assert a["dir"] == b["dir"] == a["returned"] == want
    env_dir = str(tmp_path / "from_env")
    c = _cache_probe(str(tmp_path), env_dir=env_dir)
    assert "jax_compilation_cache_dir" not in c["calls"]
    assert c["dir"] == c["returned"] == env_dir  # jax read it itself
    assert a["min_secs"] == b["min_secs"] == c["min_secs"] == 0
    assert not (a["backend_initialised"] or c["backend_initialised"])


def test_unusable_cache_dir_is_an_error(tmp_path, monkeypatch):
    from fast_tffm_tpu.compile_cache import enable_compilation_cache
    blocker = tmp_path / "a_file"
    blocker.write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(blocker / "cache"))
    with pytest.raises(OSError):
        enable_compilation_cache()


def test_interpret_mode_only_on_cpu():
    from fast_tffm_tpu.ops.pallas_fm import _interpret_on
    assert _interpret_on("cpu") is True
    assert _interpret_on("tpu") is False
    for backend in ("gpu", "rocm", "some_plugin"):
        with pytest.raises(RuntimeError, match="kernel = xla"):
            _interpret_on(backend)


def test_pallas_block_is_sublane_multiple_or_whole():
    from fast_tffm_tpu.ops.pallas_fm import _block_b
    assert _block_b(8192, 8, 64) == 512
    for B in (1, 2, 24, 100, 512):
        assert _block_b(B, 8, 64) == B          # one block: whole array
    b = _block_b(8200, 8, 64)
    assert b % 8 == 0 and 8200 % b == 0 and b <= 512
    with pytest.raises(ValueError, match="batch_size"):
        _block_b(8191, 8, 64)                    # prime: no legal block


def test_foreign_parser_binary_is_rebuilt_not_loaded(tmp_path, monkeypatch):
    """A binary that came in with the tree from another CPU carries
    another build key in its name: the loader never opens it (nor a
    bare legacy ``_parser.so``) and builds its own beside it."""
    from fast_tffm_tpu.data import cparser
    shutil.copy(cparser._SRC, tmp_path / "_parser.cc")
    monkeypatch.setattr(cparser, "_SRC", str(tmp_path / "_parser.cc"))
    monkeypatch.setattr(cparser, "_SO", str(tmp_path / "_parser.so"))
    monkeypatch.setattr(cparser, "_lib", None)
    monkeypatch.setattr(cparser, "_load_error", None)
    here = cparser.artifact_path()
    with monkeypatch.context() as m:
        m.setattr(cparser, "_cpu_identity", lambda: "another-machine")
        foreign = cparser.artifact_path()
    assert foreign != here
    junk = b"built with -march=native somewhere else"
    for path in (foreign, str(tmp_path / "_parser.so")):
        with open(path, "wb") as fh:
            fh.write(junk)               # dlopen of this would fail
    assert not os.path.exists(here)
    lib = cparser._load()
    assert lib.fm_abi_version() == cparser._ABI_VERSION
    assert os.path.exists(here)
    for path in (foreign, str(tmp_path / "_parser.so")):
        with open(path, "rb") as fh:
            assert fh.read() == junk     # never touched, never opened


def test_build_key_covers_source_and_flags(tmp_path, monkeypatch):
    from fast_tffm_tpu.data import cparser
    src = tmp_path / "_parser.cc"
    shutil.copy(cparser._SRC, src)
    monkeypatch.setattr(cparser, "_SRC", str(src))
    base = cparser.build_key()
    with monkeypatch.context() as m:
        m.setattr(cparser, "_CXXFLAGS", cparser._CXXFLAGS + ("-DX",))
        assert cparser.build_key() != base
    with open(src, "a") as fh:
        fh.write("\n// edited\n")
    assert cparser.build_key() != base


def test_run_meta_names_platform_and_device_kind():
    import jax
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.obs.telemetry import run_meta
    meta = run_meta(FmConfig(), "train")
    dev = jax.devices()[0]
    assert meta["platform"] == dev.platform == "cpu"
    assert meta["device_kind"] == dev.device_kind
    assert meta["backend"] == "cpu"


def test_regime_line_names_what_auto_resolved_to():
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.models.fm import ModelSpec, regime_line
    cfg = FmConfig(bucket_ladder=(8, 64))
    line = regime_line(ModelSpec.from_config(cfg), cfg)
    assert "backend=cpu" in line and "kernel=L8:xla,L64:xla" in line
    assert "dedup=host" in line      # 8 forced devices: the mesh rule
    assert "kernel = auto" in line


class _Records(logging.Handler):
    """propagate is off on the run logger, so caplog never sees it."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_fallback_log_lines_carry_the_marks_chip_smoke_greps(
        tmp_path, monkeypatch):
    """chip_smoke.py's negative checks look for three log lines by
    their wording. Provoke each fallback and pin that the line it
    writes — at WARNING or above — still carries the mark."""
    import types
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    seen = _Records()
    logger = logging.getLogger("fast_tffm_tpu")
    logger.addHandler(seen)
    try:
        # 1. The C++ parser cannot be built -> the Python parser.
        from fast_tffm_tpu.data import cparser
        monkeypatch.setattr(cparser, "_SO", str(tmp_path / "_parser.so"))
        monkeypatch.setattr(cparser, "_lib", None)
        monkeypatch.setattr(cparser, "_load_error", None)

        def _no_compiler(out):
            raise OSError("g++: not found")

        monkeypatch.setattr(cparser, "_build", _no_compiler)
        assert cparser.available() is False
        # 2. The Pallas kernel on the cpu backend -> the interpreter.
        from fast_tffm_tpu.ops.pallas_fm import _interpret_on
        _interpret_on.cache_clear()
        assert _interpret_on("cpu") is True
        # 3. A background serve warm-up that cannot compile.
        from fast_tffm_tpu.obs.registry import MetricsRegistry
        from fast_tffm_tpu.serve.server import ScorerServer

        def _cannot_compile():
            raise RuntimeError("no such shape")

        stub = types.SimpleNamespace(
            _warmup=_cannot_compile, _reg=MetricsRegistry(),
            _logger=logger, _warmup_error=None)
        ScorerServer._warmup_bg(stub)
        assert isinstance(stub._warmup_error, RuntimeError)
    finally:
        logger.removeHandler(seen)
    loud = [r.getMessage() for r in seen.records
            if r.levelno >= logging.WARNING]
    for mark in (chip_smoke.PYTHON_PARSER_MARK, chip_smoke.INTERPRET_MARK,
                 chip_smoke.WARMUP_FAILED_MARK):
        assert sum(mark in m for m in loud) == 1, (mark, loud)


@pytest.mark.parametrize("shards", [1, 4])
def test_chip_smoke_reads_the_preflight_line_of_a_mesh_too(
        monkeypatch, shards):
    """The pre-flight's log line gained "per device, 1/4 of ..." on a
    mesh (PR 27) and chip_smoke.py's reader did not follow: the
    four-chip smoke failed with "logged no capacity pre-flight" until
    PR 29 ran it again. Hold the reader to the line the program
    writes, for one device and for a mesh."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.obs import memory
    monkeypatch.setenv(memory.FAKE_CAPACITY_ENV, str(1 << 30))
    seen = _Records()
    logger = logging.getLogger("fast_tffm_tpu")
    logger.addHandler(seen)
    try:
        memory.preflight_capacity(
            FmConfig(vocabulary_size=4096, factor_num=8), "train",
            shards=shards)
    finally:
        logger.removeHandler(seen)
    lines = [r.getMessage() for r in seen.records]
    found = [chip_smoke.PREFLIGHT_LINE.search(m) for m in lines]
    found = [m for m in found if m]
    assert len(found) == 1, lines
    assert ("per device" in found[0].group(0)) == (shards > 1)
    assert int(found[0].group(1)) > 0
    assert int(found[0].group(2)) == 1 << 30


def _smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_to_pass_without_a_tpu(tmp_path):
    """The driver's argument-less invocation on a machine with no TPU:
    non-zero, names the platform it found, prints no result."""
    out = _smoke(str(tmp_path), os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "'cpu'" in out.stderr and "no accelerator" in out.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no program here" in out.stderr


def test_chip_smoke_last_stdout_line_is_the_verdict_and_nothing_else(
        tmp_path, monkeypatch, capsys):
    """The driver reads the LAST line of stdout and takes exactly
    ``{"ok", "device": {"platform", "kind", "count"}}``: observations
    ride on the line before it, never beside ``ok``. A rehearsal prints
    no verdict at all."""
    import json
    import types
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def _stub(rehearsal):
        return lambda args: types.SimpleNamespace(
            rehearsal=rehearsal, out=str(tmp_path), close=lambda: None,
            main=lambda: {"device": dict(device), "test_auc": 0.65,
                          "observations": {"train": {"steps": 64}}})

    monkeypatch.setattr(chip_smoke, "Smoke", _stub(False))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert json.loads(lines[-2])["observations"]["train"]["steps"] == 64
    with open(tmp_path / "result.json") as fh:
        assert json.loads(fh.read()) == json.loads(lines[-2])

    monkeypatch.setattr(chip_smoke, "Smoke", _stub(True))
    assert chip_smoke.main(["--rehearse-cpu"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1 and "ok" not in json.loads(lines[0])
    assert json.loads(lines[0])["rehearsal"] is True
