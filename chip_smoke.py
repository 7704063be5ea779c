#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives BASELINE config #1 (2nd-order FM, k=8, hashed ids into 2^22
rows, batch 8192, 39 features per example in the L=64 bucket, logistic
loss, 2 epochs) through ``run_tffm.py`` exactly as a user would, on a
seeded synthetic corpus (data/synth.py; rows are corpus length, the
widths are never cut):

  1. ``train``   from a clean model dir (``kernel = pallas``): loss
     falls, a checkpoint lands;
  2. ``predict`` twice (cold, then warm from the compile cache): one
     finite score per test line, AUC within 0.01 of the NumPy oracle
     trained on the same data, and the warm run compiles nothing new;
  3. ``fmckpt publish`` + ``serve``: /healthz ready, POST /score bytes
     identical to the score file, X-FM-Step, SIGTERM -> clean exit 0;
  4. ``train`` again with ``kernel = xla``: final loss within 1e-4 of
     leg 1's — the compiled Pallas kernel computes the right thing.

On a host with more than one device the same script runs legs 1-2 on
the mesh path the CLI takes by itself there, and checks that the
row-sharded state landed evenly over the devices.

This parent never imports jax: every leg is a child process, one after
another, so the chip has one owner at a time. The oracle is NumPy.

It FAILS — non-zero exit, no result line — when jax finds no TPU, when
any leg fails, when a leg ran on the Python parser or the Pallas
interpreter, when leg 1's lowered train step holds no Mosaic custom
call, or when the device capacity came back unknown. On a pass stdout
is two JSON lines. The LAST is the verdict, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it and no other key (the driver's
contract). The line before it holds the OBSERVATIONS for later work
(ROADMAP S2/S5/S6/D8) — not benchmark metrics — and is also written to
``chiprun_out/chip_smoke/result.json``.

``--rehearse-cpu`` is the on-chip-measurement guide's "make the command
run here first": children run with JAX_PLATFORMS=cpu at a short corpus,
the TPU-only checks are reported instead of enforced, and the only
line printed carries ``"rehearsal": true, "platform": "cpu"``: there is
no verdict line. It is never a default and never read from the
environment.
"""

from __future__ import annotations

import argparse
import datetime
import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_SECONDS = 1100  # the contract allows 1200, compile included

# BASELINE config #1 (FM on the Criteo-Kaggle-like sample of
# data/synth.py): 2^22 rows of k = 8, batches of 8192, two epochs at
# learning rate 0.05 and lambda 1e-6. The bucket is this script's own:
# 39 features land in L=64 under the default ladder, the width at which
# the Pallas kernel applies. ``kernel = auto`` takes it there for raw
# ids only (serve); a one-chip train step and, since PR 45, a one-chip
# predict run on the host unique, where auto resolves to XLA (PERF.md
# section 6, PR 26), so leg 1 asks for the kernel by name.
VOCAB, K, BATCH, L, EPOCHS, LR, LAM = 1 << 22, 8, 8192, 64, 2, 0.05, 1e-6
SEED = 17
# Corpus length (train, test): not a width, and not a knob either.
CHIP_ROWS, REHEARSAL_ROWS = (262144, 32768), (65536, 8192)

# The wording of the three fallback log lines the negative checks look
# for. tests/test_bringup.py provokes each fallback and pins that its
# line still carries the mark, so a reworded message fails tier-1
# instead of silently disarming the check.
PYTHON_PARSER_MARK = "PYTHON parser"
INTERPRET_MARK = "INTERPRET mode"
WARMUP_FAILED_MARK = "serve warmup failed"
# obs/memory.preflight_capacity's log line: planned bytes, capacity. On
# a mesh it says "N bytes per device, 1/4 of ... over 4 devices, device
# capacity ..." (tests/test_bringup.py holds both forms to this).
PREFLIGHT_LINE = re.compile(
    r"capacity pre-flight \(train\): predicted resident (\d+) bytes"
    r"(?: per device, [^\n]*?)?, device capacity (\d+|UNKNOWN)")

PROBE = ("import json, jax; d = jax.devices(); print(json.dumps("
         "{'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))")


def verdict_line(device: dict) -> str:
    """The last line of a passing run's stdout, to the driver's
    contract: ``ok`` and ``device`` and nothing else, ``device`` being
    jax's platform, device_kind and device count and nothing else."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


class SmokeFailure(Exception):
    """A check did not hold; the message names what was found."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class Smoke:
    def __init__(self, args):
        self.rehearsal = args.rehearse_cpu
        self.rows, self.test_rows = (REHEARSAL_ROWS if self.rehearsal
                                     else CHIP_ROWS)
        self.expect_loss = args.expect_final_loss
        self.t_start = time.monotonic()
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        # Small things worth reading after a failure: the chip tool
        # brings this directory back; the corpus and model stay in tmp.
        self.out = os.path.join(
            os.getcwd(), "chiprun_out",
            "chip_smoke_rehearsal" if self.rehearsal else "chip_smoke")
        self.env = dict(os.environ)
        if self.rehearsal:
            self.env["JAX_PLATFORMS"] = "cpu"
        # The children's own rule for where the cache is (no jax in
        # that module's import).
        from fast_tffm_tpu import compile_cache
        self.cache_dir, self.cache_from_env = compile_cache.cache_dir(
            self.env)
        self.cache_entries = compile_cache.cache_entries
        self.live = []  # Popen objects still running
        self.walls = {}
        self.obs = {}

    # -- children ---------------------------------------------------------

    def remaining(self) -> float:
        left = DEADLINE_SECONDS - (time.monotonic() - self.t_start)
        check(left > 0, f"out of time: {DEADLINE_SECONDS}s deadline hit")
        return left

    def spawn(self, name: str, argv, extra_env=None) -> subprocess.Popen:
        env = dict(self.env, **(extra_env or {}))
        log = open(os.path.join(self.out, f"{name}.log"), "wb")
        proc = subprocess.Popen(argv, cwd=HERE, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        log.close()
        self.live.append(proc)
        return proc

    def reap(self, proc: subprocess.Popen, timeout: float) -> int:
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill(proc)
            raise SmokeFailure(f"pid {proc.pid} did not exit within "
                               f"{timeout:.0f}s; killed")
        self.live.remove(proc)
        return rc

    def kill(self, proc: subprocess.Popen) -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        if proc in self.live:
            self.live.remove(proc)

    def run(self, name: str, argv, extra_env=None) -> str:
        """One leg, run to its end; returns its log. A non-zero exit
        fails the smoke with the log's tail."""
        t0 = time.monotonic()
        proc = self.spawn(name, argv, extra_env)
        rc = self.reap(proc, self.remaining())
        self.walls[name] = round(time.monotonic() - t0, 1)
        text = self.log(name)
        check(rc == 0, f"leg {name} exited {rc}:\n{text[-4000:]}")
        return text

    def log(self, name: str) -> str:
        with open(os.path.join(self.out, f"{name}.log"),
                  errors="replace") as fh:
            return fh.read()

    def cli(self, *argv):
        return [sys.executable, os.path.join(HERE, "run_tffm.py"), *argv]

    # -- set-up -----------------------------------------------------------

    def probe_device(self) -> dict:
        out = subprocess.run(
            [sys.executable, "-c", PROBE], cwd=HERE, env=self.env,
            capture_output=True, text=True, timeout=self.remaining())
        check(out.returncode == 0,
              f"jax found no usable device:\n{out.stderr[-2000:]}")
        dev = json.loads(out.stdout.strip().splitlines()[-1])
        if not self.rehearsal:
            check(dev["platform"] == "tpu",
                  f"no accelerator: jax reports platform "
                  f"{dev['platform']!r} ({dev['kind']}, {dev['count']} "
                  "device(s)); chip_smoke.py passes only on a TPU")
        return dev

    def cfg_path(self, name: str) -> str:
        return os.path.join(self.work, f"{name}.cfg")

    def write_cfg(self, name: str, extra_train: str = "") -> None:
        """One config per model dir: ``model_<name>``, ``score_<name>``."""
        w = self.work
        with open(self.cfg_path(name), "w") as fh:
            fh.write(f"""
[General]
vocabulary_size = {VOCAB}
factor_num = {K}
hash_feature_id = True
model_file = {w}/model_{name}/fm
log_file = {w}/log/{name}.log

[Train]
train_files = {w}/train.txt
epoch_num = {EPOCHS}
batch_size = {BATCH}
learning_rate = {LR}
factor_lambda = {LAM}
bias_lambda = {LAM}
init_value_range = 0.01
loss_type = logistic
max_features_per_example = {L}
bucket_ladder = {L}
shuffle = False
log_steps = 4
{extra_train}

[Predict]
predict_files = {w}/test.txt
score_path = {w}/score_{name}

[Serve]
serve_port = {self.port}
""")

    # -- checks shared by the legs ----------------------------------------

    def run_meta(self, metrics_path: str) -> dict:
        with open(metrics_path) as fh:
            first = json.loads(fh.readline())
        check(first.get("event") == "run_start",
              f"{metrics_path}: no run_start event")
        return first["meta"]

    def last_metrics(self, metrics_path: str) -> dict:
        last = {}
        with open(metrics_path) as fh:
            for line in fh:
                e = json.loads(line)
                if e.get("event") == "metrics":
                    last = e
        return last

    def check_leg(self, name: str, text: str, metrics_path: str,
                  file_parser: bool = True) -> None:
        """What every leg owes: it ran on the probed device, on the C++
        parser, and never in the Pallas interpreter. ``file_parser``
        is False for serve, which parses each small request with the
        Python parser on the caller's thread by design (no fallback
        involved) and never loads the C++ one."""
        meta = self.run_meta(metrics_path)
        for key, want in (("platform", self.device["platform"]),
                          ("device_kind", self.device["kind"]),
                          ("device_count", self.device["count"])):
            check(meta.get(key) == want,
                  f"leg {name} ran on {key}={meta.get(key)!r}, the "
                  f"probe saw {want!r}")
        check(PYTHON_PARSER_MARK not in text,
              f"leg {name} fell back to the Python parser:\n"
              + "\n".join(ln for ln in text.splitlines()
                          if "parser" in ln))
        if file_parser:
            m = re.search(r"host parser: C\+\+ (\S+) \(([^)]*)\)", text)
            check(m is not None,
                  f"leg {name} never logged its host parser")
            self.obs.setdefault("parser", {})[name] = {
                "artifact": m.group(1), "how": m.group(2)}
        if not self.rehearsal:
            check(INTERPRET_MARK not in text,
                  f"leg {name} ran the Pallas kernel interpreted")

    def regime(self, name: str, text: str) -> dict:
        m = re.search(rf"{name} regime: backend=(\S+) devices=(\d+) "
                      r"dedup=(\S+) kernel=(\S+)", text)
        check(m is not None, f"no '{name} regime:' line in the log")
        return {"backend": m.group(1), "devices": int(m.group(2)),
                "dedup": m.group(3), "kernel": m.group(4)}

    def final_loss(self, name: str, text: str):
        m = re.search(r"training done: (\d+) steps, final loss "
                      r"([-\d.naife]+)", text)
        check(m is not None, f"leg {name} logged no 'training done'")
        return int(m.group(1)), float(m.group(2))

    # -- the legs ---------------------------------------------------------

    def leg_train(self, name: str, cfg_name: str, ir_dir=None) -> dict:
        metrics = os.path.join(self.work, f"{name}.metrics.jsonl")
        env = {"FM_METRICS_FILE": metrics}
        if ir_dir:
            env["JAX_DUMP_IR_TO"] = ir_dir
        text = self.run(name, self.cli("train", self.cfg_path(cfg_name)),
                        env)
        self.check_leg(name, text, metrics)
        steps, loss = self.final_loss(name, text)
        want_steps = EPOCHS * -(-self.rows // BATCH)
        check(steps == want_steps,
              f"leg {name} took {steps} steps, expected {want_steps}")
        check(loss == loss and abs(loss) != float("inf"),
              f"leg {name} final loss is {loss}")
        lines = [(int(s), float(v), t) for t, s, v in re.findall(
            r"^(\S+ \S+) INFO \S+ step (\d+) epoch \d+ loss ([-\d.naife]+)",
            text, re.M)]
        check(len(lines) >= 2, f"leg {name} logged {len(lines)} loss "
              "lines; need two to see the loss fall")
        check(lines[-1][1] < lines[0][1],
              f"leg {name} loss did not fall: step {lines[0][0]} "
              f"{lines[0][1]} -> step {lines[-1][0]} {lines[-1][1]}")
        ckpt = os.path.join(self.work, f"model_{cfg_name}", "fm.ckpt",
                            str(steps))
        check(os.path.isdir(ckpt), f"leg {name} left no checkpoint "
              f"at {ckpt}")
        out = {"steps": steps, "final_loss": loss,
               "first_logged_loss": lines[0][1],
               "regime": self.regime("train", text)}
        # Steps per second between loss lines: each line is written
        # right after a scalar fetch, so both ends have waited for the
        # device. The first window holds the compile and is left out.
        if len(lines) >= 3:
            def stamp(t):
                return datetime.datetime.strptime(t, "%Y-%m-%d %H:%M:%S,%f")
            dt = (stamp(lines[-1][2]) - stamp(lines[1][2])).total_seconds()
            if dt > 0:
                out["steps_per_sec_after_first_window"] = round(
                    (lines[-1][0] - lines[1][0]) / dt, 2)
        m = PREFLIGHT_LINE.search(text)
        check(m is not None, f"leg {name} logged no capacity pre-flight")
        out["plan_resident_bytes"] = int(m.group(1))
        if not self.rehearsal:
            check(m.group(2) != "UNKNOWN",
                  "device_capacity_bytes() came back unknown on a TPU: "
                  "the capacity pre-flight checked nothing")
        if m.group(2) != "UNKNOWN":
            out["capacity_bytes"] = int(m.group(2))
        gauges = self.last_metrics(metrics).get("gauges", {})
        for key, gauge in (("ledger_peak_bytes", "mem/peak_bytes"),
                           ("peak_bytes_in_use", "mem/device_peak_bytes"),
                           ("host_build_workers", "pipeline/host_threads")):
            if gauge in gauges:
                out[key] = int(gauges[gauge])
        return out

    def leg_predict(self, name: str, cfg_name: str) -> dict:
        metrics = os.path.join(self.work, f"{name}.metrics.jsonl")
        before = self.cache_entries(self.cache_dir)
        text = self.run(name, self.cli("predict", self.cfg_path(cfg_name)),
                        {"FM_METRICS_FILE": metrics})
        self.check_leg(name, text, metrics)
        m = re.search(r"predict sweep: \d+ files, (\d+) examples, "
                      r"(\d+) examples/s", text)
        check(m is not None and int(m.group(1)) == self.test_rows,
              f"leg {name} scored {m and m.group(1)} examples, the test "
              f"file has {self.test_rows}")
        return {"regime": self.regime("predict", text),
                "sweep_examples_per_sec_incl_compile": int(m.group(2)),
                "cache_programs_before": before,
                "cache_programs_after": self.cache_entries(self.cache_dir)}

    def leg_serve(self, cfg_name: str, steps: int, score_lines) -> dict:
        model = os.path.join(self.work, f"model_{cfg_name}", "fm")
        text = self.run("publish", [sys.executable, "-m", "tools.fmckpt",
                                    "publish", model, str(steps)])
        check(f"published step {steps}" in text,
              f"fmckpt publish said:\n{text[-1000:]}")
        metrics = os.path.join(self.work, "serve.metrics.jsonl")
        t0 = time.monotonic()
        proc = self.spawn("serve",
                          self.cli("serve", self.cfg_path(cfg_name)),
                          {"FM_METRICS_FILE": metrics})
        base = f"http://127.0.0.1:{self.port}"
        health = None
        while health is None or not health.get("ready"):
            check(proc.poll() is None, "serve exited before it was "
                  f"ready:\n{self.log('serve')[-4000:]}")
            self.remaining()
            try:
                with urllib.request.urlopen(f"{base}/healthz",
                                            timeout=5) as resp:
                    health = json.loads(resp.read().decode())
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.5)
        ready_wall = round(time.monotonic() - t0, 1)
        check(health["served_step"] == steps,
              f"serving step {health['served_step']}, published {steps}")
        with open(os.path.join(self.work, "test.txt")) as fh:
            test_lines = fh.read().splitlines(keepends=True)
        n = len(test_lines)
        cuts = [(0, 1), (1, 8), (n // 2, n // 2 + 64), (n - 256, n)]
        for a, b in cuts:
            body = "".join(test_lines[a:b]).encode()
            try:
                with urllib.request.urlopen(
                        urllib.request.Request(f"{base}/score", data=body),
                        timeout=60) as resp:
                    got = resp.read()
                    step = resp.headers.get("X-FM-Step")
            except urllib.error.HTTPError as e:
                raise SmokeFailure(
                    f"POST /score of test lines [{a}:{b}] -> {e.code}: "
                    f"{e.read()[:500]!r}")
            check(step == str(steps),
                  f"X-FM-Step {step!r}, want {steps}")
            want = "".join(score_lines[a:b]).encode()
            check(got == want,
                  f"serve bytes differ from the score file on test lines "
                  f"[{a}:{b}]: {got[:60]!r} vs {want[:60]!r}")
        proc.send_signal(signal.SIGTERM)
        rc = self.reap(proc, min(120, self.remaining()))
        self.walls["serve"] = round(time.monotonic() - t0, 1)
        text = self.log("serve")
        check(rc == 0, f"serve exited {rc} on SIGTERM:\n{text[-4000:]}")
        check("scorer server closed" in text,
              f"serve never logged 'scorer server closed':\n{text[-2000:]}")
        self.check_leg("serve", text, metrics, file_parser=False)
        check(WARMUP_FAILED_MARK not in text, "serve warm-up failed")
        m = re.search(r"pre-compiled (\d+) serve shapes .* in ([\d.]+)s",
                      text)
        check(m is not None, "serve logged no warm-up")
        return {"regime": self.regime("serve", text),
                "ready_after_seconds": ready_wall,
                "warmup_shapes": int(m.group(1)),
                "warmup_seconds": float(m.group(2)),
                "requests_checked": len(cuts)}

    def mosaic_in_train_step(self, ir_dir: str) -> bool:
        """Whether leg 1's lowered train step (jax's own IR dump of the
        module it compiled) holds a Mosaic custom call."""
        mods = glob.glob(os.path.join(ir_dir, "*train_step*"))
        check(bool(mods), f"jax dumped no train-step module to {ir_dir}: "
              f"{sorted(os.listdir(ir_dir))[:40]}")
        for path in mods:
            with open(path, errors="replace") as fh:
                if "tpu_custom_call" in fh.read():
                    return True
        return False

    # -- the whole thing --------------------------------------------------

    def main(self) -> dict:
        self.device = self.probe_device()
        one_chip = self.device["count"] == 1
        shutil.rmtree(self.out, ignore_errors=True)
        os.makedirs(self.out)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]

        # Corpus and oracle are NumPy in this process; neither module
        # imports jax (asserted at the end), so the chip stays free for
        # the children.
        from fast_tffm_tpu.data import synth
        from fast_tffm_tpu.metrics import exact_auc
        import numpy as np
        t0 = time.monotonic()
        train, test = (os.path.join(self.work, f) for f in
                       ("train.txt", "test.txt"))
        meta = synth.write_dataset(train, test, self.rows, self.test_rows,
                                   seed=SEED)
        self.walls["generate"] = round(time.monotonic() - t0, 1)

        cache_before = self.cache_entries(self.cache_dir)
        self.write_cfg("pallas", "kernel = pallas")
        ir_dir = os.path.join(self.work, "ir_train")
        os.makedirs(ir_dir)
        t1 = self.leg_train("train", "pallas", ir_dir)
        # The mesh path resolves to the XLA kernel (host dedup), so the
        # Mosaic question is only asked of the one-chip step.
        mosaic = self.mosaic_in_train_step(ir_dir) if one_chip else None
        if one_chip and not self.rehearsal:
            check(t1["regime"]["dedup"] == "host"
                  and t1["regime"]["kernel"] == f"L{L}:pallas",
                  f"one chip should train on the host unique, with the "
                  f"Pallas kernel it was asked for at L={L}; got "
                  f"{t1['regime']}")
            check(mosaic, "leg 1's lowered train step holds no Mosaic "
                  "custom call (tpu_custom_call)")

        p_cold = self.leg_predict("predict_cold", "pallas")
        score_path = os.path.join(self.work, "score_pallas",
                                  "test.txt.score")
        with open(score_path) as fh:
            score_lines = fh.read().splitlines(keepends=True)
        cold_bytes = "".join(score_lines)
        p_warm = self.leg_predict("predict_warm", "pallas")
        with open(score_path) as fh:
            check(fh.read() == cold_bytes,
                  "the warm predict wrote different scores")
        check(p_warm["cache_programs_after"]
              == p_warm["cache_programs_before"],
              f"the warm predict compiled something new: cache went "
              f"{p_warm['cache_programs_before']} -> "
              f"{p_warm['cache_programs_after']} programs")
        check(len(score_lines) == self.test_rows,
              f"{len(score_lines)} scores for {self.test_rows} lines")
        scores = np.array([float(x) for x in score_lines])
        check(bool(np.isfinite(scores).all()), "non-finite scores")
        labels = np.loadtxt(test, usecols=0)
        auc = float(exact_auc(scores, labels))

        result = {"train": t1, "predict_cold": p_cold,
                  "predict_warm": p_warm}
        if one_chip:
            result["serve"] = self.leg_serve("pallas", t1["steps"],
                                             score_lines)
            self.write_cfg("xla", "kernel = xla")
            t2 = self.leg_train("train_xla", "xla")
            check(t2["regime"]["kernel"] == f"L{L}:xla",
                  f"kernel = xla resolved to {t2['regime']}")
            result["train_xla"] = t2
            gap = abs(t1["final_loss"] - t2["final_loss"])
            check(gap <= 1e-4,
                  f"Pallas and XLA final losses differ by {gap:.2e}: "
                  f"{t1['final_loss']} vs {t2['final_loss']}")
        else:
            m = re.search(r"mesh training: (\{[^}]*\}) over (\d+) devices.*"
                          r"bytes in use per local device: (\[[\d, ]+\]|"
                          r"unmeasured)", self.log("train"))
            check(m is not None, "no 'mesh training:' line with "
                  "per-device bytes in leg 1's log")
            check(int(m.group(2)) == self.device["count"],
                  f"mesh spans {m.group(2)} devices of "
                  f"{self.device['count']}")
            result["mesh"] = {"shape": m.group(1)}
            if m.group(3) == "unmeasured":
                check(self.rehearsal, "per-device bytes in use came back "
                      "unmeasured on a TPU")
            else:
                per_dev = json.loads(m.group(3))
                check(len(per_dev) == self.device["count"]
                      and max(per_dev) <= 1.5 * min(per_dev),
                      f"state is not spread evenly: bytes in use per "
                      f"device {per_dev} (a table that landed on the "
                      "first chip?)")
                result["mesh"]["bytes_in_use_per_device"] = per_dev
        if self.expect_loss is not None:
            gap = abs(t1["final_loss"] - self.expect_loss)
            check(gap <= 1e-3, f"final loss {t1['final_loss']} is "
                  f"{gap:.2e} from the expected {self.expect_loss}")
            result["expected_final_loss"] = self.expect_loss

        # The oracle: outside everything timed above.
        t0 = time.monotonic()
        tr = synth.parse_file_blocks(train, VOCAB, BATCH)
        te = synth.parse_file_blocks(test, VOCAB, BATCH)
        oracle_auc = float(exact_auc(synth.numpy_fm_train_predict(
            tr, te, VOCAB, k=K, lr=LR, epochs=EPOCHS, factor_lambda=LAM,
            bias_lambda=LAM), labels))
        self.walls["oracle"] = round(time.monotonic() - t0, 1)
        check(abs(auc - oracle_auc) <= 0.01,
              f"score-file AUC {auc:.4f} is not within 0.01 of the "
              f"oracle's {oracle_auc:.4f}")
        check("jax" not in sys.modules,
              "this parent imported jax; it must stay off the chip")

        return {
            "device": self.device,
            "config": {"vocabulary_size": VOCAB, "factor_num": K,
                       "batch_size": BATCH, "bucket": L, "epochs": EPOCHS,
                       "train_rows": self.rows,
                       "test_rows": self.test_rows, "seed": SEED},
            "wall_seconds": self.walls,
            "total_seconds": round(time.monotonic() - self.t_start, 1),
            "compile_cache": {
                "dir": self.cache_dir,
                "from_env": self.cache_from_env,
                "programs_at_start": cache_before,
                "cold": cache_before == 0,
                "predict_cold_wall": self.walls["predict_cold"],
                "predict_warm_wall": self.walls["predict_warm"]},
            "mosaic_custom_call_in_train_step": mosaic,
            "test_auc": round(auc, 4),
            "oracle_auc": round(oracle_auc, 4),
            "bayes_auc": round(meta["bayes_auc"], 4),
            "observations": dict(result, **self.obs),
        }

    def close(self) -> None:
        for proc in list(self.live):
            self.kill(proc)
        shutil.rmtree(self.work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the children on the CPU backend at a "
                    "short corpus; reports, never passes")
    ap.add_argument("--expect-final-loss", type=float,
                    help="another run's leg-1 final loss (the one-chip "
                    "run's, on a four-chip host): must agree to 1e-3")
    args = ap.parse_args(argv)
    if not os.path.exists(os.path.join(HERE, "run_tffm.py")) \
            or not os.path.isdir(os.path.join(HERE, "fast_tffm_tpu")):
        print(f"chip_smoke.py: {HERE} holds no run_tffm.py / "
              "fast_tffm_tpu: there is no program here to start",
              file=sys.stderr)
        return 3
    sys.path.insert(0, HERE)
    smoke = Smoke(args)
    try:
        out = smoke.main()
    except SmokeFailure as e:
        print(f"chip_smoke.py FAILED: {e}", file=sys.stderr)
        return 2
    finally:
        smoke.close()
    if smoke.rehearsal:
        out = dict({"rehearsal": True,
                    "platform": out["device"]["platform"]}, **out)
    line = json.dumps(out)
    with open(os.path.join(smoke.out, "result.json"), "w") as fh:
        fh.write(line + "\n")
    print(line)
    if not smoke.rehearsal:
        print(verdict_line(out["device"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
