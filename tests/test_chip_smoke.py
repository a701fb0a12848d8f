"""chip_smoke.py, rehearsed on the CPU (the first rehearsal of the
``on-chip-measurement`` guide): every phase function runs in-process at a
tiny ``--cfg`` size with the platform check steered HERE (monkeypatch —
the script has no option for it), so paths, arguments, control flow and
each check are exercised at no chip time.  On the CPU the programs hold
no Pallas kernel, so every phase must come out ``ok: false`` for exactly
that reason — the test that a run which took the oracle branch fails.
The script itself, run as a child with no chip, must refuse before any
model is built.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ("tpu__SCALES=((64,96),)", "tpu__MAX_GT=8",
        "network__ANCHOR_SCALES=(2,4)",
        "TRAIN__RPN_PRE_NMS_TOP_N=200", "TRAIN__RPN_POST_NMS_TOP_N=32",
        "TRAIN__BATCH_ROIS=16",
        "TEST__RPN_PRE_NMS_TOP_N=200", "TEST__RPN_POST_NMS_TOP_N=32")
FAKE_TPU = {"platform": "tpu", "kind": "rehearsal", "count": 8}
DEVICE_KEYS = {"platform", "kind", "count"}   # the contract's last line


@pytest.fixture
def as_if_on_chip(monkeypatch):
    monkeypatch.setattr(chip_smoke, "device_doc", lambda: dict(FAKE_TPU))


def _one_json_line(capsys) -> dict:
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 1, lines
    return json.loads(lines[0])


def _failed_only(doc: dict, *names: str) -> None:
    """Every check passed except exactly ``names``."""
    failed = sorted(k for k, v in doc["checks"].items() if not v)
    assert failed == sorted(names), doc["checks"]
    assert doc["ok"] is False


def test_train_phase_tiny(as_if_on_chip, capsys):
    doc = chip_smoke.phase_train(cfg=TINY, network="resnet50", steps=5)
    assert _one_json_line(capsys) == doc
    assert doc["phase"] == "train" and doc["device"] == FAKE_TPU
    _failed_only(doc, "kernel_in_program")
    # steps 1+2 share the first Speedometer line, then one loss per step
    assert len(doc["losses"]) == 4
    assert doc["program"]["tpu_custom_calls"] == 0
    assert doc["setup_s"] > 0 and doc["steady_s"] > 0


def test_serve_phase_tiny(as_if_on_chip, capsys):
    doc = chip_smoke.phase_serve(cfg=TINY, network="resnet50")
    assert _one_json_line(capsys) == doc
    _failed_only(doc, "kernel_in_program")
    assert doc["requests"] == chip_smoke.SERVE_REQUESTS
    # one program per orientation, both from warmup
    assert doc["warmup_programs"] == doc["recompiles"] == 2
    assert doc["aot_hit"] + doc["aot_miss"] == 2
    assert doc["cache_unavailable"] == 0
    assert doc["native"] is True
    # random weights at TEST.THRESH 1e-3: the per-image cap fills
    assert doc["detections"] > 0


def test_kernels_phase_tiny(as_if_on_chip, capsys):
    doc = chip_smoke.phase_kernels(shapes=((300, 50), (100, 300)), seeds=2)
    assert _one_json_line(capsys) == doc
    # off the chip the public nms_pallas IS the oracle: equal, no kernel
    _failed_only(doc, "kernel_in_program")
    assert doc["cases"] == 4 and doc["mismatches"] == []


def test_dp_phase_tiny_on_four_virtual_devices(as_if_on_chip, capsys):
    # the second rehearsal: the four-chip path on four of the suite's
    # virtual CPU devices.  The band is a rehearsal band — bf16 re-fusion
    # jitter between the 4-way and the 1-device program reaches a few
    # percent within five steps at this tiny size
    doc = chip_smoke.phase_dp(cfg=TINY, network="resnet50", steps=5,
                              band=0.1)
    assert _one_json_line(capsys) == doc
    _failed_only(doc, "dp4_kernel_in_program", "dp1_kernel_in_program")
    assert doc["dp4"]["program"]["partitions"] == 4
    assert doc["dp1"]["program"]["partitions"] == 1
    assert len(doc["loss_rel_dev"]) == 4


def test_dp_phase_needs_four_devices(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "device_doc",
                        lambda: dict(FAKE_TPU, count=1))
    _failed_only(chip_smoke.phase_dp(), "four_devices")


@pytest.mark.parametrize("phase", sorted(chip_smoke.PHASES))
def test_phase_refuses_without_chip(phase, capsys):
    # the real device_doc: this suite's CPU.  Nothing may be built.
    doc = chip_smoke.PHASES[phase]()
    assert _one_json_line(capsys) == doc
    assert doc["checks"] == {"platform_is_tpu": False}
    assert doc["ok"] is False and doc["device"]["platform"] == "cpu"
    assert doc["seconds"] < 5.0
    assert chip_smoke.run_phase(phase) == 1


def _check_last_line(out: str, ok: bool) -> dict:
    last = json.loads(out.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == DEVICE_KEYS
    assert last["ok"] is ok
    return last


def test_script_exits_nonzero_without_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    last = _check_last_line(proc.stdout, ok=False)
    assert last["device"]["platform"] == "cpu"
    # the first phase refused on the platform alone and no other started
    docs = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
    assert [d.get("phase") for d in docs] == ["train", None]
    assert docs[0]["checks"] == {"platform_is_tpu": False}
    assert "training on" not in proc.stderr   # train_net never ran


@pytest.mark.parametrize("child,why", [
    ("raise SystemExit(3)", "exits non-zero without a line"),
    ("print('{\"phase\": \"train\", \"ok\": true, \"device\": "
     "{\"platform\": \"tpu\", \"kind\": \"x\", \"count\": 1}}'); "
     "raise SystemExit(3)", "passing line but non-zero exit"),
    ("print('{\"phase\": \"train\", \"ok\": false, \"device\": "
     "{\"platform\": \"tpu\", \"kind\": \"x\", \"count\": 1}}')",
     "zero exit but the line says not ok"),
    ("raise RuntimeError('phase blew up')", "a phase that raises"),
])
def test_failing_child_ends_the_run_nonzero(monkeypatch, capsys, child, why):
    started = []

    def argv(name):
        started.append(name)
        return [sys.executable, "-c", child]

    monkeypatch.setattr(chip_smoke, "_child_argv", argv)
    assert chip_smoke.main([]) == 1, why
    _check_last_line(capsys.readouterr().out, ok=False)
    assert started == ["train"]   # no later phase runs after a failure


def test_run_phase_lets_an_exception_out(monkeypatch):
    def boom():
        raise RuntimeError("phase blew up")

    monkeypatch.setitem(chip_smoke.PHASES, "train", boom)
    with pytest.raises(RuntimeError):
        chip_smoke.run_phase("train")


@pytest.mark.parametrize("argv,phases", [
    ([], ["train", "native", "serve", "kernels"]),
    (["--chips", "4"], ["dp"]),
])
def test_main_phase_order_and_last_line(monkeypatch, capsys, argv, phases):
    ran = []
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": len(phases)}

    def child(name, timeout):
        ran.append(name)
        assert timeout > 0
        return 0, {"phase": name, "ok": True, "device": device}

    monkeypatch.setattr(chip_smoke, "_run_child", child)
    monkeypatch.setattr(chip_smoke, "build_native",
                        lambda: ran.append("native"))
    assert chip_smoke.main(argv) == 0
    assert ran == phases
    assert _check_last_line(capsys.readouterr().out, ok=True)["device"] \
        == device


@pytest.mark.skipif(not chip_smoke._have_toolchain(),
                    reason="no native toolchain on this box")
def test_build_native_replaces_a_stale_library_and_fails_loudly(
        monkeypatch, tmp_path):
    ndir = tmp_path / "mx_rcnn_tpu" / "native"
    shutil.copytree(os.path.join(REPO, "mx_rcnn_tpu", "native", "src"),
                    ndir / "src")
    shutil.copy(os.path.join(REPO, "mx_rcnn_tpu", "native", "Makefile"),
                ndir)
    so = ndir / "libmxr_native.so"
    so.write_bytes(b"stale: not what the committed source builds")
    monkeypatch.setattr(chip_smoke, "REPO", str(tmp_path))
    chip_smoke.build_native()
    assert so.read_bytes()[:4] == b"\x7fELF"

    (ndir / "src" / "mxr_native.cpp").write_text("this is not C++\n")
    with pytest.raises(subprocess.CalledProcessError):
        chip_smoke.build_native()
    assert not so.exists()   # and no stale binary is left to load
