"""``chip_smoke.py`` off the chip: without a TPU it refuses before any
work, and the explicit CPU rehearsal drives every phase — including the
more-than-one-device branch — over the virtual 8-device mesh."""

import importlib.util
import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def test_without_a_chip_it_exits_nonzero_before_any_work(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, SMOKE], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "synth" not in proc.stdout  # no phase started


def test_cpu_rehearsal_passes_every_phase(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    # the rehearsal names the interpreter through these two variables;
    # monkeypatch owns them so they are restored afterwards
    monkeypatch.setenv("SNTC_SERVE_KERNELS", "interpret")
    monkeypatch.setenv("SNTC_TREE_HIST", "pallas")
    assert smoke.main(["--rehearse-cpu"]) == 0
    lines = capsys.readouterr().out.splitlines()
    # the result line is last and carries exactly these keys; the detailed
    # summary is the line before it
    device = {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    summary = json.loads(lines[-2])
    assert summary["ok"] and summary["rehearsal"] and summary["claim"] is None
    assert list(summary)[-1] == "claim"
    assert summary["device"] == device
    a, b = summary["phase_a_mlp"], summary["phase_b_rf"]
    assert a["head_device_dispatches"] == a["served_batches"]
    assert b["head_device_dispatches"] == b["served_batches"]
    assert all(n >= 1 for n in b["kernel_dispatches"].values())
    assert set(summary["kernel_twins"]) == {
        "forest_traversal", "pad_assemble", "tree_hist",
    }
    assert summary["multi_device"]["shards_on_devices"] == len(jax.devices())
    assert b["serve_mesh"]["labels_equal_single_device"]
