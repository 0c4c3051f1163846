"""Launcher integration tests: one real dry-run cell (subprocess, 512
forced devices, lower+compile+roofline extraction), the training driver
end to end with checkpoint restart, and the serving driver.  All
subprocesses share the session-scoped compiled-artifact cache
(tests/conftest.py), so repeat full-tier runs skip the XLA compiles."""
import json
import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cmd(args, env, timeout=900):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=ROOT)


pytestmark = pytest.mark.slow      # subprocess lower+compile integration


def test_dryrun_single_cell(subprocess_env):
    """xlstm decode_32k: the fastest cell — full lower+compile on the
    256-chip production mesh with roofline extraction."""
    r = run_cmd(["-m", "repro.launch.dryrun", "--arch", "xlstm-350m",
                 "--shape", "decode_32k"], subprocess_env())
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["status"] == "ok"
    assert rec["mesh"] == "16x16"
    assert rec["flops_per_device"] > 0
    assert rec["bytes_per_device"] > 0
    assert rec["bottleneck"] in ("compute", "memory", "collective")
    assert rec["t_compute_s"] >= 0 and rec["t_memory_s"] > 0


def test_train_driver_with_crash_recovery(subprocess_env):
    with tempfile.TemporaryDirectory() as d:
        r = run_cmd(["-m", "repro.launch.train", "--arch", "xlstm-350m",
                     "--smoke", "--steps", "12", "--batch", "2",
                     "--seq", "32", "--ckpt-dir", d, "--ckpt-every", "4",
                     "--inject-failure-at", "6", "--log-every", "4"],
                    subprocess_env())
        assert r.returncode == 0, r.stdout + r.stderr[-2000:]
        assert '"restarts": 1' in r.stdout
        # checkpoints exist
        assert any(x.startswith("step_") for x in os.listdir(d))


def test_serve_driver(subprocess_env):
    r = run_cmd(["-m", "repro.launch.serve", "--arch", "zamba2-2.7b",
                 "--smoke", "--batch", "2", "--prompt-len", "8",
                 "--gen", "4"], subprocess_env())
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "serve ok" in r.stdout
