"""Dry-run smoke: one real 512-placeholder-device lowering in a
subprocess (the in-process test session is pinned to 1 CPU device)."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_dryrun_subprocess_decode():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen1.5-0.5b", "--shape", "decode_32k",
         "--mesh", "pod", "--out", "-"],
        capture_output=True, text=True, timeout=1200,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/root"),
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.splitlines()[-1])
    assert rec["status"] == "ok"
    assert rec["chips"] == 256
    assert rec["dominant"] in ("compute", "memory", "collective")
    assert rec["flops_dev"] > 0 and rec["coll_bytes_dev"] >= 0


def test_skip_list_documented():
    from repro.launch.dryrun import SKIPS
    assert ("whisper-small", "long_500k") in SKIPS
    assert len(SKIPS) == 1          # 39/40 combos run