"""chip_smoke.py off the chip: it must refuse before it builds anything."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_tpu_fails_before_building_a_model(tmp_path):
    """Run from a directory that holds chip_smoke.py and nothing else of the
    repo, on the CPU: exit code non-zero, last line ``"ok": false`` — and no
    ImportError, so nothing of the repo (let alone a model) was asked for
    before the device check turned the run away."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode not in (0, 1), out.stderr[-2000:]  # 1 is a phase that failed
    assert "Traceback" not in out.stderr and "paddlenlp_tpu" not in out.stderr
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
