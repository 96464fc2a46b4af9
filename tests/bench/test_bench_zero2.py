"""The four-chip cell's check through a whole ZeRO-2 run on four CPU
devices: sound, it is correct; with the exchange between chips left out
(each rank keeps its own gradient), it is not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

WORKER = Path(__file__).resolve().parent / "_zero2_worker.py"


def _run(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(WORKER), mode], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("exchange", False)])
def test_zero2_check(mode, correct):
    r = _run(mode)
    assert r["device"]["count"] == 4
    assert r["correct"] is correct, r["checks"]
