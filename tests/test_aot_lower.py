"""AOT lowering of the flagship hybrid program without hardware.

VERDICT r3 #2: the real Llama-3-8B v5p-64 config must lower (with GSPMD
shardings) and fit the HBM budget before first chip contact.  The full run
is ``tools/aot_lower_8b.py`` (it writes ``AOT_8B.md``); the test drives the
same code path at reduced depth so it stays in the quick tier's reach.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "aot_lower_8b.py")


@pytest.mark.slow
def test_aot_lower_8b_reduced_depth():
    proc = subprocess.run(
        [sys.executable, _TOOL, "--layers", "2", "--seq", "256",
         "--global-batch", "64"],
        capture_output=True, text=True, timeout=540,
        env={k: v for k, v in os.environ.items()
             if k != "XLA_FLAGS"})  # tool sets its own 64-device flag
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("AOT8B_OK")]
    assert line, proc.stdout[-2000:]
    stats = json.loads(line[0][len("AOT8B_OK "):])
    assert stats["sharding_annotations"] > 0
    assert stats["est_mem_gb_per_device"] <= stats["hbm_gb"]
    # hidden/vocab/heads are the REAL 8B shapes even at reduced depth
    assert stats["plan"]["dp"] * stats["plan"]["mp"] * stats["plan"]["pp"] \
        * stats["plan"]["sharding"] == 64

