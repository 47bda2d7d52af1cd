"""With the timed path broken underneath, the run comes out not correct.

Each case drives a whole run at a tiny size on the CPU (only the look for a
chip is skipped) with one fault planted, and the sound path for contrast.
Run by hand: ``python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).with_name("fault_run.py")


def drive(*args: str) -> dict:
    out = subprocess.run([sys.executable, str(SCRIPT), *args], capture_output=True,
                         text=True, timeout=600, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("devices", [1, 4])
def test_sound_run_is_correct(devices):
    r = drive("--fault", "none", "--devices", str(devices))
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault,devices", [
    ("unchanged", 1),     # a step that returns its state unchanged
    ("half_batch", 1),    # half of each tick's arrivals left out
    ("altered", 1),       # an answer altered where it is produced
    ("no_exchange", 4),   # the all-gathers between chips left out
])
def test_fault_is_not_correct(fault, devices):
    r = drive("--fault", fault, "--devices", str(devices))
    assert r["correct"] is False, r["checks"]
