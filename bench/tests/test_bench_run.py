"""``bench/run.py`` refuses to run without a TPU, and an unknown chip is
an error."""
import os
import subprocess
import sys

import pytest

from bench.peaks import peaks_for
from bench.spec import REPO


def test_run_exits_nonzero_on_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "qwen2-1.5b.chat-switch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks_for("TPU v9 imaginary")
