"""Elastic restart loop (VERDICT r1 item 10, SURVEY §5 "surpass, not
parity"): SIGKILL a training process mid-run, restart, and the loss curve
continues identically.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")
TOTAL = 8


def _spawn(ckpt, log, step_delay=0.0):
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               ELASTIC_STEP_DELAY=str(step_delay))
    return subprocess.Popen(
        [sys.executable, WORKER, str(ckpt), str(log), str(TOTAL)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _read_losses(log):
    out = {}
    if os.path.exists(log):
        for line in open(log):
            s, l = line.strip().split(",")
            out[int(s)] = float(l)     # later lifetimes overwrite
    return out


@pytest.mark.slow
def test_sigkill_resume_identical_curve(tmp_path):
    # 1. uninterrupted reference run
    ref_log = tmp_path / "ref.log"
    p = _spawn(tmp_path / "ref_ckpt", ref_log)
    out, _ = p.communicate(timeout=900)
    assert p.returncode == 0, out[-2000:]
    ref = _read_losses(ref_log)
    assert len(ref) == TOTAL

    # 2. interrupted run: SIGKILL once ~half the steps are logged
    log = tmp_path / "run.log"
    ckpt = tmp_path / "ckpt"
    p = _spawn(ckpt, log, step_delay=0.5)
    deadline = time.time() + 900
    try:
        while time.time() < deadline:
            if p.poll() is not None:
                break
            if len(_read_losses(log)) >= TOTAL // 2:
                p.send_signal(signal.SIGKILL)
                break
            time.sleep(0.05)
        p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode != 0, "worker should have been killed mid-run"
    assert len(_read_losses(log)) < TOTAL

    # 3. restart: resumes from latest COMMITTED step and finishes
    p2 = _spawn(ckpt, log)
    out2, _ = p2.communicate(timeout=900)
    assert p2.returncode == 0, out2[-2000:]
    got = _read_losses(log)
    assert len(got) == TOTAL
    for s in range(TOTAL):
        np.testing.assert_allclose(got[s], ref[s], rtol=1e-6,
                                   err_msg=f"step {s} diverged after resume")
