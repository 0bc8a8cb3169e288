"""Chaos-harness end-to-end acceptance (ISSUE 2): a training run that
suffers an injected NaN streak (guard skip → rollback), a SIGTERM
preemption, and a corrupted newest checkpoint still reaches the target
step count on restart, with a bitwise-matching loss curve on the clean
steps vs an UNINTERRUPTED run under the same chaos plan — and the
profiler JSON reports nonzero resilience/* counters for every injected
fault class.

A separate case drives the watchdog: an artificial step hang makes the
monitor dump state and abort with the watchdog exit code; the restarted
worker (hang cleared — transient by construction) completes.
"""
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "resilience_worker.py")
TOTAL = 10

# slow: multi-process, ~90s — excluded from the tier-1 time budget;
# the chaos-smoke CI job (-m chaos) and manual acceptance runs cover it
pytestmark = [pytest.mark.chaos, pytest.mark.slow]


def _spawn(ckpt, log, profile, extra_env=None, timeout=600):
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    for k in ("CHAOS_NAN_CURSORS", "CHAOS_FLAKY", "CHAOS_PREEMPT_STEP",
              "CHAOS_HANG", "WATCHDOG_TIMEOUT_S", "WATCHDOG_ABORT",
              "WATCHDOG_DUMP_FILE"):
        env.pop(k, None)
    env.update(extra_env or {})
    p = subprocess.Popen(
        [sys.executable, WORKER, str(ckpt), str(log), str(profile),
         str(TOTAL)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    out, _ = p.communicate(timeout=timeout)
    return p.returncode, out


def _read_losses(log):
    out = {}
    for line in open(log):
        s, l = line.strip().split(",")
        out[int(s)] = float(l)           # later lifetimes overwrite
    return out


def _union_counters(profile):
    import json

    tot = {}
    for line in open(profile):
        rec = json.loads(line)
        for k, v in rec["counters"].items():
            tot[k] = tot.get(k, 0.0) + (v or 0.0)
    return tot


def test_nan_preempt_corrupt_restart_bitwise_curve(tmp_path):
    from paddle_tpu.resilience import chaos

    nan_env = {"CHAOS_NAN_CURSORS": "3,4,5", "CHAOS_FLAKY": "6:2"}

    # 1. uninterrupted reference run under the SAME chaos plan
    rc, out = _spawn(tmp_path / "ref_ck", tmp_path / "ref.log",
                     tmp_path / "ref.jsonl", nan_env)
    assert rc == 0, out[-3000:]
    ref = _read_losses(tmp_path / "ref.log")
    assert sorted(ref) == list(range(TOTAL))

    # 2. same plan + deterministic self-preemption after step 7
    ck, log, prof = tmp_path / "ck", tmp_path / "run.log", \
        tmp_path / "run.jsonl"
    rc, out = _spawn(ck, log, prof,
                     dict(nan_env, CHAOS_PREEMPT_STEP="7"))
    assert rc == 75, f"expected resumable preempt exit, got {rc}: " \
        + out[-3000:]
    assert len(_read_losses(log)) < TOTAL

    # 3. corrupt the NEWEST committed checkpoint (silent bit flip —
    #    only the CRC verify can see it), then restart
    chaos.flip_shard_byte(str(ck), offset=100)
    rc, out = _spawn(ck, log, prof, nan_env)
    assert rc == 0, out[-3000:]

    # target step count reached; clean steps bitwise-match the
    # uninterrupted run (NaN steps must be NaN in both)
    got = _read_losses(log)
    assert sorted(got) == list(range(TOTAL))
    for s in range(TOTAL):
        if math.isnan(ref[s]):
            assert math.isnan(got[s]), f"step {s}: expected NaN"
        else:
            assert got[s] == ref[s], \
                f"step {s} diverged after restart: {got[s]} != {ref[s]}"

    # every injected fault class moved its counter somewhere across the
    # faulted run's lifetimes
    tot = _union_counters(prof)
    assert tot.get("resilience/steps_skipped", 0) > 0      # NaN grads
    assert tot.get("resilience/rollbacks", 0) > 0          # K-streak
    assert tot.get("resilience/preemptions", 0) > 0        # SIGTERM
    assert tot.get("resilience/restore_fallbacks", 0) > 0  # corruption
    assert tot.get("resilience/data_retries", 0) > 0       # flaky loader


def test_watchdog_aborts_hung_step_and_restart_completes(tmp_path):
    ck, log, prof = tmp_path / "ck", tmp_path / "run.log", \
        tmp_path / "run.jsonl"
    dump = tmp_path / "watchdog.txt"
    rc, out = _spawn(ck, log, prof, {
        "CHAOS_HANG": "4:30.0",
        "WATCHDOG_TIMEOUT_S": "3",
        "WATCHDOG_ABORT": "1",
        "WATCHDOG_DUMP_FILE": str(dump)})
    assert rc == 74, f"expected watchdog abort exit, got {rc}: " \
        + out[-3000:]
    assert dump.exists()
    text = dump.read_text()
    assert "hung-step dump" in text and "thread" in text

    # transient hang: the restarted worker (no hang) finishes the job
    rc, out = _spawn(ck, log, prof, {})
    assert rc == 0, out[-3000:]
    assert sorted(_read_losses(log)) == list(range(TOTAL))
