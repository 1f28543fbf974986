"""``chip_smoke.py``'s contract off the chip: with no TPU it must exit
non-zero and print no result, alone in a directory it must fail, and its
CPU rehearsal must run every phase at the smoke preset and still fail
the device check."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            continue
    return False


def test_no_accelerator_fails_without_result():
    res = _run([SCRIPT], REPO, timeout=300)
    assert res.returncode != 0
    assert not _printed_result(res.stdout)
    assert "jax finds no TPU" in res.stderr


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    res = _run([str(tmp_path / "chip_smoke.py")], tmp_path, timeout=300)
    assert res.returncode != 0
    assert not _printed_result(res.stdout)
    assert "no repro package" in res.stderr


def test_cpu_rehearsal_runs_every_phase_then_fails():
    res = _run([SCRIPT, "--rehearse-on-cpu"], REPO, timeout=900)
    assert res.returncode != 0, res.stdout + res.stderr
    assert not _printed_result(res.stdout)
    assert "rehearsal passed every phase" in res.stderr, res.stderr
    for phase in ("served ", "routes: ", "one chip vs oracle: ",
                  "max_logit_err="):
        assert phase in res.stdout, (phase, res.stdout)
