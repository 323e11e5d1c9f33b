"""chip_smoke.py's contract off the chip: it fails, says what it found,
and prints no result (the run that passes is the builder's and the
driver's, on the chip)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, script=SCRIPT, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc.returncode, proc.stdout


def _has_result(stdout):
    lines = [l for l in stdout.splitlines() if l.strip()]
    try:
        return bool(json.loads(lines[-1]).get("ok"))
    except (ValueError, IndexError, AttributeError):
        return False


def test_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key besides ``ok`` and
    ``device`` {platform, kind, count}: what the legs measured goes on
    the ``detail:`` line, never here."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        line = chip_smoke.result_line(ok, device)
        assert "\n" not in line
        assert json.loads(line) == {"ok": ok, "device": device}
    # and it is the last thing main() prints
    src = open(SCRIPT).read()
    assert src.rindex("print(result_line(") == src.rindex("print(")


def test_no_tpu_fails_and_names_the_platform():
    rc, out = _run([], REPO)
    assert rc != 0
    assert "no TPU" in out and "'cpu'" in out
    assert not _has_result(out)


def test_alone_in_a_directory_fails(tmp_path):
    """Past the device gate (rehearsal lets a CPU through), a directory
    that holds chip_smoke.py and nothing else of the repo still fails."""
    alone = shutil.copy(SCRIPT, tmp_path)
    rc, out = _run(["--rehearsal"], str(tmp_path), script=alone)
    assert rc != 0
    assert "cannot import mxnet_tpu" in out
    assert not _has_result(out)


@pytest.mark.slow
def test_rehearsal_runs_every_leg_and_prints_no_result():
    rc, out = _run(["--rehearsal"], REPO, timeout=900)
    assert rc == 0, out[-3000:]
    assert "REHEARSAL" in out
    for leg in ("train", "flash", "serve"):
        assert f"[{leg}] passed" in out
    assert not _has_result(out)
