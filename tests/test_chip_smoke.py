"""chip_smoke.py on the CPU: a REHEARSAL of the script (tiny preset), which
proves its control flow and is never a pass — plus the two ways it must
fail: no accelerator, and a child dying under it."""

import json
import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.e2e

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402  (stdlib-only module: never imports jax)


def _result_lines(stdout: str) -> list[dict]:
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{") and '"ok"' in ln]


def test_smoke_parent_stays_off_jax():
    """A parent that touched JAX would hold the chip; the smoke's process
    must not even import it."""
    subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; assert 'jax' not in sys.modules"],
        cwd=REPO, check=True, timeout=60)


def test_rehearsal_holds_every_check_and_is_never_a_pass():
    r = subprocess.run([sys.executable, SMOKE, "--rehearse-cpu"],
                       capture_output=True, text=True, timeout=600)
    tail = r.stdout[-4000:] + r.stderr[-4000:]
    assert r.returncode == chip_smoke.REHEARSAL_EXIT != 0, tail
    assert "REHEARSAL" in r.stdout and "[FAIL]" not in r.stdout, tail
    assert r.stdout.count("[PASS]") >= 15, tail
    (last,) = _result_lines(r.stdout)
    assert last["ok"] is False and last["rehearsal"] is True
    assert "gateway scheduler:" in r.stdout
    assert "attention dispatch: op=decode impl=xla" in r.stdout


def test_no_accelerator_fails_with_no_result_line():
    """What the driver's first run sees in a sandbox without a chip."""
    r = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode not in (0, chip_smoke.REHEARSAL_EXIT)
    assert _result_lines(r.stdout) == []
    assert "Unable to initialize backend 'tpu'" in r.stderr


def test_killing_the_server_child_fails_the_smoke():
    p = subprocess.Popen([sys.executable, SMOKE, "--rehearse-cpu"],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
    try:
        deadline = time.monotonic() + 120
        server = None
        while server is None and time.monotonic() < deadline:
            out = subprocess.run(
                ["pgrep", "-P", str(p.pid), "-f", "server.api_http"],
                capture_output=True, text=True).stdout.split()
            server = int(out[0]) if out else None
            time.sleep(0.2)
        assert server is not None, "the smoke never started its server"
        os.kill(server, 9)
        stdout, stderr = p.communicate(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode not in (0, chip_smoke.REHEARSAL_EXIT)
    assert _result_lines(stdout) == []
    assert "server-0 exited with code" in stderr
