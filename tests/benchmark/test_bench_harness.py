"""benchmark/run.py on the CPU: a REHEARSAL of the harness at the tiny
presets, which proves its control flow and prints no result — plus the ways
a run must fail: no accelerator, a child dying under it, and a directory
that holds the benchmark without the program."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.e2e

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")
REHEARSAL_EXIT = 10


def _result_lines(stdout: str) -> list[dict]:
    out = []
    for ln in stdout.splitlines():
        if ln.startswith("{"):
            try:
                doc = json.loads(ln)
            except ValueError:
                continue
            if "correct" in doc and "metrics" in doc:
                out.append(doc)
    return out


def _run(*argv, timeout=600):
    return subprocess.run([sys.executable, RUN, *argv], capture_output=True,
                          text=True, timeout=timeout, cwd=REPO)


def test_traced_rehearsal_holds_every_check_and_prints_no_result():
    r = _run("--workload", "qwen7b_chat", "--seed", "2147483999",
             "--seconds", "6", "--trace", "1", "--trace-seconds", "1.5",
             "--rehearse-cpu")
    tail = r.stdout[-4000:] + r.stderr[-3000:]
    assert r.returncode == REHEARSAL_EXIT, tail
    assert "[FAIL]" not in r.stdout and r.stdout.count("[PASS]") == 9, tail
    assert _result_lines(r.stdout) == []
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL held: ")
    read = json.loads(last[len("REHEARSAL held: "):])["metrics_read"]
    # spans, counters, the polled ring, the client and the trace all read
    for name in ("gateway.pick_p50_us", "server.queue_wait_p50_ms",
                 "engine.host_gap_pct", "engine.batch_rows_mean",
                 "model.decode_step_ms", "client.late_p90_ms",
                 "device.idle_pct", "setup.load_s",
                 # appended by PR 41: the request path and the overlap
                 "gateway.ttft_p50_ms", "server.first_write_p50_ms",
                 "server.write_lag_ms", "engine.decode_overlap_pct"):
        assert name in read, tail
    assert "phases replica-0" in r.stdout
    assert "new traced programs in the window [0]" in r.stdout


def test_closed_loop_rehearsal_of_the_sparse_model():
    r = _run("--workload", "mixtral_d6_batch", "--seed", "7",
             "--seconds", "5", "--trace", "0", "--rehearse-cpu")
    tail = r.stdout[-4000:] + r.stderr[-3000:]
    assert r.returncode == REHEARSAL_EXIT, tail
    assert _result_lines(r.stdout) == []
    assert '"output_tok_s"' in r.stdout.strip().splitlines()[-1]


def test_no_accelerator_fails_with_no_result_line():
    """What a run sees in a sandbox without a chip."""
    r = _run("--workload", "qwen7b_chat", "--seed", "1", "--seconds", "5",
             "--trace", "0")
    assert r.returncode not in (0, REHEARSAL_EXIT)
    assert _result_lines(r.stdout) == []
    assert "replica-0 exited with code" in r.stderr


def test_killing_a_replica_fails_the_run():
    p = subprocess.Popen(
        [sys.executable, RUN, "--workload", "qwen7b_chat", "--seed", "1",
         "--seconds", "20", "--trace", "0", "--rehearse-cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=REPO)
    try:
        deadline = time.monotonic() + 120
        server = None
        while server is None and time.monotonic() < deadline:
            out = subprocess.run(
                ["pgrep", "-P", str(p.pid), "-f", "benchmark.server_wrapper"],
                capture_output=True, text=True).stdout.split()
            server = int(out[0]) if out else None
            time.sleep(0.2)
        assert server is not None, "the run never started its replica"
        time.sleep(3.0)
        os.kill(server, 9)
        stdout, stderr = p.communicate(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode not in (0, REHEARSAL_EXIT)
    assert _result_lines(stdout) == []
    assert "replica-0 exited with code" in stderr
    import socket

    for port in (18960, 18961):  # every child stopped: nobody listens
        with socket.socket() as sock:
            assert sock.connect_ex(("127.0.0.1", port)) != 0


def test_the_benchmark_without_the_program_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: non-zero exit, no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "qwen7b_chat",
         "--seed", "1", "--seconds", "5", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert r.returncode != 0
    assert _result_lines(r.stdout) == []
    assert "the program is not here" in r.stderr
