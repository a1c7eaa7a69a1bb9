"""Two-process serving check: the Engine decodes over a mesh spanning hosts.

The reference's unit of scheduling is a pod; the SURVEY maps that to a
slice-backed replica where one model server spans multiple HOSTS (a v5e-16
slice is 4 hosts x 4 chips — SURVEY §2.5).  `tests/test_multihost.py` proved
two OS processes can TRAIN over one mesh; serving is harder because the
engine is a host-driven loop: every process must issue the identical
sequence of jitted calls (multi-controller SPMD), and every host-read value
must be fully replicated.

This check runs the REAL `server.engine.Engine` in two coordinated
processes over a `tensor=8` mesh (4 virtual CPU devices per process — the
tensor axis, and with it every per-layer attention/MLP psum, crosses the
process boundary exactly where DCN sits on a multi-host slice):

- determinism: all requests are submitted BEFORE `start()`, slots >=
  requests, equal budgets, greedy sampling, a fixed engine seed — so both
  loops admit, prefill, and decode in lockstep with no timing-dependent
  branch;
- replication: with no `data` axis the batch dimension is unsharded, so
  sampled tokens (and the prefill's first token) come back fully
  replicated and `np.asarray` on them is legal in every process.

Used by `tests/test_multihost.py` (serving parity assertion) and
`__graft_entry__.dryrun_multichip` (the driver's multi-chip certification,
which reports the multi-host serve result in its tail line).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SERVE_WORKER = r"""
import os, sys
import jax
sys.path.insert(0, os.environ["GRAFT_REPO"])

from llm_instance_gateway_tpu.parallel.mesh import (
    MeshConfig, initialize_distributed, make_mesh,
)

initialize_distributed()
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()

import dataclasses
import jax.numpy as jnp

from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import LLAMA3_8B
from llm_instance_gateway_tpu.server.engine import (
    Engine, EngineConfig, Request, SamplingParams,
)

cfg = dataclasses.replace(
    LLAMA3_8B, name="multihost-serve", vocab_size=256, d_model=64,
    n_layers=2, n_heads=8, n_kv_heads=8, d_ff=128, head_dim=8,
    max_seq_len=64, use_flash_attention=False, use_pallas_decode=False,
)
mesh = make_mesh(MeshConfig(tensor=8))
params = transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
engine = Engine(
    cfg, params,
    EngineConfig(decode_slots=2, max_seq_len=64, prefill_buckets=(16,)),
    eos_id=None, dtype=jnp.float32, seed=0, mesh=mesh,
)
reqs = [
    Request(prompt_tokens=[5, 6, 7], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.0)),
    Request(prompt_tokens=[9, 10, 11, 12], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.0)),
]
# Submit BEFORE start: both processes' loops see the same full queue on
# their first admission pass — no timing-dependent divergence.
for r in reqs:
    engine.submit(r)
engine.start()
try:
    for r in reqs:
        assert r.done.wait(240), "request hung"
        assert r.error is None, r.error
finally:
    engine.stop()
toks = ";".join(",".join(map(str, r.output_tokens)) for r in reqs)
print(f"MULTIHOST SERVE OK pid={jax.process_index()} tokens={toks}",
      flush=True)
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_two_process(worker_src: str, n_local: int = 4,
                    timeout_s: float = 300.0) -> list[str]:
    """Launch ``worker_src`` in 2 coordinated processes (``n_local``
    virtual CPU devices each) under the env contract the GKE manifests set
    (TPU_GATEWAY_COORDINATOR/_PROCESS_ID/_NUM_PROCESSES).  Returns both
    processes' combined stdout/stderr; raises RuntimeError on a non-zero
    exit.  The single launch scaffold for every two-process check (train
    and serve) — the coordination contract lives here only."""
    import tempfile
    import time

    port = free_port()
    procs = []
    files = []
    timed_out = False
    try:
        for pid in (0, 1):
            env = dict(os.environ)
            # The workers are virtual-CPU processes by construction; named
            # in the environment so that, launched from a process that
            # holds a chip, they never go near it.
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
            env["GRAFT_REPO"] = REPO
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={n_local}")
            env["TPU_GATEWAY_COORDINATOR"] = f"127.0.0.1:{port}"
            env["TPU_GATEWAY_PROCESS_ID"] = str(pid)
            env["TPU_GATEWAY_NUM_PROCESSES"] = "2"
            # Temp FILES, not pipes: a worker blocked writing a full 64KiB
            # pipe while its peer waits in a cross-process collective would
            # deadlock the pair (nobody drains until communicate()).
            f = tempfile.TemporaryFile(mode="w+", encoding="utf-8",
                                       errors="replace")
            files.append(f)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", worker_src], env=env,
                stdout=f, stderr=subprocess.STDOUT, text=True,
            ))
        deadline = time.monotonic() + timeout_s
        for p in procs:
            remaining = deadline - time.monotonic()
            try:
                p.wait(timeout=max(0.1, remaining))
            except subprocess.TimeoutExpired:
                timed_out = True
                break
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        outs = []
        for f in files:
            f.seek(0)
            outs.append(f.read())
            f.close()
    if timed_out:
        raise RuntimeError(
            "two-process worker timed out:\n"
            + "\n---\n".join(o[-2000:] for o in outs))
    for p, out in zip(procs, outs):
        if p.returncode != 0:
            raise RuntimeError(f"two-process worker failed:\n{out[-3000:]}")
    return outs


def run_two_process_serve(timeout_s: float = 300.0) -> list[str]:
    """Serving check: returns the per-process token strings (len 2) — the
    caller asserts they match.  Raises RuntimeError on any failure."""
    outs = run_two_process(SERVE_WORKER, timeout_s=timeout_s)
    tokens = []
    for out in outs:
        ok = [l for l in out.splitlines() if l.startswith("MULTIHOST SERVE OK")]
        if not ok:
            raise RuntimeError(f"no OK line:\n{out[-3000:]}")
        tokens.append(ok[0].rsplit("tokens=", 1)[1])
    return tokens
