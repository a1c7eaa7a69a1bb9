"""The benchmark's thin wrapper round the model server — the process that
holds the chip.

    python -m benchmark.server_wrapper <config.json> <rehearse 0|1> \
        <control dir> -- <api_http arguments>

It does three things and then calls ``server.api_http.main`` unchanged:

1. registers the configuration: the file's ``base_preset`` from the program's
   registry with ``reduced`` applied by ``dataclasses.replace``, under the
   file's served name — no program file is edited for a configuration;
2. prints the phases of its own start (import, device) for the launcher's
   phase clock;
3. takes the device trace.  Only the process that holds the chip can trace
   it, so on SIGUSR1 a side thread runs ``jax.profiler`` for
   ``BENCH_TRACE_SECONDS`` and on SIGUSR2 (after the window) reduces the
   trace to ``<control dir>/trace_summary.json`` with
   ``benchmark.trace_reduce``.  The parent never imports JAX.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import signal
import sys
import threading
import time

T0 = time.time()


def say(phase: str, **kv) -> None:
    """A phase line on stdout, which the launcher reads from the log."""
    # One write, newline included: two threads say things at the same time.
    sys.stdout.write("BENCH_PHASE " + json.dumps(
        {"phase": phase, "t": round(time.time(), 4), **kv}) + "\n")
    sys.stdout.flush()


def register(config: dict, rehearse: bool) -> str:
    from benchmark import manifest
    from llm_instance_gateway_tpu.models import gemma, llama, mixtral, qwen

    section = manifest.section(config, rehearse)
    for mod in (llama, gemma, mixtral, qwen):
        if section["base_preset"] in mod.CONFIGS:
            base = mod.CONFIGS[section["base_preset"]]
            served = section["served_model"]
            if served != section["base_preset"] or section.get("reduced"):
                mod.CONFIGS[served] = dataclasses.replace(
                    base, name=served, **section.get("reduced", {}))
            return served
    raise SystemExit(f"base preset {section['base_preset']!r} is not in the "
                     "program's registry")


def _preimport() -> None:
    try:
        import orbax.checkpoint  # noqa: F401
    except Exception:  # noqa: BLE001 — the server imports it again itself
        pass
    say("orbax_imported")


class Tracer(threading.Thread):
    """Takes one device trace on a signal from the parent, reduces it on a
    second one.  Runs beside the server, never on the engine's thread."""

    def __init__(self, control_dir: str, seconds: float):
        super().__init__(daemon=True, name="bench-tracer")
        self.dir, self.seconds = control_dir, seconds
        self.start_evt, self.reduce_evt = threading.Event(), threading.Event()

    def run(self) -> None:
        self.start_evt.wait()
        import jax

        trace_dir = os.path.join(self.dir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # device ops and XLA host events only
        opts.host_tracer_level = 2
        t0 = time.time()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        time.sleep(self.seconds)
        t1 = time.time()
        jax.profiler.stop_trace()
        say("trace_taken", seconds=round(t1 - t0, 3))
        self.reduce_evt.wait()
        from benchmark import trace_reduce

        out = os.path.join(self.dir, "trace_summary.json")
        try:
            paths = glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            summary = trace_reduce.summarise_xplane(sorted(paths)[-1])
            summary["host_window"] = [t0, t1]
        except Exception as e:  # noqa: BLE001 — the parent reads the reason
            summary = {"error": f"{type(e).__name__}: {e}"}
        with open(out + ".tmp", "w") as f:
            json.dump(summary, f)
        os.replace(out + ".tmp", out)
        say("trace_reduced")


def main() -> None:
    sep = sys.argv.index("--")
    config_path, rehearse, control_dir = sys.argv[1:sep]
    server_argv = sys.argv[sep + 1:]
    with open(config_path) as f:
        config = json.load(f)
    served = register(config, rehearse == "1")
    say("import", since_spawn_s=round(time.time() - T0, 3))

    # The first adapter load imports orbax (13 s on six cores, chip run of
    # PR 23).  Imported here beside the device coming up, which waits on the
    # chip and not on the cores, it costs set-up nothing.
    threading.Thread(target=_preimport, daemon=True,
                     name="bench-preimport").start()

    tracer = Tracer(control_dir,
                    float(os.environ.get("BENCH_TRACE_SECONDS", "4")))
    tracer.start()
    signal.signal(signal.SIGUSR1, lambda *_: tracer.start_evt.set())
    signal.signal(signal.SIGUSR2, lambda *_: tracer.reduce_evt.set())

    # Timestamps on the program's own log lines (its basicConfig then finds
    # the root logger configured and leaves it): the phase clock reads them.
    import logging

    logging.basicConfig(
        level=logging.INFO,
        format="%(created).3f %(levelname)s:%(name)s:%(message)s")
    from llm_instance_gateway_tpu import runtime

    # api_http.main initialises the backend once more through the same call;
    # made here first so that the phase clock sees the device come up.
    platform = server_argv[server_argv.index("--platform") + 1]
    info = runtime.resolve_platform(platform)
    say("device", platform=info.platform, kind=info.device_kind,
        count=info.count)

    from llm_instance_gateway_tpu.server import api_http

    api_http.main(["--model", served] + server_argv)


if __name__ == "__main__":
    main()
