"""Logits of the system's own programs against the plain float32 reference,
at a configuration's published widths.  Run on the chip, outside any timed
window; the benchmark's cells check determinism and lengths, this checks the
mathematics.

    python3 benchmark/reference_check.py --config olmoe-1b-7b --seed <n>

What it does, in one process (the one that holds the chip):

1. weights at the configuration's widths from ``--seed`` through the
   program's own ``init_params`` and int8 quantisation, as the server would
   (``server_args``: ``--quantize``, ``--decode-slots``, ``--max-seq-len``,
   ``--max-loras``), and one seeded LoRA adapter (rank and targets as
   ``benchmark/adapter_writer.py`` writes them) in slot 0;
2. a seeded sample of ``--sequences`` prompts with lengths from the cell's
   traffic mix, the first at its shortest and the second at its longest
   length, the third on the adapter, each followed by ``--decode`` fed
   tokens;
3. the system: each prompt through the jitted bucket prefill, inserted into
   its lane of the configuration's slots x positions cache, then the fed
   tokens through the jitted decode step over all lanes together (the empty
   slots inactive), logits kept at the last prompt position and at every
   decoded position;
4. the reference: ``benchmark/reference/olmoe.py`` 's full forward over
   prompt + fed tokens on the SAME (dequantised) weights, the same positions;
5. per sequence the largest and the mean error (below); exit 1 over the
   tolerance.

Errors are relative to the reference's own scale over the compared
positions: ``max |got - ref| / max |ref|`` and ``mean |got - ref| / mean
|ref|``.  With random weights an argmax flips on rounding, so tokens are
fed, not sampled.

The tolerance, and why.  The configuration states bf16 activations over
int8 weights; the reference computes in float32 on the same weights.  What
is left is bf16 rounding of every activation (2^-9 relative each, through 16
layers and a top-8 choice that a near-tie can flip).  ``--readings`` prints
two more numbers per sequence to place the limit: the reference against
itself with activations rounded to bfloat16 before every matmul (what the
stated precision costs) and to float8_e4m3 (the nearest precision below,
which has to come out as NOT correct).  With ``--readings`` the verdict also
holds the limits to that placing: bfloat16 inside both, float8 outside one.
On the v5e at OLMoE-1B-7B's widths (my chip runs, PR 27; four seeds, sixteen
sequences) the system read 0.013-0.022 largest and 0.011-0.017 mean, the
reference at bfloat16 0.011-0.018 and 0.007-0.010 (the system also keeps its
residual stream and every output in bf16), at float8 0.075-0.085 and
0.066-0.078.  The limits sit between the system's largest and float8's
smallest, nearer the system's: a float8 activation path fails both.  What
they cannot catch on the chip is a fault smaller than bf16's own noise, such
as a bf16 router softmax (0.1% at tiny size): the float32 CPU tests catch
that one.  At the rehearsal's tiny size the readings place nothing: one
flipped expert choice of a 64-wide model moves a logit by several percent.
``tests/test_reference_parity.py`` shows a bf16 router softmax, a
renormalised gate and a missing QK-norm each failing at tiny size.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Largest and mean relative error a sequence may show (see the docstring).
TOL_MAX = 0.04
TOL_MEAN = 0.03


def arg_after(argv: list[str], flag: str, default: str) -> str:
    return argv[argv.index(flag) + 1] if flag in argv else default


def sample_lengths(traffic: dict, n: int, rng: random.Random) -> list[int]:
    spec = traffic["prompt_tokens"]
    lo, hi = int(spec["min"]), int(spec["max"])
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    drawn = [min(hi, max(lo, int(round(rng.lognormvariate(mu, sigma)))))
             for _ in range(max(0, n - 2))]
    return [lo, hi][:n] + drawn


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default=None,
                    help="traffic mix to draw prompt lengths from (default: "
                         "the mix of the first cell of this configuration)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--readings", action="store_true",
                    help="also read the reference against itself at "
                         "bfloat16 (has to pass the limits) and float8 "
                         "activations (has to fail them)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the tiny preset on the CPU: a rehearsal of this "
                         "script, exits 10, never a result")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.reference import olmoe as reference
    from benchmark.run import DEFAULT_BUCKETS
    from benchmark.server_wrapper import register
    from llm_instance_gateway_tpu.models import (
        gemma, llama, lora as lora_lib, mixtral, qwen, transformer)

    man = manifest.load_manifest()
    config = manifest.load_config(args.config)
    section = manifest.section(config, args.rehearse_cpu)
    traffic_name = args.traffic or next(
        w["traffic"] for w in man["workloads"] if w["config"] == args.config)
    traffic = manifest.load_traffic(traffic_name)
    served = register(config, args.rehearse_cpu)
    sargs = section["server_args"]
    cfg = {**llama.CONFIGS, **gemma.CONFIGS, **mixtral.CONFIGS,
           **qwen.CONFIGS}[served]
    cfg = dataclasses.replace(
        cfg, max_lora_slots=int(arg_after(sargs, "--max-loras", "4")))
    slots = int(arg_after(sargs, "--decode-slots", "8"))
    s_max = int(arg_after(sargs, "--max-seq-len", "1024"))
    quantize = arg_after(sargs, "--quantize", "none") == "int8"
    dtype = jnp.dtype(arg_after(sargs, "--dtype", "bfloat16"))
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"no TPU here ({dev.platform}); --rehearse-cpu rehearses",
              file=sys.stderr)
        return 2
    # The engine's arithmetic wraps a seed the same way (int32 keys).
    seed = args.seed % (2 ** 31 - 1)
    print(f"reference_check: {served} on {dev.device_kind}, {slots} x "
          f"{s_max} {dtype.name} lanes, int8={quantize}, seed {args.seed}, "
          f"lengths from {traffic_name}", flush=True)

    t0 = time.time()
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=dtype, quantize=quantize)
    rank = min(int(traffic.get("adapters", {}).get("rank", 16)),
               cfg.max_lora_rank)
    rs = np.random.RandomState(seed % (2 ** 32 - 1))
    dims = lora_lib.target_dims(cfg)
    adapter = {t: {"a": rs.randn(cfg.n_layers, dims[t][0], rank) * 0.05,
                   "b": rs.randn(cfg.n_layers, rank, dims[t][1]) * 0.05}
               for t in ("q", "v")}
    bufs = lora_lib.load_adapter(lora_lib.init_lora_buffers(cfg, dtype), cfg,
                                 0, adapter, alpha=2.0 * rank, rank=rank)

    rng = random.Random(seed)
    n_seq = min(args.sequences, slots)
    lengths = [min(n, s_max - args.decode - 1)
               for n in sample_lengths(traffic, n_seq, rng)]
    seqs = [np.asarray([rng.randrange(cfg.vocab_size)
                        for _ in range(n + args.decode)], np.int32)
            for n in lengths]
    on_adapter = [i == 2 for i in range(n_seq)]
    slot_ids = np.full((slots,), -1, np.int32)
    slot_ids[:n_seq] = [0 if a else -1 for a in on_adapter]

    # -- the system's own programs ---------------------------------------
    # Weights and adapters are ARGUMENTS of the programs, as in the engine:
    # closed over, 7 GB of constants would be lowered into each program.
    prefill = jax.jit(lambda params, bufs, toks, pos, n, sid:
                      transformer.prefill(cfg, params, toks, pos,
                                          lora_bufs=bufs, slot_ids=sid,
                                          lengths=n))
    insert = jax.jit(transformer.insert_prefill, donate_argnums=(0,))
    step = jax.jit(
        lambda params, bufs, cache, toks, pos, sid, act:
        transformer.decode_step(cfg, params, cache, toks, pos,
                                lora_bufs=bufs, slot_ids=sid, active=act),
        donate_argnums=(2,))
    cache = transformer.init_decode_cache(cfg, slots, s_max, dtype=dtype)
    got = [[] for _ in seqs]
    for i, (seq, n) in enumerate(zip(seqs, lengths)):
        bucket = next(b for b in DEFAULT_BUCKETS + (s_max,) if b >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seq[:n]
        pos = np.zeros((1, bucket), np.int32)
        pos[0, :n] = np.arange(n)
        logits, k, v = prefill(params, bufs, jnp.asarray(toks),
                               jnp.asarray(pos), jnp.asarray([n]),
                               jnp.asarray(slot_ids[i:i + 1]))
        cache = insert(cache, k, v, i, n)
        got[i].append(np.asarray(logits[0, n - 1]))
    active = np.zeros((slots,), bool)
    active[:n_seq] = True
    for j in range(args.decode):
        toks = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        for i, (seq, n) in enumerate(zip(seqs, lengths)):
            toks[i], pos[i] = seq[n + j], n + j
        logits, cache = step(params, bufs, cache, jnp.asarray(toks),
                             jnp.asarray(pos), jnp.asarray(slot_ids),
                             jnp.asarray(active))
        logits = np.asarray(logits)
        for i in range(n_seq):
            got[i].append(logits[i])
    del cache
    print(f"system: {n_seq} prefills, {args.decode} decode steps, "
          f"{time.time() - t0:.1f} s", flush=True)

    # -- the reference, and the verdict -----------------------------------
    def err(a, ref):
        d = np.abs(a - ref)
        return float(d.max() / np.abs(ref).max()), float(
            d.mean() / np.abs(ref).mean())

    ok, rows = True, []
    for i, (seq, n) in enumerate(zip(seqs, lengths)):
        lora = (bufs, 0) if on_adapter[i] else None
        t1 = time.time()
        ref = np.asarray(reference.forward(cfg, params, jnp.asarray(seq),
                                           lora))[n - 1:]
        e_max, e_mean = err(np.stack(got[i]), ref)
        row = {"sequence": i, "prompt": n, "adapter": on_adapter[i],
               "err_max": e_max, "err_mean": e_mean,
               "argmax_agree": float(np.mean(
                   np.argmax(np.stack(got[i]), -1) == np.argmax(ref, -1))),
               "reference_s": round(time.time() - t1, 1)}
        if args.readings:
            for name, dt in (("bf16", jnp.bfloat16),
                             ("fp8", jnp.float8_e4m3fn)):
                low = np.asarray(reference.forward(
                    cfg, params, jnp.asarray(seq), lora, round_to=dt))[n - 1:]
                row[f"{name}_max"], row[f"{name}_mean"] = err(low, ref)
        passed = e_max <= TOL_MAX and e_mean <= TOL_MEAN
        if args.readings:
            # The limits are placed only if the stated precision passes
            # them and the nearest one below fails them.
            row["placed"] = (
                row["bf16_max"] <= TOL_MAX and row["bf16_mean"] <= TOL_MEAN
                and (row["fp8_max"] > TOL_MAX or row["fp8_mean"] > TOL_MEAN))
            passed &= row["placed"]
        ok &= passed
        rows.append(row)
        print(("PASS " if passed else "FAIL ") + json.dumps(row), flush=True)
    print(json.dumps({"ok": ok, "tol_max": TOL_MAX, "tol_mean": TOL_MEAN,
                      "worst_max": max(r["err_max"] for r in rows),
                      "worst_mean": max(r["err_mean"] for r in rows),
                      "device": dev.device_kind, "seed": args.seed,
                      "seconds": round(time.time() - t0, 1)}), flush=True)
    if args.rehearse_cpu:
        return 10
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
