"""Logits of the system's own programs against the plain float32 reference
for a latent-attention configuration (``glm-4.7-flash-d13``), at its published
widths.  What ``benchmark/reference_check.py`` does for OLMoE (that script
imports ``benchmark.reference.olmoe`` by name and cannot serve this one); run
on the chip, outside any timed window.

    python3 benchmark/reference_check_glm.py --config glm-4.7-flash-d13 --seed <n>

In one process (the one that holds the chip):

1. weights at the configuration's widths from ``--seed`` through the
   program's own ``init_params`` and int8 quantisation, as the server would
   (``server_args``: ``--quantize``, ``--decode-slots``, ``--max-seq-len``);
   no adapter (the configuration serves none);
2. ``--sequences`` prompts with lengths from the cell's traffic mix: the
   first at its shortest length (a prefill bucket), the second at its longest
   (over 1,024: the chunk stream), the others drawn; each followed by
   ``--decode`` fed tokens;
3. the system, as the engine drives it: a prompt up to the largest bucket
   through the jitted bucket prefill (expanded attention, the flash kernel)
   and ``insert_prefill``; a longer one through the jitted chunk program
   (``prefill_with_cache``) 1,024 tokens at a time into its lane; then the fed
   tokens through the jitted decode step over all lanes together (absorbed
   attention, the kernel ``mla_decode_attention`` over the latent cache, the
   empty slots inactive); logits kept at the last prompt position and at
   every decoded position;
4. the reference: ``benchmark/reference/glm4_moe_lite.py`` 's full forward
   over prompt + fed tokens on the SAME (dequantised) weights, one layer and
   one expert at a time;
5. per sequence the largest and the mean error; exit 1 over the limits.

Errors are relative to the reference's own scale over the compared
positions: ``max |got - ref| / max |ref|`` and ``mean |got - ref| / mean
|ref|``.  Tokens are fed, not sampled (an argmax flips on rounding).

Two passes, each with its own limits, and why.  The configuration states
bf16 activations over int8 weights; the reference computes in float32 on the
same weights.  With seeded random weights this model's choice of experts is
not stable under that rounding: the top-4 of 64 sigmoid scores + bias sit
~0.03 apart at the fourth place, a bf16 residual stream is ~1% off by the
later layers, and the gates are renormalised and scaled by 1.8, so ONE flipped
choice swaps ~0.4 of a token's expert mix (in OLMoE, whose gates are the full
softmax's, a flip moves ~0.02).  The reference against ITSELF with bf16
activations then reads 0.45-0.86 of the largest logit (my chip run, PR 35,
seed 3500000011), no nearer than the system.  So:

- pass ``pinned``: the selection bias is replaced by one that pins four
  seeded experts a layer (+100 on them; the bias picks and never weighs, so
  the gates are still the sigmoid scores of the chosen, renormalised and
  scaled).  No choice can flip, and every matmul, norm, rope, the latent cache,
  both attention forms, the chunk stream, the gates and the shared expert
  are held to tight limits (``TOL["pinned"]``): bf16 has to pass them, float8
  to fail them.
- pass ``drawn``: the weights as the server draws them.  The limit that
  can be placed is on the MEAN error (``TOL["drawn"]``): the system and the
  bf16 reading sit near each other, unrelated logits read ~1.4 and the float8
  reading ~1.2; the largest error only has a cap that float8 need not miss.
  What this pass adds is that the data-dependent choice follows the rule at
  published widths; that the rule is exact is ``tests/test_mla.py`` in float32.

``--readings`` adds, per sequence, the reference against itself with
activations rounded to bfloat16 (the stated precision; has to pass) and to
float8_e4m3 (the nearest below; has to fail one limit), and holds the verdict
to that placing.  PERF.md section 6 (PR 35) gives the readings the limits were
set from.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# (largest, mean) relative error a sequence may show, by pass (docstring).
TOL = {"pinned": (0.07, 0.06), "drawn": (1.2, 0.5)}
PIN = 100.0  # added to the pinned experts' selection bias


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="glm-4.7-flash-d13")
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--routing", choices=("both", *TOL), default="both",
                    help="which pass: the selection pinned to four seeded "
                         "experts a layer, as drawn, or both")
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
    from benchmark.reference import glm4_moe_lite as reference
    from benchmark.reference_check import arg_after, sample_lengths
    from benchmark.run import DEFAULT_BUCKETS, rehearsal_traffic
    from benchmark.server_wrapper import register
    from llm_instance_gateway_tpu.models import mixtral, transformer

    man = manifest.load_manifest()
    config = manifest.load_config(args.config)
    section = manifest.section(config, args.rehearse_cpu)
    traffic_name = args.traffic or next(
        w["traffic"] for w in man["workloads"] if w["config"] == args.config)
    traffic = manifest.load_traffic(traffic_name)
    served = register(config, args.rehearse_cpu)
    sargs = section["server_args"]
    cfg = dataclasses.replace(mixtral.CONFIGS[served], max_lora_slots=0)
    slots = int(arg_after(sargs, "--decode-slots", "8"))
    s_max = int(arg_after(sargs, "--max-seq-len", "1024"))
    quantize = arg_after(sargs, "--quantize", "none") == "int8"
    dtype = jnp.dtype(arg_after(sargs, "--dtype", "bfloat16"))
    buckets = [b for b in DEFAULT_BUCKETS if b <= s_max]
    if args.rehearse_cpu:  # the tiny preset: the mix's shape, not its size
        traffic, buckets = rehearsal_traffic(traffic), [16, 32, 64]
    chunk = buckets[-1]
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"no TPU here ({dev.platform}); --rehearse-cpu rehearses",
              file=sys.stderr)
        return 2
    seed = args.seed % (2 ** 31 - 1)
    print(f"reference_check_glm: {served} on {dev.device_kind}, {slots} x "
          f"{s_max} {dtype.name} latent lanes, int8={quantize}, seed "
          f"{args.seed}, lengths from {traffic_name}, buckets to {chunk}",
          flush=True)

    t0 = time.time()
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=dtype, quantize=quantize)
    rng = random.Random(seed)
    n_seq = min(args.sequences, slots)
    lengths = [min(n, s_max - args.decode - 1)
               for n in sample_lengths(traffic, n_seq, rng)]
    seqs = [np.asarray([rng.randrange(cfg.vocab_size)
                        for _ in range(n + args.decode)], np.int32)
            for n in lengths]

    # -- the system's own programs, weights as ARGUMENTS as in the engine --
    prefill = jax.jit(lambda params, toks, pos, n: transformer.prefill(
        cfg, params, toks, pos, lengths=n))
    insert = jax.jit(transformer.insert_prefill, donate_argnums=(0,))
    stream = jax.jit(
        lambda params, cache, toks, pos, slot, end, last:
        transformer.prefill_with_cache(cfg, params, cache, toks, pos, slot,
                                       end, last),
        donate_argnums=(1,))
    step = jax.jit(
        lambda params, cache, toks, pos, act: transformer.decode_step(
            cfg, params, cache, toks, pos, active=act),
        donate_argnums=(1,))

    def pinned(params):
        """``params`` with a selection bias that pins four seeded experts in
        each sparse layer."""
        layers = dict(params["layers"])
        bias = np.array(layers["router_bias"], np.float32)
        rs = np.random.RandomState(seed % (2 ** 32 - 1))
        for row in bias:
            row[rs.choice(cfg.n_experts, cfg.n_experts_per_token,
                          replace=False)] += PIN
        layers["router_bias"] = jnp.asarray(bias, layers["router_bias"].dtype)
        return dict(params, layers=layers)

    def system_logits(params):
        """Per sequence the logits at its last prompt position and at every
        decoded one, and the path its prompt took."""
        cache = transformer.init_decode_cache(cfg, slots, s_max, dtype=dtype)
        got, path = [[] for _ in seqs], []
        for i, (seq, n) in enumerate(zip(seqs, lengths)):
            if n <= chunk:
                bucket = next(b for b in buckets if b >= n)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :n] = seq[:n]
                pos = np.zeros((1, bucket), np.int32)
                pos[0, :n] = np.arange(n)
                logits, k, v = prefill(params, jnp.asarray(toks),
                                       jnp.asarray(pos), jnp.asarray([n]))
                cache = insert(cache, k, v, i, n)
                got[i].append(np.asarray(logits[0, n - 1]))
                path.append(f"bucket {bucket}")
                continue
            for start in range(0, n, chunk):  # the engine's chunk stream
                piece = seq[start:min(n, start + chunk)]
                toks = np.zeros((chunk,), np.int32)
                toks[:len(piece)] = piece
                last, cache = stream(
                    params, cache, jnp.asarray(toks),
                    jnp.asarray(start + np.arange(chunk, dtype=np.int32)),
                    jnp.int32(i), jnp.int32(start + len(piece)),
                    jnp.int32(len(piece) - 1))
            got[i].append(np.asarray(last))
            path.append(f"{-(-n // chunk)} chunks of {chunk}")
        active = np.zeros((slots,), bool)
        active[:n_seq] = True
        for j in range(args.decode):
            toks = np.zeros((slots,), np.int32)
            pos = np.zeros((slots,), np.int32)
            for i, (seq, n) in enumerate(zip(seqs, lengths)):
                toks[i], pos[i] = seq[n + j], n + j
            logits, cache = step(params, cache, jnp.asarray(toks),
                                 jnp.asarray(pos), jnp.asarray(active))
            logits = np.asarray(logits)
            for i in range(n_seq):
                got[i].append(logits[i])
        return [np.stack(g) for g in got], path

    def err(a, ref):
        d = np.abs(a - ref)
        return float(d.max() / np.abs(ref).max()), float(
            d.mean() / np.abs(ref).mean())

    def one_pass(label, params) -> bool:
        tol_max, tol_mean = TOL[label]
        t1 = time.time()
        got, path = system_logits(params)
        print(f"{label}: system, {n_seq} prefills ({', '.join(path)}), "
              f"{args.decode} decode steps, {time.time() - t1:.1f} s",
              flush=True)
        ok, rows = True, []
        for i, (seq, n) in enumerate(zip(seqs, lengths)):
            t1 = time.time()
            ref = np.asarray(reference.forward(
                cfg, params, jnp.asarray(seq), logits_from=n - 1))
            e_max, e_mean = err(got[i], ref)
            row = {"routing": label, "sequence": i, "prompt": n,
                   "path": path[i], "err_max": e_max, "err_mean": e_mean,
                   "err_max_prefill": err(got[i][:1], ref[:1])[0],
                   "err_max_decode": err(got[i][1:], ref[1:])[0],
                   "argmax_agree": float(np.mean(
                       np.argmax(got[i], -1) == np.argmax(ref, -1))),
                   "reference_s": round(time.time() - t1, 1)}
            passed = e_max <= tol_max and e_mean <= tol_mean
            if args.readings:
                for name, dt in (("bf16", jnp.bfloat16),
                                 ("fp8", jnp.float8_e4m3fn)):
                    low = np.asarray(reference.forward(
                        cfg, params, jnp.asarray(seq), round_to=dt,
                        logits_from=n - 1))
                    row[f"{name}_max"], row[f"{name}_mean"] = err(low, ref)
                # The limits are placed only if the stated precision passes
                # them and the nearest one below fails one.
                row["placed"] = (
                    row["bf16_max"] <= tol_max and row["bf16_mean"] <= tol_mean
                    and (row["fp8_max"] > tol_max
                         or row["fp8_mean"] > tol_mean))
                passed &= row["placed"]
            ok &= passed
            rows.append(row)
            print(("PASS " if passed else "FAIL ") + json.dumps(row),
                  flush=True)
        print(json.dumps({"routing": label, "ok": ok, "tol_max": tol_max,
                          "tol_mean": tol_mean,
                          "worst_max": max(r["err_max"] for r in rows),
                          "worst_mean": max(r["err_mean"] for r in rows),
                          "device": dev.device_kind, "seed": args.seed,
                          "seconds": round(time.time() - t0, 1)}), flush=True)
        return ok

    ok = True
    for label in TOL if args.routing == "both" else (args.routing,):
        ok &= one_pass(label, pinned(params) if label == "pinned" else params)
    print(json.dumps({"ok": ok, "seed": args.seed,
                      "seconds": round(time.time() - t0, 1)}), flush=True)
    if args.rehearse_cpu:
        return 10
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
