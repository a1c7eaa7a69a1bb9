"""Logits, delta-rule state and latent rows of the system's own programs
against the plain float32 reference for the configuration with a matrix state
and a share of the experts (``ling-3.0-flash-d12``), at its published widths.
What ``benchmark/reference_check_lfm2.py`` does for LFM2 and
``reference_check_glm.py`` for GLM (each imports its own reference by name and
cannot serve this one); run on the chip, outside any timed window.

    python3 benchmark/reference_check_ling.py --seed <n> [--readings] \\
        [--routing pinned|drawn]

In one process (the one that holds the chip):

1. weights at the configuration's widths from ``--seed`` through the
   program's own ``init_params`` and int8 quantisation, as the server would
   (``server_args``: ``--quantize``, ``--decode-slots``, ``--max-seq-len``);
   no adapter (the configuration serves none);
2. five prompts of the mix's range (``PROMPTS``): 600 tokens (the 1,024
   bucket, so ``insert_prefill`` writes the state at a TRUE length shorter
   than the bucket), 1,024 (the bucket exactly full), 1,026 and 2,049 (the
   chunk stream; they END two and one positions past a chunk's edge, so the
   compared logits read the state the edge handed over) and 4,096 (the mix's
   longest: three edges, a last chunk exactly full), each followed by
   ``--decode`` (128) fed tokens;
3. the system, as the engine drives it: prompts to 1,024 through the jitted
   bucket prefill and ``insert_prefill``, longer ones through the jitted
   chunk program (``prefill_with_cache``, 1,024 tokens at a time, the last
   chunk padded) into EVERY slot in turn (slot i holds prompt i mod 5), so
   that the decode steps that follow run over all 64 rows live, through the
   kernels ``kda_decode_update`` (the state rewritten in place),
   ``mla_decode_attention`` and ``moe_gmm_int8`` over the held experts;
   logits kept at the last prompt position and at every decoded position of
   slots 0-4, the KDA state of those slots after the prompt and after the
   last step, their latent rows after the last step.  Slots 5-9 hold the same
   sequences and have to give the same numbers bit for bit;
4. the reference: ``benchmark/reference/bailing_hybrid.py`` 's full forward
   over prompt + fed tokens on the SAME (dequantised) weights, one layer and
   one expert at a time, the attention 512 queries at a time, the delta rule
   one position after another;
5. per sequence the largest and the mean error of the logits, the largest
   error of the KDA state (over all ten layers) and of the latent rows; exit
   1 over the limits.

Errors are relative to the reference's own scale over the compared numbers:
``max |got - ref| / max |ref|`` and ``mean |got - ref| / mean |ref|``.  Tokens
are fed, not sampled (an argmax flips on rounding).  The limits, each with its
reason:

- ``TOL`` logits, two passes as for GLM and LFM2 (the configuration states
  bf16 activations over int8 weights and a float32 state, the reference
  computes in float32 on the same weights, and a top-8 choice of 256 is not
  stable under that rounding): pass ``pinned`` replaces the selection bias by
  one that pins eight seeded experts a layer, four of them held here and
  four not, in the two held and two other groups (program and reference read
  the same leaf), so no choice can flip and every matmul, the convs, the
  gate, the recurrence through insert, chunk edges and decode steps, the
  latent layer, the gates over held and absent experts alike and the share
  are held to tight limits: bf16 has to pass them, float8 to fail them, and
  so have the wrong functions ``no_bound`` (a gate without the bound),
  ``no_delta`` (the delta term dropped) and ``share_renormalised`` (gates
  normalised over the held experts only).  Read on the chip (PR 60, seeds 3054000811 and 1954000822,
  ten sequences): the system 0.059-0.069 largest, 0.057-0.060 mean; bf16
  0.036-0.040 / 0.033-0.034; float8 0.42-0.60 / 0.42-0.45; ``no_bound``
  1.17-1.25 largest, ``no_delta`` 0.70-0.76, ``share_renormalised``
  0.69-0.74: each logits limit 0.2, three times the system's largest and
  under half of float8's smallest.  Pass ``drawn`` takes the weights as the
  server draws them: a flipped choice moves single logits far while the mean
  stays, so the limits are wider and the tight one is the MEAN's (read, seed
  3054000811: the system 0.30-0.46 largest, 0.23-0.28 mean, 0.23-0.52 state,
  0.39-0.46 rows);
- ``TOL``'s third, the KDA state: a running sum in float32 whose inputs (q, k,
  v, g, beta) carry one bf16 matmul's rounding each: its error is that of the
  layer's input after at most 11 layers, not one that grows with the
  sequence, which the readings place (the system 0.055-0.168 over the ten
  sequences and no larger at 4,096 positions than at 600; bf16 0.043-0.075;
  float8 0.39-0.63; ``no_delta`` 3.5-4.2): the limit 0.27, 1.6 times the
  system's largest and 0.7 of float8's smallest.  The system reads above
  bf16 here because the program rounds the in-projection's OUTPUT to bf16
  before the gate multiplies it by exp(A_log), up to 16, inside a sigmoid,
  where the reading rounds what ENTERS a matmul: the stated precision's own
  step, not a fault.  ``bf16_state`` (the reference with its state rounded
  to bfloat16 after every position) is READ and held to nothing: ISSUE 60
  asked that it fail, and it cannot while bf16 activations pass: it moves
  the logits by 0.021-0.025 and the state by 0.029-0.050 (seed 1954000822,
  the sequences of 1,026 and 2,049 positions), LESS than rounding the
  activations does (0.037-0.040 / 0.043-0.059 there), because under these
  weights most channels forget within tens of positions and a rounding of
  the sum does not build up; a state kept in bf16 would show in a check of
  the update alone against its float32 form to a unit roundoff, as
  ``reference_check_lfm2.py`` holds the conv operator, which this script
  has not (PERF.md section 7 row 38);
- ``TOL``'s fourth, the latent rows: one bf16 matmul and a norm away from the
  layer's input, stored in bf16 (the system 0.052-0.073, bf16 0.030-0.038,
  float8 0.39-0.44: the limit 0.2).

``--readings`` adds, per sequence and pass, the reference against itself with
activations rounded to bfloat16 (the stated precision; has to pass) and to
float8_e4m3 (the nearest below; has to fail one limit) and, on the sequences
that end just past an edge (pinned pass), the wrong functions (each but
``bf16_state`` has to fail one limit), and holds the verdict to that placing.  PERF.md section 6
(PR 60) gives the readings the limits were set from.
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

# (largest, mean) relative error a sequence's logits may show, the largest of
# its KDA state and of its latent rows, by pass.
TOL = {"pinned": (0.2, 0.2, 0.27, 0.2), "drawn": (1.0, 0.45, 1.0, 1.0)}
PIN = 100.0  # added to the pinned experts' selection bias
PROMPTS = (600, 1024, 1026, 2049, 4096)
EDGE = (2, 3)  # the sequences that end just past a chunk's edge


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ling-3.0-flash-d12")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode", type=int, default=128)
    ap.add_argument("--routing", choices=("both", *TOL), default="both",
                    help="which pass: the selection pinned to eight seeded "
                         "experts a layer, as drawn, or both")
    ap.add_argument("--readings", action="store_true",
                    help="also read the reference against itself at "
                         "bfloat16 (has to pass the limits), at float8 "
                         "activations and as each wrong function (each has "
                         "to fail them)")
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
    from benchmark.reference import bailing_hybrid as reference
    from benchmark.reference_check import arg_after
    from benchmark.run import DEFAULT_BUCKETS
    from benchmark.server_wrapper import register
    from llm_instance_gateway_tpu.models import mixtral, transformer

    config = manifest.load_config(args.config)
    section = manifest.section(config, args.rehearse_cpu)
    served = register(config, args.rehearse_cpu)
    sargs = section["server_args"]
    cfg = dataclasses.replace(mixtral.CONFIGS[served], max_lora_slots=0)
    slots = int(arg_after(sargs, "--decode-slots", "8"))
    s_max = int(arg_after(sargs, "--max-seq-len", "1024"))
    quantize = arg_after(sargs, "--quantize", "none") == "int8"
    dtype = jnp.dtype(arg_after(sargs, "--dtype", "bfloat16"))
    buckets = [b for b in DEFAULT_BUCKETS if b <= s_max]
    chunk = buckets[-1]
    prompts, n_decode = PROMPTS, args.decode
    if args.rehearse_cpu:  # the tiny preset: the script's shape, not its size
        prompts, n_decode = (9, 16, 18, 33, 64), min(args.decode, 6)
        buckets, chunk, slots = [16], 16, 10
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"no TPU here ({dev.platform}); --rehearse-cpu rehearses",
              file=sys.stderr)
        return 2
    seed = args.seed % (2 ** 31 - 1)
    print(f"reference_check_ling: {served} on {dev.device_kind}, {slots} "
          f"slots x {s_max} {dtype.name} latent rows in "
          f"{cfg.n_layers_of('full')} layers + a float32 matrix state in "
          f"{cfg.n_layers_of('kda')}, {cfg.experts_held} of {cfg.n_experts} "
          f"experts held, vocabulary {cfg.vocab_size}, int8={quantize}, "
          f"seed {args.seed}, prompts {prompts} (buckets to {buckets[-1]}, "
          f"chunks of {chunk}), {n_decode} decode steps", flush=True)

    t0 = time.time()
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=dtype, quantize=quantize)
    rng = random.Random(seed)
    seqs = [np.asarray([rng.randrange(cfg.vocab_size)
                        for _ in range(n + n_decode)], np.int32)
            for n in prompts]

    def pinned(params):
        """``params`` with a selection bias that pins, in each sparse layer,
        ``k`` seeded experts: half of them among those held here and half
        among the others, spread over ``topk_group`` groups so that the
        group rule keeps them: program and reference read the same leaf."""
        layers = dict(params["layers"])
        bias = np.array(layers["router_bias"], np.float32)
        rs = np.random.RandomState(seed % (2 ** 32 - 1))
        k, size = cfg.n_experts_per_token, cfg.n_experts // cfg.n_group
        held_groups = range(cfg.expert_first // size,
                            (cfg.expert_first + cfg.experts_held) // size)
        other_groups = [g for g in range(cfg.n_group) if g not in held_groups]
        half = cfg.topk_group // 2
        for row in bias:
            groups = (list(rs.choice(list(held_groups), half, replace=False))
                      + list(rs.choice(other_groups, cfg.topk_group - half,
                                       replace=False)))
            for g in groups:
                row[g * size + rs.choice(size, k // cfg.topk_group,
                                         replace=False)] += PIN
        layers["router_bias"] = jnp.asarray(bias, layers["router_bias"].dtype)
        return dict(params, layers=layers)

    # -- the system's own programs, weights as ARGUMENTS as in the engine --
    prefill = jax.jit(lambda params, toks, pos, n: transformer.prefill(
        cfg, params, toks, pos, lengths=n))
    insert = jax.jit(
        lambda cache, k, v, slot, n: transformer.insert_prefill(
            cache, k, v, slot, n, cfg=cfg), donate_argnums=(0,))
    stream = jax.jit(
        lambda params, cache, toks, pos, slot, end, last:
        transformer.prefill_with_cache(cfg, params, cache, toks, pos, slot,
                                       end, last),
        donate_argnums=(1,))
    step = jax.jit(
        lambda params, cache, toks, pos, act: transformer.decode_step(
            cfg, params, cache, toks, pos, active=act),
        donate_argnums=(1,))

    def system(params):
        """Per sequence the logits at its last prompt position and at every
        decoded one (slots 0-4), its KDA state [2 ends, L_kda, H, dk, dv]
        after the prompt and after the last step, its latent rows [L_mla,
        S, width] after the last step, and whether slots 5-9 gave the same
        numbers."""
        cache = transformer.init_decode_cache(cfg, slots, s_max, dtype=dtype)
        got, same = [[] for _ in seqs], True
        owner = np.arange(slots) % len(seqs)
        for slot, o in enumerate(owner):
            seq, n = seqs[o], prompts[o]
            if n <= buckets[-1]:  # the bucketed admission
                bucket = next(b for b in buckets if b >= n)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :n] = seq[:n]
                pos = np.zeros((1, bucket), np.int32)
                pos[0, :n] = np.arange(n)
                logits, k, v = prefill(params, jnp.asarray(toks),
                                       jnp.asarray(pos), jnp.asarray([n]))
                cache = insert(cache, k, v, slot, n)
                last = logits[0, n - 1]
            else:
                for start in range(0, n, chunk):  # the engine's chunk stream
                    piece = seq[start:min(n, start + chunk)]
                    toks = np.zeros((chunk,), np.int32)
                    toks[:len(piece)] = piece
                    last, cache = stream(
                        params, cache, jnp.asarray(toks),
                        jnp.asarray(start + np.arange(chunk, dtype=np.int32)),
                        jnp.int32(slot), jnp.int32(start + len(piece)),
                        jnp.int32(len(piece) - 1))
            if slot < len(seqs):
                got[slot].append(np.asarray(last))
            elif slot < 2 * len(seqs):
                same &= bool(np.array_equal(np.asarray(last), got[o][0]))
        held = [np.asarray(cache["kda"][:, :len(seqs)])]
        active = jnp.ones((slots,), bool)
        for j in range(n_decode):
            toks = np.asarray([seqs[o][prompts[o] + j] for o in owner],
                              np.int32)
            pos = np.asarray([prompts[o] + j for o in owner], np.int32)
            logits, cache = step(params, cache, jnp.asarray(toks),
                                 jnp.asarray(pos), active)
            head = np.asarray(logits[:2 * len(seqs)])
            for i in range(len(seqs)):
                got[i].append(head[i])
                if slots >= 2 * len(seqs):
                    same &= bool(np.array_equal(head[i], head[len(seqs) + i]))
        held.append(np.asarray(cache["kda"][:, :len(seqs)]))
        states = [np.stack([h[:, i] for h in held]) for i in range(len(seqs))]
        rows = [np.asarray(cache["k"][:, i, :prompts[i] + n_decode,
                                      :cfg.latent_width].astype(jnp.float32))
                for i in range(len(seqs))]
        return [np.stack(g) for g in got], states, rows, same

    def err(a, ref):
        d = np.abs(a - ref)
        return float(d.max() / np.abs(ref).max()), float(
            d.mean() / np.abs(ref).mean())

    def ref_run(params, seq, n, **kw):
        states = []
        logits = np.asarray(reference.forward(
            cfg, params, jnp.asarray(seq), logits_from=n - 1, states=states,
            state_ends=(n, len(seq)), **kw))
        # KDA layers hand [ends, H, dk, dv], latent layers [S, width]
        kda = [np.asarray(s) for s in states if s.ndim == 4]
        rows = [np.asarray(s) for s in states if s.ndim == 2]
        # [L_kda, ends, ...] -> [ends, L_kda, ...]
        return logits, np.moveaxis(np.stack(kda), 1, 0), np.stack(rows)

    def one_pass(label, params) -> bool:
        tol_max, tol_mean, tol_state, tol_rows = TOL[label]
        t1 = time.time()
        got, states, rows, same = system(params)
        print(f"{label}: system, {len(seqs)} prompts into {slots} slots "
              f"(bucket prefill and chunk stream), {n_decode} decode steps "
              f"over all of them, {time.time() - t1:.1f} s; slots "
              f"{len(seqs)}.. repeat slots 0..: {same}", flush=True)
        ok, out = same, []
        for i, (seq, n) in enumerate(zip(seqs, prompts)):
            t1 = time.time()
            ref, ref_state, ref_rows = ref_run(params, seq, n)
            e_max, e_mean = err(got[i], ref)
            row = {"routing": label, "sequence": i, "prompt": n,
                   "err_max": e_max, "err_mean": e_mean,
                   "err_max_prefill": err(got[i][:1], ref[:1])[0],
                   "err_max_decode": err(got[i][1:], ref[1:])[0],
                   "state_err_prompt": err(states[i][0], ref_state[0])[0],
                   "state_err_end": err(states[i][1], ref_state[1])[0],
                   "rows_err": err(rows[i], ref_rows)[0],
                   "argmax_agree": float(np.mean(
                       np.argmax(got[i], -1) == np.argmax(ref, -1))),
                   "reference_s": round(time.time() - t1, 1)}
            passed = (e_max <= tol_max and e_mean <= tol_mean
                      and row["state_err_prompt"] <= tol_state
                      and row["state_err_end"] <= tol_state
                      and row["rows_err"] <= tol_rows)
            if args.readings:
                lows = [("bf16", {"round_to": jnp.bfloat16}),
                        ("fp8", {"round_to": jnp.float8_e4m3fn})]
                if label == "pinned" and i in EDGE:
                    lows += [(w, {"wrong": w}) for w in reference.WRONG]
                for name, kw in lows:
                    low, low_state, low_rows = ref_run(params, seq, n, **kw)
                    row[f"{name}_max"], row[f"{name}_mean"] = err(low, ref)
                    row[f"{name}_state"] = max(
                        err(low_state[e], ref_state[e])[0] for e in (0, 1))
                    row[f"{name}_rows"] = err(low_rows, ref_rows)[0]
                # The limits are placed only if the stated precision passes
                # them and every other reading fails one (but ``bf16_state``,
                # which is read and held to nothing: docstring).
                lows = [low for low in lows if low[0] != "bf16_state"]
                row["placed"] = (
                    row["bf16_max"] <= tol_max
                    and row["bf16_mean"] <= tol_mean
                    and row["bf16_state"] <= tol_state
                    and row["bf16_rows"] <= tol_rows
                    and all(row[f"{name}_max"] > tol_max
                            or row[f"{name}_mean"] > tol_mean
                            or row[f"{name}_state"] > tol_state
                            or row[f"{name}_rows"] > tol_rows
                            for name, _ in lows[1:]))
                passed &= row["placed"]
            ok &= passed
            out.append(row)
            print(("PASS " if passed else "FAIL ") + json.dumps(row),
                  flush=True)
        print(json.dumps({"routing": label, "ok": ok, "tol_max": tol_max,
                          "tol_mean": tol_mean, "tol_state": tol_state,
                          "tol_rows": tol_rows,
                          "worst_max": max(r["err_max"] for r in out),
                          "worst_mean": max(r["err_mean"] for r in out),
                          "worst_state": max(
                              max(r["state_err_prompt"], r["state_err_end"])
                              for r in out),
                          "worst_rows": max(r["rows_err"] for r in out),
                          "rows_independent": same,
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
