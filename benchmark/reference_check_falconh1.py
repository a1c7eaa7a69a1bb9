"""Logits AND recurrent state of the system's own programs against the plain
float32 reference for a configuration with a state-space mixer
(``falcon-h1-34b-d8``), at its published widths.  What
``benchmark/reference_check.py`` does for OLMoE and
``reference_check_glm.py`` for GLM (each imports its own reference by name and
cannot serve this one); run on the chip, outside any timed window.

    python3 benchmark/reference_check_falconh1.py --seed <n> [--readings]

In one process (the one that holds the chip):

1. weights at the configuration's widths from ``--seed`` through the
   program's own ``init_params`` and int8 quantisation, as the server would
   (``server_args``: ``--quantize``, ``--decode-slots``, ``--max-seq-len``);
   no adapter (the configuration serves none);
2. two prompts, of 192 tokens (the traffic mix's median) and of 512 (its
   longest), each followed by ``--decode`` (256) fed tokens;
3. the system, as the engine drives it: each prompt through the jitted bucket
   prefill (flash attention, the chunked scan, padding past the true length)
   and ``insert_prefill`` into EVERY slot in turn (even slots the first
   prompt, odd ones the second), so that the decode steps that follow run
   over all 64 lanes live, through the kernels ``decode_attention`` and
   ``ssm_decode_update``; logits kept at the last prompt position and at
   every decoded position of slots 0 and 1, and the recurrent state those
   slots hold after the last step.  Slots 2 and 3 hold the same sequences
   and have to give the same numbers bit for bit: a row's result does not
   depend on where it lies or on its neighbours;
4. the reference: ``benchmark/reference/falcon_h1.py`` 's full forward over
   prompt + fed tokens on the SAME (dequantised) weights, one layer at a
   time, the recurrence one position after another;
5. per sequence three errors, and exit 1 over the limits:
   ``max |got - ref| / max |ref|`` and ``mean |got - ref| / mean |ref|`` of
   the logits over the compared positions, and ``max |H - H_ref| / max
   |H_ref|`` of the recurrent state over all layers.

Tokens are fed, not sampled (an argmax flips on rounding).

The limits (``TOL``) and why there are three.  The configuration states bf16
activations over int8 weights and a FLOAT32 recurrent state; the reference
computes in float32 on the same weights.  The logits' limits lie between
what the reference reads against itself with bf16 activations (the stated
precision: has to pass) and with float8_e4m3 activations (the nearest below:
has to fail one).  They cannot see the state's precision: with seeded random
weights and the family's multipliers the skip ``D x`` is most of the mixer's
output and the state's reading a few percent of it, so a bf16 state moves
the logits by less than bf16 activations do.  The state's own limit lies
between what the system reads (its float32 state was fed bf16-rounded x, B,
C and dt) and what the reference reads against itself with its state rounded
to bf16 after every position (has to fail).  ``--readings`` takes all three
readings and holds the verdict to that placing.  PERF.md section 6 (PR 43)
gives the readings the limits were set from.
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

# Largest and mean relative error of a sequence's logits, largest relative
# error of its recurrent state (docstring).
TOL = {"logits_max": 0.025, "logits_mean": 0.025, "state": 0.01}
PROMPTS = (192, 512)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="falcon-h1-34b-d8")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode", type=int, default=256)
    ap.add_argument("--readings", action="store_true",
                    help="also read the reference against itself at "
                         "bfloat16 activations (has to pass the logits' "
                         "limits), float8 activations (has to fail one) and "
                         "a bfloat16 state (has to fail the state's limit)")
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
    from benchmark.reference import falcon_h1 as reference
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
    prompts, n_decode = PROMPTS, args.decode
    if args.rehearse_cpu:  # the tiny preset: the script's shape, not its size
        prompts, n_decode = (12, 40), min(args.decode, 8)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"no TPU here ({dev.platform}); --rehearse-cpu rehearses",
              file=sys.stderr)
        return 2
    seed = args.seed % (2 ** 31 - 1)
    print(f"reference_check_falconh1: {served} on {dev.device_kind}, {slots} "
          f"slots x {s_max} {dtype.name} lanes + float32 state, "
          f"int8={quantize}, seed {args.seed}, prompts {prompts}, "
          f"{n_decode} decode steps", flush=True)

    t0 = time.time()
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=dtype, quantize=quantize)
    rng = random.Random(seed)
    seqs = [np.asarray([rng.randrange(cfg.vocab_size)
                        for _ in range(n + n_decode)], np.int32)
            for n in prompts]

    # -- the system's own programs, weights as ARGUMENTS as in the engine --
    prefill = jax.jit(lambda params, toks, pos, n: transformer.prefill(
        cfg, params, toks, pos, lengths=n))
    insert = jax.jit(transformer.insert_prefill, donate_argnums=(0,))
    step = jax.jit(
        lambda params, cache, toks, pos, act: transformer.decode_step(
            cfg, params, cache, toks, pos, active=act),
        donate_argnums=(1,))

    def system():
        """Per sequence the logits at its last prompt position and at every
        decoded one (slots 0 and 1), the state they hold at the end, and
        whether slots 2 and 3 gave the same numbers."""
        cache = transformer.init_decode_cache(cfg, slots, s_max, dtype=dtype)
        got, same = [[] for _ in seqs], True
        for i, (seq, n) in enumerate(zip(seqs, prompts)):
            bucket = next(b for b in buckets if b >= n)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = seq[:n]
            pos = np.zeros((1, bucket), np.int32)
            pos[0, :n] = np.arange(n)
            logits, k, v = prefill(params, jnp.asarray(toks),
                                   jnp.asarray(pos), jnp.asarray([n]))
            for slot in range(i, slots, len(seqs)):
                cache = insert(cache, k, v, slot, n)
            got[i].append(np.asarray(logits[0, n - 1]))
        active = jnp.ones((slots,), bool)
        owner = np.arange(slots) % len(seqs)
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
        states = [np.asarray(cache["ssm"][:, i]) for i in range(len(seqs))]
        return [np.stack(g) for g in got], states, same

    def err(a, ref):
        d = np.abs(a - ref)
        return float(d.max() / np.abs(ref).max()), float(
            d.mean() / np.abs(ref).mean())

    def ref_run(seq, n, **kw):
        """The reference's logits from position n - 1 and its states after
        the last FED token, laid out as the cache's: [L, H, N, P]."""
        states = []
        logits = np.asarray(reference.forward(
            cfg, params, jnp.asarray(seq), logits_from=n - 1, states=states,
            **kw))
        return logits, np.stack([np.asarray(h).transpose(0, 2, 1)
                                 for h in states])

    t1 = time.time()
    got, got_states, same = system()
    print(f"system: {len(seqs)} prefills into {slots} slots, {n_decode} "
          f"decode steps over all of them, {time.time() - t1:.1f} s; slots "
          f"{len(seqs)}.. repeat slots 0..: {same}", flush=True)
    ok, rows = same, []
    for i, (seq, n) in enumerate(zip(seqs, prompts)):
        t1 = time.time()
        # The system fed seq[:-1] and then seq[-1]: its state is the one
        # after the whole of ``seq``, its last logits those after seq[-1].
        ref, ref_state = ref_run(seq, n)
        ref = ref[:len(got[i])]
        e_max, e_mean = err(got[i], ref)
        e_state = err(got_states[i], ref_state)[0]
        row = {"sequence": i, "prompt": n, "err_max": e_max,
               "err_mean": e_mean, "err_state": e_state,
               "err_max_prefill": err(got[i][:1], ref[:1])[0],
               "err_max_decode": err(got[i][1:], ref[1:])[0],
               "err_max_last_32": err(got[i][-32:], ref[-32:])[0],
               "argmax_agree": float(np.mean(
                   np.argmax(got[i], -1) == np.argmax(ref, -1))),
               "reference_s": round(time.time() - t1, 1)}
        passed = (e_max <= TOL["logits_max"] and e_mean <= TOL["logits_mean"]
                  and e_state <= TOL["state"])
        if args.readings:
            for name, kw in (("bf16", {"round_to": jnp.bfloat16}),
                             ("fp8", {"round_to": jnp.float8_e4m3fn}),
                             ("bf16_state", {"state_dtype": jnp.bfloat16})):
                low, low_state = ref_run(seq, n, **kw)
                row[f"{name}_max"], row[f"{name}_mean"] = err(
                    low[:len(ref)], ref)
                row[f"{name}_state"] = err(low_state, ref_state)[0]
            # The limits are placed only if the stated precision passes
            # them and each nearest one below fails one.
            row["placed"] = (
                row["bf16_max"] <= TOL["logits_max"]
                and row["bf16_mean"] <= TOL["logits_mean"]
                and row["bf16_state"] <= TOL["state"]
                and (row["fp8_max"] > TOL["logits_max"]
                     or row["fp8_mean"] > TOL["logits_mean"])
                and row["bf16_state_state"] > TOL["state"])
            passed &= row["placed"]
        ok &= passed
        rows.append(row)
        print(("PASS " if passed else "FAIL ") + json.dumps(row), flush=True)
    print(json.dumps({"ok": ok, **{f"tol_{k}": v for k, v in TOL.items()},
                      "worst_max": max(r["err_max"] for r in rows),
                      "worst_mean": max(r["err_mean"] for r in rows),
                      "worst_state": max(r["err_state"] for r in rows),
                      "rows_independent": same,
                      "device": dev.device_kind, "seed": args.seed,
                      "seconds": round(time.time() - t0, 1)}), flush=True)
    if args.rehearse_cpu:
        return 10
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
