"""Logits of the system's own programs against the plain float32 reference
for the configuration with two kinds of attention layer and cache lane
(``smallthinker-21b-a3b-d12``), at its published widths.  What
``benchmark/reference_check.py`` does for OLMoE, ``reference_check_glm.py``
for GLM and ``reference_check_falconh1.py`` for Falcon-H1 (each imports its
own reference by name and cannot serve this one); run on the chip, outside
any timed window.

    python3 benchmark/reference_check_smallthinker.py --seed <n> [--readings]

In one process (the one that holds the chip):

1. weights at the configuration's widths from ``--seed`` through the
   program's own ``init_params`` and int8 quantisation, as the server would
   (``server_args``: ``--quantize``, ``--decode-slots``, ``--max-seq-len``);
   no adapter (the configuration serves none);
2. four prompts (``PROMPTS``): 3,968 tokens (just under the window, so the
   decode steps that follow cross from inside the window to past it and wrap
   the ring), 4,096 (the cell's shortest: the ring exactly full), 6,144 (its
   median) and 8,192 (its longest), each followed by ``--decode`` (256) fed
   tokens;
3. the system, as the engine drives it: every prompt through the jitted chunk
   program (``prefill_with_cache``: 1,024 tokens at a time, a window layer's
   chunk against its ring as it stood plus its own keys, the last chunk
   padded) into EVERY slot in turn (slot i holds prompt i mod 4), so that the
   decode steps that follow run over all 32 lanes live, through the kernel
   ``decode_attention`` over the full lanes and ``decode_attention_window``
   over the rings; logits kept at the last prompt position and at every
   decoded position of slots 0-3.  Slots 4-7 hold the same sequences and
   have to give the same numbers bit for bit: a row's result does not depend
   on where it lies or on its neighbours;
4. the reference: ``benchmark/reference/smallthinker.py`` 's full forward
   over prompt + fed tokens on the SAME (dequantised) weights, one layer and
   one expert at a time, the attention 512 queries at a time, masks from
   positions;
5. per sequence the largest and the mean error; exit 1 over the limits.

Errors are relative to the reference's own scale over the compared
positions: ``max |got - ref| / max |ref|`` and ``mean |got - ref| / mean
|ref|``.  Tokens are fed, not sampled (an argmax flips on rounding).

Two passes, each with its own limits, and why (as for GLM, PERF.md section 7
row 23).  The configuration states bf16 activations over int8 weights; the
reference computes in float32 on the same weights.  With seeded random
weights a top-6 choice of 64 is not stable under that rounding, and the gates
are renormalised over the chosen, so ONE flipped choice swaps about a sixth of
a token's expert mix.  So:

- pass ``pinned``: six seeded experts a layer get +100 on their router
  logits, in this process alone (``pin_routing``: the program's router is
  handed its input with a column of ones and its weights with the bias as a
  further row; the reference reads the same bias as a ``router_bias`` leaf).
  The gates are the softmax over the CHOSEN logits, which a shift common to
  all six leaves as it was.  No choice can flip, and every matmul,
  norm, RoPE on the window layers and none on the full ones, both kinds of
  lane, the ring's wrap, the chunk stream, the gates (still the softmax of
  the chosen logits, read from the layer's input) and the experts are held
  to tight limits (``TOL["pinned"]``): bf16 has to pass them, float8 to fail
  them, and so has each of three other functions computed on purpose: every
  layer attending every earlier position (the window left off), RoPE on the
  full layers too, the router reading the normed stream after attention;
- pass ``drawn``: the weights as the server draws them.  A flipped choice
  moves single logits by a quarter of the largest (system 0.26-0.30, my chip
  run, PR 45, seed 4500000101) while the mean error stays at 0.04-0.06, so
  the limits (``TOL["drawn"]``) are wider, and the one that is tight is the
  MEAN's.  What this pass adds is that the data-dependent choice follows the
  rule at published widths; that the rule is exact is
  ``tests/test_window.py`` in float32.

``--readings`` adds, per sequence and pass, the reference against itself with
activations rounded to bfloat16 (the stated precision; has to pass) and to
float8_e4m3 (the nearest below; has to fail one limit) and, on the longest
pinned sequence, the three wrong functions (each has to fail one limit), and
holds the verdict to that placing.  PERF.md section 6 (PR 45) gives the readings
the limits were set from.
"""

from __future__ import annotations

import argparse
import contextlib
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
TOL = {"pinned": (0.05, 0.04), "drawn": (0.6, 0.15)}
PIN = 100.0  # added to the pinned experts' router bias
PROMPTS = (3968, 4096, 6144, 8192)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="smallthinker-21b-a3b-d12")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode", type=int, default=256)
    ap.add_argument("--routing", choices=("both", *TOL), default="both",
                    help="which pass: the choice pinned to six seeded "
                         "experts a layer, as drawn, or both")
    ap.add_argument("--readings", action="store_true",
                    help="also read the reference against itself at "
                         "bfloat16 (has to pass the limits), at float8 "
                         "activations and as each of the three wrong "
                         "functions (each has to fail them)")
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
    from benchmark.reference import smallthinker as reference
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
    chunk = [b for b in DEFAULT_BUCKETS if b <= s_max][-1]
    prompts, n_decode = PROMPTS, args.decode
    if args.rehearse_cpu:  # the tiny preset: the script's shape, not its size
        prompts, n_decode, chunk = (12, 16, 37, 50), min(args.decode, 10), 16
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"no TPU here ({dev.platform}); --rehearse-cpu rehearses",
              file=sys.stderr)
        return 2
    seed = args.seed % (2 ** 31 - 1)
    print(f"reference_check_smallthinker: {served} on {dev.device_kind}, "
          f"{slots} slots x ({s_max} full + {min(cfg.sliding_window, s_max)} "
          f"ring) {dtype.name} positions, int8={quantize}, seed {args.seed}, "
          f"prompts {prompts} in chunks of {chunk}, {n_decode} decode steps",
          flush=True)

    t0 = time.time()
    params = transformer.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=dtype, quantize=quantize)
    rng = random.Random(seed)
    seqs = [np.asarray([rng.randrange(cfg.vocab_size)
                        for _ in range(n + n_decode)], np.int32)
            for n in prompts]

    def programs():
        """The system's own programs, weights as ARGUMENTS as in the engine;
        traced anew for each pass."""
        stream = jax.jit(
            lambda params, cache, toks, pos, slot, end, last:
            transformer.prefill_with_cache(cfg, params, cache, toks, pos,
                                           slot, end, last),
            donate_argnums=(1,))
        step = jax.jit(
            lambda params, cache, toks, pos, act: transformer.decode_step(
                cfg, params, cache, toks, pos, active=act),
            donate_argnums=(1,))
        return stream, step

    # The bias that pins six seeded experts in each layer.
    bias = np.zeros((cfg.n_layers, cfg.n_experts), np.float32)
    rs = np.random.RandomState(seed % (2 ** 32 - 1))
    for row in bias:
        row[rs.choice(cfg.n_experts, cfg.n_experts_per_token,
                      replace=False)] = PIN

    @contextlib.contextmanager
    def pin_routing():
        """While it is open, the program's router adds ``bias`` to its
        logits: ``_moe_route`` reads its input through the router's matrix
        alone, so a column of ones on the input and the layer's bias as one
        more row of the matrix are ``x W_r + bias``; the program itself
        knows nothing of it."""
        route = transformer._moe_route

        def pinned_route(cfg, lp, x, live=None):
            w = lp["router"]
            row = jnp.asarray(bias)[lp["layer"]].astype(w.dtype)
            one = jnp.ones((*x.shape[:-1], 1), x.dtype)
            return route(cfg, {**lp, "router": jnp.concatenate([w, row[None]])},
                         jnp.concatenate([x, one], axis=-1), live)

        transformer._moe_route = pinned_route
        try:
            yield
        finally:
            transformer._moe_route = route

    def system(params):
        """Per sequence the logits at its last prompt position and at every
        decoded one (slots 0..3), and whether slots 4..7 gave the same."""
        stream, step = programs()
        cache = transformer.init_decode_cache(cfg, slots, s_max, dtype=dtype)
        got, same = [[] for _ in seqs], True
        owner = np.arange(slots) % len(seqs)
        for slot, o in enumerate(owner):
            seq, n = seqs[o], prompts[o]
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
        return [np.stack(g) for g in got], same

    def err(a, ref):
        d = np.abs(a - ref)
        return float(d.max() / np.abs(ref).max()), float(
            d.mean() / np.abs(ref).mean())

    def ref_run(params, seq, n, **kw):
        return np.asarray(reference.forward(
            cfg, params, jnp.asarray(seq), logits_from=n - 1, **kw))

    def one_pass(label) -> bool:
        tol_max, tol_mean = TOL[label]
        t1 = time.time()
        with pin_routing() if label == "pinned" else contextlib.nullcontext():
            got, same = system(params)
        print(f"{label}: system, {len(seqs)} prompts streamed into {slots} "
              f"slots, {n_decode} decode steps over all of them, "
              f"{time.time() - t1:.1f} s; slots {len(seqs)}.. repeat slots "
              f"0..: {same}", flush=True)
        ok, rows = same, []
        for i, (seq, n) in enumerate(zip(seqs, prompts)):
            t1 = time.time()
            ref = ref_run(ref_params[label], seq, n)
            e_max, e_mean = err(got[i], ref)
            row = {"routing": label, "sequence": i, "prompt": n,
                   "err_max": e_max, "err_mean": e_mean,
                   "err_max_prefill": err(got[i][:1], ref[:1])[0],
                   "err_max_decode": err(got[i][1:], ref[1:])[0],
                   "err_max_last_32": err(got[i][-32:], ref[-32:])[0],
                   "argmax_agree": float(np.mean(
                       np.argmax(got[i], -1) == np.argmax(ref, -1))),
                   "reference_s": round(time.time() - t1, 1)}
            passed = e_max <= tol_max and e_mean <= tol_mean
            if args.readings:
                lows = [("bf16", {"round_to": jnp.bfloat16}),
                        ("fp8", {"round_to": jnp.float8_e4m3fn})]
                if label == "pinned" and i == len(seqs) - 1:
                    # the longest: far past the window
                    lows += [(w, {"wrong": w}) for w in reference.WRONG]
                for name, kw in lows:
                    low = ref_run(ref_params[label], seq, n, **kw)
                    row[f"{name}_max"], row[f"{name}_mean"] = err(low, ref)
                # The limits are placed only if the stated precision passes
                # them and every other reading fails one.
                row["placed"] = (
                    row["bf16_max"] <= tol_max
                    and row["bf16_mean"] <= tol_mean
                    and all(row[f"{name}_max"] > tol_max
                            or row[f"{name}_mean"] > tol_mean
                            for name, _ in lows[1:]))
                passed &= row["placed"]
            ok &= passed
            rows.append(row)
            print(("PASS " if passed else "FAIL ") + json.dumps(row),
                  flush=True)
        print(json.dumps({"routing": label, "ok": ok, "tol_max": tol_max,
                          "tol_mean": tol_mean,
                          "worst_max": max(r["err_max"] for r in rows),
                          "worst_mean": max(r["err_mean"] for r in rows),
                          "rows_independent": same,
                          "device": dev.device_kind, "seed": args.seed,
                          "seconds": round(time.time() - t0, 1)}), flush=True)
        return ok

    ref_params = {"drawn": params, "pinned": dict(params, layers=dict(
        params["layers"], router_bias=jnp.asarray(bias)))}
    ok = True
    for label in TOL if args.routing == "both" else (args.routing,):
        ok &= one_pass(label)
    print(json.dumps({"ok": ok, "seed": args.seed,
                      "seconds": round(time.time() - t0, 1)}), flush=True)
    if args.rehearse_cpu:
        return 10
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
