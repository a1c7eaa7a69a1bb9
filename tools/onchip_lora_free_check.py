"""On-chip check of the decode programs of an engine with adapters against
each other.

An engine that holds adapter buffers runs a decode block on one of several
traces of ``jit_decode_block``: without the LoRA delta when no row of the
block names an adapter, else with the delta of the targets that some
resident adapter carries (``Engine._block_lora_buffers``).  A base row's
delta is an exact 0, and so is every delta of a target that no resident
adapter carries, so the traces say the same thing of a row up to what
separately compiled programs differ by in bf16.  This tool reads
that difference where the benchmark's probes cannot (each runs alone and
sees one variant): at Qwen2.5-7B's published widths, int8 weights from the
program's key, 32 slots x 2,048 positions, four adapter slots with one
non-zero rank-16 adapter on q and v resident (the benchmark's cell), it
decodes ONE base prompt

1. alone (every block without the delta),
2. alone again (the same program: what one program differs from itself by),
3. beside an adapter row that outlives it (every block with the delta of
   q and v),

and then ONE adapter prompt

4. alone (the two-target program),
5. alone again,
6. alone once more after an adapter that carries all seven targets was
   loaded beside it (the seven-target program, the only one before the
   targets were told apart; the load compiles it on this thread, and what
   that cost is printed),

greedy over the whole vocabulary, and compares, token by token up to the
first token that differs, the sampled token's log-probability and the top
five: the largest absolute difference, and where the tokens part if they do
(1 against 2, 1 against 3, 4 against 5, 4 against 6).  It also prints what
each first call of a program cost the engine thread (trace, lowering,
compile or cache read), for the set-up cost of a second decode program, and
asserts by ``tpu:lora_target_reads_total`` which program each run met.

    python tools/onchip_lora_free_check.py [--tokens 64] [--rehearse-cpu]

Exit 0 when runs 1 and 2, and 4 and 5, agree bit for bit, run 3 stays
within --tol of run 1 and run 6 of run 4; ``--rehearse-cpu`` runs the script
on ``qwen-tiny`` on the CPU and exits 10: a rehearsal is never a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REHEARSAL_EXIT = 10


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--prompt", type=int, default=96)
    ap.add_argument("--tol", type=float, default=0.125,
                    help="largest |difference| of a log-probability allowed "
                         "between the two programs (bf16 logits of size 2-4 "
                         "step by 1/64-1/32)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    from llm_instance_gateway_tpu import runtime

    runtime.resolve_platform("cpu" if args.rehearse_cpu else None)
    if not args.rehearse_cpu:
        runtime.require_accelerator("tools/onchip_lora_free_check.py")
    runtime.configure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import monitoring

    from llm_instance_gateway_tpu.models import qwen, transformer
    from llm_instance_gateway_tpu.models.lora import TARGETS, target_dims
    from llm_instance_gateway_tpu.server.engine import (
        Engine, EngineConfig, Request, SamplingParams)
    from llm_instance_gateway_tpu.server.lora_manager import LoRAManager

    rehearse = args.rehearse_cpu
    cfg = dataclasses.replace(
        qwen.CONFIGS["qwen-tiny" if rehearse else "qwen2.5-7b"],
        max_lora_slots=4)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    slots, s_max = (4, 512) if rehearse else (32, 2048)
    print(f"device: {jax.devices()[0].device_kind} x {jax.device_count()}; "
          f"model {cfg.name}: d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}", flush=True)

    events: list[tuple] = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.append(
            (name.rsplit("/", 1)[-1], kw.get("fun_name", ""), secs)))

    params = jax.device_put(transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=dtype, quantize=not rehearse))
    lora = LoRAManager(cfg, dtype=dtype)
    rank = min(16, cfg.max_lora_rank)
    dims, rng = target_dims(cfg), np.random.RandomState(7)

    def adapter(targets) -> dict:
        return {
            t: {"a": rng.randn(cfg.n_layers, dims[t][0], rank) * 0.05,
                "b": rng.randn(cfg.n_layers, rank, dims[t][1]) * 0.05}
            for t in targets}

    lora.load("tuned", weights=adapter(("q", "v")), alpha=2.0 * rank,
              rank=rank)
    engine = Engine(cfg, params,
                    EngineConfig(decode_slots=slots, max_seq_len=s_max),
                    lora_manager=lora, eos_id=None, dtype=dtype)

    # The first call of each decode program, on the engine thread's clock.
    first_calls: list[tuple] = []
    jit_decode = engine._jit_decode

    delta_steps = [0]  # steps of the blocks handed adapter buffers

    def timed(params, lora_bufs, *rest, **kw):
        if lora_bufs is not None:
            delta_steps[0] += kw["n_steps"]
        n = jit_decode._cache_size()
        t0 = time.perf_counter()
        out = jit_decode(params, lora_bufs, *rest, **kw)
        if jit_decode._cache_size() > n:
            first_calls.append((
                "without the delta" if lora_bufs is None else
                f"with the delta of {len(lora_bufs) // 2} targets",
                time.perf_counter() - t0))
        return out

    timed.lower = jit_decode.lower  # the engine prepares a trace ahead
    engine._jit_decode = timed

    prng = np.random.RandomState(52)
    prompt = [int(t) for t in prng.randint(32, 127, size=args.prompt)]

    def base() -> Request:
        return Request(prompt_tokens=prompt, max_new_tokens=args.tokens,
                       sampling=SamplingParams(temperature=0.0), logprobs=5,
                       streaming=True)

    def tuned() -> Request:
        return Request(prompt_tokens=prompt[:64],
                       max_new_tokens=args.tokens, adapter="tuned",
                       sampling=SamplingParams(temperature=0.0), logprobs=5,
                       streaming=True)

    def free_steps() -> int:
        return engine.profiler.hist_state()["lora_free_steps"]

    def targets_a_delta_step(run_it) -> tuple:
        """``run_it()`` and the targets its delta steps were handed, a
        step."""
        reads = engine.profiler.hist_state()["lora_target_reads"]
        steps = delta_steps[0]
        out = run_it()
        reads = engine.profiler.hist_state()["lora_target_reads"] - reads
        return out, reads / max(delta_steps[0] - steps, 1)

    def run(req: Request) -> Request:
        engine.submit(req)
        assert req.done.wait(1500) and req.error is None, req.error
        return req

    engine.start()
    try:
        at = free_steps()
        one = run(base())
        assert free_steps() - at >= args.tokens - 1, "run 1 met the delta"
        two = run(base())
        companion = engine.submit(Request(
            prompt_tokens=prompt[:32], max_new_tokens=args.tokens * 3,
            sampling=SamplingParams(temperature=0.0), adapter="tuned",
            streaming=True))
        t_end = time.monotonic() + 1500
        while len(companion.output_tokens) < 2:
            assert time.monotonic() < t_end and not companion.done.is_set()
            time.sleep(0.002)
        at = free_steps()
        three = run(base())
        beside = not companion.done.is_set() and free_steps() == at
        assert companion.done.wait(1500) and companion.error is None
        four, read_4 = targets_a_delta_step(lambda: run(tuned()))
        five, _ = targets_a_delta_step(lambda: run(tuned()))
        t0 = time.perf_counter()
        lora.load("wide", weights=adapter(TARGETS), alpha=2.0 * rank,
                  rank=rank)
        widening_load_s = time.perf_counter() - t0
        first_after = len(first_calls)
        six, read_6 = targets_a_delta_step(lambda: run(tuned()))
        # The engine thread compiled nothing for the wider set: the first
        # call of a trace compiled ahead costs it milliseconds, a compile
        # or a read of the compile cache seconds.
        compiled_on_the_loop = [
            c for c in first_calls[first_after:] if c[1] > 0.5]
        t0 = time.perf_counter()
        lora.load("narrow", weights=adapter(("q",)), alpha=2.0 * rank,
                  rank=rank)
        load_inside_the_set_s = time.perf_counter() - t0
    finally:
        engine.stop()

    def compare(a: Request, b: Request) -> dict:
        same = 0
        while (same < len(a.output_tokens)
               and a.output_tokens[same] == b.output_tokens[same]):
            same += 1
        # Up to and including the first token that differs: its context is
        # still shared, so its distribution is still comparable.
        upto = min(same + 1, len(a.output_tokens))
        worst = worst_top = 0.0
        for i in range(upto):
            ta, tb = a.output_top_logprobs[i], b.output_top_logprobs[i]
            for tok in set(ta) & set(tb):
                worst_top = max(worst_top, abs(ta[tok] - tb[tok]))
            if i < same:
                worst = max(worst, abs(a.output_logprobs[i]
                                       - b.output_logprobs[i]))
        out = {"tokens": len(a.output_tokens), "same_tokens": same,
               "max_abs_diff_sampled_logprob": worst,
               "max_abs_diff_top5_logprob": worst_top}
        if same < len(a.output_tokens):
            i = same
            out["parted_at"] = {
                "index": i, "a": a.output_tokens[i], "b": b.output_tokens[i],
                "a_top": a.output_top_logprobs[i],
                "b_top": b.output_top_logprobs[i]}
        return out

    again, two_programs = compare(one, two), compare(one, three)
    adapter_again, two_and_seven = compare(four, five), compare(four, six)
    report = {
        "device": jax.devices()[0].device_kind,
        "model": cfg.name, "slots": slots, "positions": s_max,
        "prompt_tokens": len(prompt),
        "one_program_against_itself": again,
        "without_against_with_the_delta": two_programs,
        "run_3_decoded_beside_the_adapter_row": beside,
        "adapter_row_two_targets_against_itself": adapter_again,
        "adapter_row_two_targets_against_seven": two_and_seven,
        "targets_a_delta_step_run_4": read_4,
        "targets_a_delta_step_run_6": read_6,
        "load_that_widens_the_targets_s": round(widening_load_s, 3),
        "load_inside_the_targets_s": round(load_inside_the_set_s, 3),
        "decode_programs_the_loop_compiled_after_the_widening_load":
            compiled_on_the_loop,
        "first_call_s": [(what, round(s, 3)) for what, s in first_calls],
        "logprob_scale": {
            "sampled_mean": float(np.mean(one.output_logprobs)),
            "top5_gap_mean": float(np.mean([
                sorted(t.values())[-1] - sorted(t.values())[-2]
                for t in one.output_top_logprobs]))},
    }
    print(json.dumps(report, indent=1, default=str), flush=True)
    # A program's cache events carry no name: they follow its lowering.
    print("compile events of the decode programs (event, function, s):")
    ours = False
    for name, fun, secs in events:
        ours = "decode_block" in fun if fun else ours
        if ours:
            print(f"  {name:40s} {fun:24s} {secs:8.3f}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/lora_free_check.json", "w") as f:
        json.dump(report, f, indent=1, default=str)

    ok = (again["same_tokens"] == again["tokens"]
          and again["max_abs_diff_sampled_logprob"] == 0.0
          and again["max_abs_diff_top5_logprob"] == 0.0
          and beside
          and two_programs["max_abs_diff_sampled_logprob"] <= args.tol
          and two_programs["max_abs_diff_top5_logprob"] <= args.tol
          and adapter_again["same_tokens"] == adapter_again["tokens"]
          and adapter_again["max_abs_diff_sampled_logprob"] == 0.0
          and adapter_again["max_abs_diff_top5_logprob"] == 0.0
          and (read_4, read_6) == (2.0, 7.0)
          and not compiled_on_the_loop
          and two_and_seven["max_abs_diff_sampled_logprob"] <= args.tol
          and two_and_seven["max_abs_diff_top5_logprob"] <= args.tol)
    print("PASS" if ok else "FAIL", flush=True)
    if rehearse:
        return REHEARSAL_EXIT
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
