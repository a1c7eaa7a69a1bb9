"""The one traffic generator.  A mix is a data file under ``traffic/``; this
module turns (file, seed, seconds) into the requests of one run.

Every seed gets THE SAME set of sizes and arrival gaps, in another order: the
set is drawn from the file's ``base_seed`` as one cycle of (size, gap) pairs,
and ``--seed`` only turns the cycle to another starting point (and picks the
prompts' letters).  So two runs with different seeds offer the same work in
the same neighbourhoods, and a difference between them is the system's, not
the draw's.  What the cycle fills is the file's ``edges``: ``"cut"`` (the
default) fills the ramp and the window, so a turn also chooses which stretch
is offered unmeasured in the ramp and which runs into the quiet drain;
``"periodic"`` fills the window alone, and offers the cycle's own
neighbours unmeasured before it (``ramp_s``) and after it (``tail_s``), so
every seed measures the whole cycle once, under the load of the same cycle
going round.

Arrival arithmetic is a copy of ``gateway/loadgen.py``
``build_arrival_timeline`` (poisson: exponential gaps at the mean rate;
burst: an on/off square wave normalised to the mean rate), so the yardstick
does not move when the program's own generator does.

Stdlib only: the benchmark's parent process never imports JAX.
"""

from __future__ import annotations

import dataclasses
import math
import random

# The byte-level tokenizer gives one token per character plus BOS.  With
# random weights the argmax wanders over the whole vocabulary, of which the
# tokenizer prints 256 ids: a +100 logit_bias on 32 printable bytes (the
# API's maximum; chip_smoke.py's pattern) keeps greedy decoding on tokens
# that come back as one character each, and keeps EOS out — so a request
# runs to exactly its ``max_tokens`` and the lengths are the schedule's.
VISIBLE_BYTES = b"abcdefghijklmnopqrstuvwxyz ,.;-\n"
LOGIT_BIAS = {str(b): 100 for b in VISIBLE_BYTES}
PROMPT_ALPHABET = "abcdefghijklmnopqrstuvwxyz      ,."

BASE_MODEL = None  # ``Request.adapter`` of a request to the base model


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # against the window's start; negative = ramp
    prompt_tokens: int    # as the server counts them (characters + BOS)
    max_tokens: int
    adapter: int | None   # index of the adapter, None = base model
    prompt: str


def _lengths(spec: dict, n: int, rng: random.Random) -> list[int]:
    if spec["dist"] == "fixed":
        return [int(spec["median"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    lo, hi = int(spec["min"]), int(spec["max"])
    return [min(hi, max(lo, int(round(rng.lognormvariate(mu, sigma)))))
            for _ in range(n)]


def _adapters(spec: dict, n: int, rng: random.Random) -> list[int | None]:
    count, share = int(spec.get("count", 0)), float(spec.get("share", 0.0))
    if count <= 0 or share <= 0:
        return [BASE_MODEL] * n
    weights = [1.0 / (i + 1) ** float(spec.get("zipf", 1.0))
               for i in range(count)]
    out: list[int | None] = []
    for _ in range(n):
        if rng.random() < share:
            out.append(rng.choices(range(count), weights)[0])
        else:
            out.append(BASE_MODEL)
    return out


def arrival_gaps(traffic: dict, n: int, rng: random.Random) -> list[float]:
    """``n`` inter-arrival gaps at the file's mean rate (loadgen.py's
    arithmetic: the next gap is exponential at the rate in force at t)."""
    rate = float(traffic["rate_rps"])
    shape = traffic.get("arrival", "poisson")
    burst_factor = float(traffic.get("burst_factor", 8.0))
    duty = float(traffic.get("duty", 0.2))
    period_s = float(traffic.get("period_s", 10.0))
    gaps, t = [], 0.0
    for _ in range(n):
        if shape == "poisson":
            r = rate
        elif shape == "burst":
            base = rate / (duty * burst_factor + (1.0 - duty))
            r = base * (burst_factor if (t % period_s) < duty * period_s
                        else 1.0)
        else:
            raise ValueError(f"unknown arrival shape {shape!r}")
        gap = rng.expovariate(max(r, 1e-6))
        gaps.append(gap)
        t += gap
    return gaps


def _prompt(n_tokens: int, rng: random.Random) -> str:
    # Distinct random text: no two prompts share a prefix worth caching.
    return "".join(rng.choices(PROMPT_ALPHABET, k=max(1, n_tokens - 1)))


def build_requests(traffic: dict, seed: int, seconds: float) -> list[Request]:
    """The run's requests, in the order they are offered.

    Open loop: ``rate x (ramp + seconds)`` requests whose gaps are scaled to
    fill exactly ``[-ramp, seconds)`` (``edges`` ``"periodic"``: ``rate x
    seconds`` that fill ``[0, seconds)``, with the cycle's end before them
    and its start again after them); the window measures those due in
    ``[0, seconds)``.  Closed loop: a pool of ``pool_requests`` that the
    clients take from in order (``due_s`` is 0: a client sends when its last
    completed).
    """
    base = random.Random(int(traffic["base_seed"]))
    open_loop = traffic["loop"] == "open"
    ramp = float(traffic.get("ramp_s", 0.0))
    edges = traffic.get("edges", "cut")
    if edges not in ("cut", "periodic"):
        raise ValueError(f"unknown edges {edges!r}")
    periodic = open_loop and edges == "periodic"
    span = float(seconds) if periodic else ramp + seconds
    if open_loop:
        n = max(1, int(round(float(traffic["rate_rps"]) * span)))
    elif traffic["loop"] == "closed":
        n = int(traffic["pool_requests"])
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    prompts = _lengths(traffic["prompt_tokens"], n, base)
    outputs = _lengths(traffic["output_tokens"], n, base)
    adapters = _adapters(traffic.get("adapters", {}), n, base)
    sizes = list(zip(prompts, outputs, adapters))
    if open_loop:
        gaps = arrival_gaps(traffic, n, base)
        scale = span / sum(gaps)
        gaps = [g * scale for g in gaps]
    else:
        gaps = [0.0] * n

    # The seed turns the fixed cycle of (size, gap) pairs to another starting
    # point and picks the prompts' letters.  A shuffle was tried first (chip
    # runs of PR 23): it pairs long prompts with short gaps anew in every
    # run, and the queueing that follows moved tpot_p50 by 9% and ttft_p90
    # by 40% between seeds whose runs repeat within 1-3% seed for seed.
    turn = int(seed) % n
    sizes = sizes[turn:] + sizes[:turn]
    gaps = gaps[turn:] + gaps[:turn]
    timed = []
    if periodic:
        # Before the window the cycle's end, walked backwards from 0 ...
        t = 0.0
        for size, gap in zip(reversed(sizes), reversed(gaps)):
            t -= gap
            if t < -ramp:
                break
            timed.insert(0, (t, size))
    t = 0.0 if periodic else -ramp
    for size, gap in zip(sizes, gaps):
        timed.append((t, size))
        t += gap
    if periodic:
        # ... and after it the cycle's start again.
        t = float(seconds)
        for size, gap in zip(sizes, gaps):
            if t >= seconds + float(traffic.get("tail_s", 0.0)):
                break
            timed.append((t, size))
            t += gap
    letters = random.Random(int(seed))
    return [Request(i, due if open_loop else 0.0, p, o, a,
                    _prompt(p, letters))
            for i, (due, (p, o, a)) in enumerate(timed)]


def payload(req: Request, base_model: str, tuned_models: list[str],
            stream: bool) -> dict:
    """The ``/v1/completions`` body of a request."""
    model = base_model if req.adapter is None else tuned_models[req.adapter]
    body = {"model": model, "prompt": req.prompt,
            "max_tokens": req.max_tokens, "temperature": 0,
            "logit_bias": LOGIT_BIAS}
    if stream:
        body["stream"] = True
    return body


def prefill_shapes(traffic: dict, buckets: list[int]) -> list[int]:
    """Prompt lengths (in tokens) that between them touch every prefill
    program the mix can meet: one per bucket that some length of
    ``[min, max]`` maps to, and one beyond the largest bucket where the mix
    reaches there (the chunk-stream program)."""
    lo = int(traffic["prompt_tokens"]["min"])
    hi = int(traffic["prompt_tokens"]["max"])
    if traffic["prompt_tokens"]["dist"] == "fixed":
        lo = hi = int(traffic["prompt_tokens"]["median"])
    buckets = sorted(buckets)
    shapes, prev = [], 0
    for b in buckets:
        if lo <= b and hi > prev:
            shapes.append(min(b, hi))
        prev = b
    if hi > buckets[-1]:
        shapes.append(hi)
    return shapes
