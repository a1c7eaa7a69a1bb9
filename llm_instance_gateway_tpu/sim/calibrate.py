"""Fit the simulator's latency model from the observables the gateway
already scrapes.

The reference calibrated its simulator constants offline against vLLM on
A100 (``constants.py:1-8``, notebook cells 2 & 5); this module fits the same
constants for OUR engine, so retuned scheduler thresholds transfer
(SURVEY.md §7 step 7: "refit prefill/decode constants to TPU continuous
batching ... before burning TPU hours").

``calibrate_from_observables`` fits the constants by least squares from
per-window means of the histogram families every replica already exports
(``tpu:prefill_seconds``, ``tpu:decode_step_seconds``,
``tpu:decode_batch_occupancy``, KV occupancy) — so the capacity twin
(gateway/capacity.py) self-calibrates from live traffic with **no TPU
access**.  Each observation window is a dict of window means:
``{prefill_tokens_mean, prefill_s_mean, kv_tokens_mean, batch_mean,
decode_step_s_mean}``.

``main`` emits the versioned committed artifact (``TWIN_CALIBRATION.json``,
format ``lig-twin-calibration/1``) with fit residuals, from deterministic
windows that ``sim_observables`` draws of a known model; the gateway loads
it via ``load_calibration``.

Run:  python -m llm_instance_gateway_tpu.sim.calibrate \
          --out TWIN_CALIBRATION.json
"""

from __future__ import annotations

import json
import random

import numpy as np

from llm_instance_gateway_tpu.sim.core import LatencyModel, V5E_DEFAULT

# Versioned artifact schema: bump the suffix on a breaking change so a
# twin never silently consumes constants fitted under different semantics.
CALIBRATION_FORMAT = "lig-twin-calibration/1"

# Constant -> decimal places in the artifact.  Rounded on WRITE (stable,
# diffable, reproducible byte-for-byte by tests); load returns the rounded
# values so the committed artifact IS the model the twin runs.
_MODEL_ROUND = {
    "prefill_min_s": 6,
    "prefill_base_s": 6,
    "prefill_per_token_s": 9,
    "decode_base_s": 6,
    "decode_per_kv_token_s": 12,
    "decode_per_seq_s": 9,
}


def calibrate_from_observables(
    observations: list,
    min_windows: int = 4,
) -> tuple[LatencyModel, dict]:
    """Fit ``LatencyModel`` constants from scraped observation windows.

    Each observation is one scrape-tick window of per-replica histogram
    deltas, reduced to means::

        {"prefill_tokens_mean": ..,   # Δtokens / Δprefills   (adapter_tokens)
         "prefill_s_mean": ..,        # Δtpu:prefill_seconds_sum / _count
         "kv_tokens_mean": ..,        # mean KV tokens held during the window
         "batch_mean": ..,            # Δtpu:decode_batch_occupancy_sum/_count
         "decode_step_s_mean": ..}    # Δtpu:decode_step_seconds_sum / _count

    Prefill is a line in prompt tokens (c0 + c1·tokens, polyfit); decode is
    a plane in (kv_tokens, batch) (c3 + c4·kv + c_batch·batch, lstsq over
    the [1, kv, batch] design matrix).  Returns ``(model, residuals)`` where
    residuals carries relative RMS fit error per phase — the artifact's
    honesty signal and the drift detector's prior.

    Raises ``ValueError`` when the windows can't identify the constants:
    fewer than ``min_windows``, no spread in prompt tokens, or a
    rank-deficient decode design (kv and batch moving in lockstep).
    """
    obs = [o for o in observations
           if o.get("prefill_s_mean", 0) > 0 and o.get("decode_step_s_mean", 0) > 0]
    if len(obs) < min_windows:
        raise ValueError(
            f"insufficient calibration windows: {len(obs)} < {min_windows}")

    # --- prefill line.
    xs = np.asarray([o["prefill_tokens_mean"] for o in obs], np.float64)
    ys = np.asarray([o["prefill_s_mean"] for o in obs], np.float64)
    if float(np.ptp(xs)) < 1.0:
        raise ValueError("degenerate prefill windows: no prompt-length spread")
    # Closed-form simple regression (identical least squares to a deg-1
    # polyfit, minus the SVD): this runs on the gateway's tick thread
    # every refit cadence, where three LAPACK round-trips per refit were
    # the dominant capacity-plane cost.
    mx = float(np.mean(xs))
    my = float(np.mean(ys))
    dx = xs - mx
    c1 = float(dx @ (ys - my)) / float(dx @ dx)
    c0 = my - c1 * mx
    c1 = max(c1, 0.0)
    c0 = max(c0, 1e-6)
    prefill_pred = c0 + c1 * xs
    prefill_rms = float(np.sqrt(np.mean((prefill_pred - ys) ** 2)))

    # --- decode plane.
    kv = np.asarray([o["kv_tokens_mean"] for o in obs], np.float64)
    batch = np.asarray([o["batch_mean"] for o in obs], np.float64)
    zs = np.asarray([o["decode_step_s_mean"] for o in obs], np.float64)
    design = np.stack([np.ones_like(kv), kv, batch], axis=1)
    # Normal equations on the 3x3 Gram matrix instead of an SVD lstsq
    # (same refit-on-tick-thread cost argument as the prefill line).
    # Columns are scaled to unit magnitude first so the degeneracy
    # check measures collinearity, not the kv-vs-batch unit gap.
    scale = np.maximum(np.abs(design).max(axis=0), 1e-12)
    scaled = design / scale
    gram = scaled.T @ scaled
    a11, a12, a13, a21, a22, a23, a31, a32, a33 = gram.ravel().tolist()
    det = (a11 * (a22 * a33 - a23 * a32)
           - a12 * (a21 * a33 - a23 * a31)
           + a13 * (a21 * a32 - a22 * a31))
    # Collinearity guard via the Hadamard ratio det/(g00*g11*g22) of
    # the scaled Gram — closed form where np.linalg.cond would run a
    # full SVD (the dominant term of a refit on the tick thread).  The
    # ratio falls off the same cliff near singularity the old
    # cond > 1e12 check caught: ~1e-3..1 for identifiable windows,
    # float-epsilon scale for collinear ones.
    if not det > 1e-12 * (a11 * a22 * a33):
        raise ValueError(
            "degenerate decode windows: kv/batch regressors are collinear")
    b1, b2, b3 = (scaled.T @ zs).tolist()
    # Cramer's rule for the 3x3 solve (guard above keeps it
    # well-conditioned; the decode_rms residual below audits the fit).
    x1 = (b1 * (a22 * a33 - a23 * a32)
          - a12 * (b2 * a33 - a23 * b3)
          + a13 * (b2 * a32 - a22 * b3)) / det
    x2 = (a11 * (b2 * a33 - a23 * b3)
          - b1 * (a21 * a33 - a23 * a31)
          + a13 * (a21 * b3 - b2 * a31)) / det
    x3 = (a11 * (a22 * b3 - a23 * b2)
          - a12 * (a21 * b3 - b2 * a31)
          + b1 * (a21 * a32 - a22 * a31)) / det
    s1, s2, s3 = scale.tolist()
    c3 = max(x1 / s1, 1e-6)
    c4 = max(x2 / s2, 0.0)
    c_batch = max(x3 / s3, 0.0)
    decode_pred = design @ np.asarray([c3, c4, c_batch])
    decode_rms = float(np.sqrt(np.mean((decode_pred - zs) ** 2)))

    model = LatencyModel(
        prefill_min_s=float(np.min(ys)),
        prefill_base_s=c0,
        prefill_per_token_s=c1,
        decode_base_s=c3,
        decode_per_kv_token_s=c4,
        decode_per_seq_s=c_batch,
    )
    residuals = {
        "windows": len(obs),
        "prefill_rms_s": round(prefill_rms, 9),
        "prefill_rms_rel": round(prefill_rms / max(float(np.mean(ys)), 1e-9), 6),
        "decode_rms_s": round(decode_rms, 9),
        "decode_rms_rel": round(decode_rms / max(float(np.mean(zs)), 1e-9), 6),
    }
    return model, residuals


def sim_observables(
    model: LatencyModel,
    seed: int = 0,
    windows: int = 24,
    noise: float = 0.0,
) -> list:
    """Deterministic observation windows a known ``model`` would produce.

    Seeded draws of window-mean regressors (prompt tokens, KV occupancy,
    decode batch) pushed through the model's own ``prefill_s``/``decode_s``
    — the ground-truth half of the calibration recovery test, and the
    source of the committed artifact (``main``).  ``noise`` adds a
    seeded relative perturbation to the timing means so the recovery test
    can exercise the 10% tolerance rather than an exact algebraic inverse.
    """
    rng = random.Random(seed)
    out = []
    for _ in range(windows):
        # Stay above the prefill_min clamp region so the line is identifiable.
        tokens = rng.uniform(96.0, 768.0)
        kv = rng.uniform(2_000.0, 60_000.0)
        batch = rng.uniform(1.0, 16.0)
        jitter = (lambda: 1.0 + rng.uniform(-noise, noise)) if noise else (lambda: 1.0)
        out.append({
            "prefill_tokens_mean": round(tokens, 3),
            "prefill_s_mean": round(model.prefill_s(tokens) * jitter(), 9),
            "kv_tokens_mean": round(kv, 3),
            "batch_mean": round(batch, 4),
            "decode_step_s_mean": round(model.decode_s(kv, batch) * jitter(), 12),
        })
    return out


def model_to_dict(model: LatencyModel) -> dict:
    """The artifact's ``model`` block: rounded, key order = schema order."""
    return {k: round(getattr(model, k), nd) for k, nd in _MODEL_ROUND.items()}


def model_from_dict(d: dict) -> LatencyModel:
    return LatencyModel(**{k: float(d[k]) for k in _MODEL_ROUND})


def calibration_artifact(model: LatencyModel, residuals: dict,
                         source: str, seed: int | None = None) -> dict:
    art = {
        "format": CALIBRATION_FORMAT,
        "source": source,
        "model": model_to_dict(model),
        "residuals": residuals,
    }
    if seed is not None:
        art["seed"] = seed
    return art


def write_calibration(path: str, artifact: dict) -> None:
    """Stable serialization (sorted keys, indent 1, trailing newline) so the
    committed artifact is byte-for-byte reproducible by the tests."""
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write("\n")


def load_calibration(path: str) -> tuple[LatencyModel, dict]:
    """Load an artifact; raises ``ValueError`` on an unknown format."""
    with open(path) as f:
        art = json.load(f)
    fmt = art.get("format")
    if fmt != CALIBRATION_FORMAT:
        raise ValueError(f"unknown calibration format: {fmt!r}")
    return model_from_dict(art["model"]), art


def main(argv: list | None = None) -> None:
    import argparse

    parser = argparse.ArgumentParser(
        description="fit the simulator LatencyModel and emit the versioned "
                    "calibration artifact the capacity twin loads")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the observation windows")
    parser.add_argument("--windows", type=int, default=24,
                        help="observation windows drawn of V5E_DEFAULT")
    parser.add_argument("--out", default="",
                        help="write the artifact JSON here (e.g. "
                        "TWIN_CALIBRATION.json); default prints to stdout")
    args = parser.parse_args(argv)

    obs = sim_observables(V5E_DEFAULT, seed=args.seed, windows=args.windows)
    model, residuals = calibrate_from_observables(obs)
    artifact = calibration_artifact(model, residuals, "sim", seed=args.seed)

    if args.out:
        write_calibration(args.out, artifact)
        print(f"wrote {args.out} ({artifact['source']}, "
              f"{artifact['residuals'].get('windows', 0)} windows)")
    else:
        print(json.dumps(artifact, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
