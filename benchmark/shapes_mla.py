"""Bytes and operations of the latent (MLA) decode-attention kernel for ONE
layer-step, from shapes: the numerator of the kernel's roofline share
(``mla_decode_attention`` in a device trace; one call a layer a decode step).
Kept with the benchmark, beside ``shapes.py`` and ``shapes_moe.py``.

    share = max(bytes / peak bytes/s, flops / peak flop/s) / device time

A layer-step reads, for every live row, one latent cache row per position the
row holds: ``kv_lora_rank + qk_rope_head_dim`` numbers (512 + 64 for
GLM-4.7-Flash), read once and used as key and as value.  The lanes a stored
row is padded with (to a multiple of 128) and the positions a tile reaches
past a row's length are the kernel's cost, not the algorithm's, and are not
counted.  ``positions`` is ``tpu:latent_kv_positions_total`` over
``tpu:dispatch_steps_sum`` (the metric ``mla.ctx_positions_mean.batch``):
the live rows' cache lengths of one step, summed.
"""

from __future__ import annotations

CACHE_BYTES = 2  # bf16 latent rows
ACT_BYTES = 2    # bf16 queries in, attention over latents out


def layer_step_bytes(model: dict, positions: float, rows: float) -> float:
    """Bytes one layer-step's kernel must move: every held position's
    latent row once, and per live row its absorbed queries in (all heads x
    the row's width) and its outputs out (all heads x ``kv_lora_rank``)."""
    width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    act = rows * model["n_heads"] * ACT_BYTES * (width + model["kv_lora_rank"])
    return positions * width * CACHE_BYTES + act


def layer_step_flops(model: dict, positions: float) -> float:
    """Multiply-adds x 2: per position and head a score over the row's
    whole width and a value sum over its ``kv_lora_rank`` columns."""
    width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return 2.0 * positions * model["n_heads"] * (width + model["kv_lora_rank"])


def window_bytes(model: dict, inputs: dict) -> float:
    """Bytes the kernel must move over a whole window, for
    ``kernel_roofline``: ``inputs`` holds the window's growth of
    ``positions`` (``tpu:latent_kv_positions_total``: per decode step the
    live rows' cache lengths, summed) and of ``steps``
    (``tpu:dispatch_steps_sum``), and ``rows_mean``, the mean live rows of a
    decode step; every layer of the stack runs the kernel once a step, and
    ``layer_step_bytes`` is linear in positions and rows."""
    return model["n_layers"] * layer_step_bytes(
        model, inputs["positions"], inputs["rows_mean"] * inputs["steps"])


def roofline_share(model: dict, positions: float, rows: float,
                   device_s: float, peak: dict) -> dict:
    """Share of the roofline the kernel reached in ``device_s`` seconds of
    device time a layer-step, and which bound it is.  ``model``: a
    configuration file's ``model`` group; ``peak``:
    ``peaks.device_peaks(kind)``."""
    nbytes = layer_step_bytes(model, positions, rows)
    flops = layer_step_flops(model, positions)
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    t_flops = flops / peak["bf16_flops"]
    return {"bytes": nbytes, "flops": flops,
            "bound": "hbm" if t_bytes >= t_flops else "mxu",
            "share_pct": 100.0 * max(t_bytes, t_flops) / device_s}
