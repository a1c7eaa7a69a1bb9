"""Bytes and operations of the expert matmuls of ONE sparse layer-step, from
shapes: the numerator of the grouped-matmul kernel's roofline share
(``moe_gmm`` / ``moe_gmm_int8`` in a device trace; three calls a layer-step:
gate, up, down).  Kept with the benchmark, beside ``shapes.py``.

    share = max(bytes / peak bytes/s, flops / peak flop/s) / device time

A layer-step reads the three matrices of every expert that at least one live
assignment chose (``tpu:moe_experts_touched_total`` over
``tpu:moe_layer_steps_total`` says how many), and computes one row per
assignment; the rows a tile is padded with are the kernel's cost, not the
algorithm's, and are not counted.
"""

from __future__ import annotations

from benchmark.shapes import SCALE_BYTES, WEIGHT_BYTES

ACT_BYTES = 2  # bf16 activations in and out of each matmul


def expert_bytes(d_model: int, d_ff: int, weights: str = "int8") -> int:
    """Stored bytes of ONE expert: gate and up [d_model, d_ff], down
    [d_ff, d_model], int8 with one f32 scale per output channel."""
    n = 3 * d_model * d_ff * WEIGHT_BYTES[weights]
    if weights == "int8":
        n += (2 * d_ff + d_model) * SCALE_BYTES
    return n


def layer_step_bytes(d_model: int, d_ff: int, experts_touched: float,
                     rows: float, weights: str = "int8") -> float:
    """Bytes the three expert matmuls of one layer-step must move: the
    touched experts' weights once, and per assignment row the activations
    in and out of each matmul (x twice, gate, up, act, out)."""
    act = rows * ACT_BYTES * (2 * d_model + 3 * d_ff + d_model)
    return experts_touched * expert_bytes(d_model, d_ff, weights) + act


def layer_step_flops(d_model: int, d_ff: int, rows: float) -> float:
    """Multiply-adds x 2 of the three matmuls over ``rows`` assignments."""
    return 2.0 * rows * 3 * d_model * d_ff


def window_bytes(model: dict, inputs: dict, weights: str = "int8") -> float:
    """Bytes the expert matmuls of a whole window must move, for
    ``kernel_roofline``: ``inputs`` holds the window's growth of
    ``touched`` (``tpu:moe_experts_touched_total``) and ``assignments``
    (``tpu:moe_assignments_total``), each summed over the decode, prefill
    and chunk programs' layer-steps; ``layer_step_bytes`` is linear in both,
    so the window's totals give the window's bytes.  An expert's width is
    ``moe_d_ff`` where the model has one beside its dense ``d_ff``."""
    d_ff = model.get("moe_d_ff") or model["d_ff"]
    return layer_step_bytes(model["d_model"], d_ff, inputs["touched"],
                            inputs["assignments"], weights)


def roofline_share(model: dict, experts_touched: float, rows: float,
                   device_s: float, peak: dict,
                   weights: str = "int8") -> dict:
    """Share of the roofline the expert matmuls of one layer-step reached
    in ``device_s`` seconds of device time, and which bound it is.
    ``model``: a configuration file's ``model`` group; ``peak``:
    ``peaks.device_peaks(kind)``."""
    nbytes = layer_step_bytes(model["d_model"], model["d_ff"],
                              experts_touched, rows, weights)
    flops = layer_step_flops(model["d_model"], model["d_ff"], rows)
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    t_flops = flops / peak["bf16_flops"]
    return {"bytes": nbytes, "flops": flops,
            "bound": "hbm" if t_bytes >= t_flops else "mxu",
            "share_pct": 100.0 * max(t_bytes, t_flops) / device_s}
