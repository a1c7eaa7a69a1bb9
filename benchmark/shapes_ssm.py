"""Bytes and operations of the state-space decode update for ONE layer-step,
from shapes: the numerator of the kernel's roofline share
(``ssm_decode_update`` in a device trace; one call a layer a decode step).
Kept with the benchmark, beside ``shapes.py``, ``shapes_moe.py`` and
``shapes_mla.py``.

    share = max(bytes / peak bytes/s, flops / peak flop/s) / device time

A layer-step reads, for every live row, the row's recurrent state
``ssm_n_heads x ssm_head_dim x ssm_d_state`` float32 numbers (32 x 128 x 256:
4 MiB for Falcon-H1-34B) and writes it back changed, once each way: the
update is ``H' = exp(dt A) H + dt x (outer) B``, ``y = H' C + D x``.  Beside
the state a row brings x [heads x head_dim], B and C [groups x d_state] and
dt [heads] in and takes y [heads x head_dim] out, in float32; A and D are a
vector a layer.  What the kernel is handed beyond that (exp(dt A), dt x and
D x laid out per head, B and C padded to eight rows) and the rows that sit
out are the kernel's cost, not the algorithm's, and are not counted.
``rows`` is ``tpu:ssm_state_rows_total``: live rows, summed over the decode
steps (the metric ``ssm.state_rows_mean.batch`` is that over
``tpu:dispatch_steps_sum``).
"""

from __future__ import annotations

STATE_BYTES = 4  # the state is float32, whatever the activations are
VEC_BYTES = 4    # x, B, C, dt in and y out as the update takes them


def row_bytes(model: dict) -> float:
    """Bytes one live row's update must move in one layer-step."""
    heads, hd = model["ssm_n_heads"], model["ssm_head_dim"]
    state = heads * hd * model["ssm_d_state"]
    vectors = (2 * heads * hd  # x in, y out
               + 2 * model["ssm_n_groups"] * model["ssm_d_state"]  # B, C
               + heads)  # dt
    return 2 * state * STATE_BYTES + vectors * VEC_BYTES


def row_flops(model: dict) -> float:
    """Per state entry a decay multiply, an increment multiply-add and a
    multiply-add against C: 5 operations."""
    return 5.0 * (model["ssm_n_heads"] * model["ssm_head_dim"]
                  * model["ssm_d_state"])


def layer_step_bytes(model: dict, rows: float) -> float:
    return rows * row_bytes(model)


def window_bytes(model: dict, inputs: dict) -> float:
    """Bytes the kernel must move over a whole window, for
    ``kernel_roofline``: ``inputs`` holds the window's growth of ``rows``
    (``tpu:ssm_state_rows_total``: per decode step the rows whose state it
    rewrites); every layer of the stack runs the kernel once a step."""
    return model["n_layers"] * layer_step_bytes(model, inputs["rows"])


def roofline_share(model: dict, rows: float, device_s: float,
                   peak: dict) -> dict:
    """Share of the roofline the kernel reached in ``device_s`` seconds of
    device time a layer-step with ``rows`` live rows, and which bound it is
    (float32 on the VPU: the chip's published peaks name no float32 rate,
    so the bf16 one stands in as the flop bound, which then can only read
    low).  ``model``: a configuration file's ``model`` group; ``peak``:
    ``peaks.device_peaks(kind)``."""
    nbytes = layer_step_bytes(model, rows)
    flops = rows * row_flops(model)
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    t_flops = flops / peak["bf16_flops"]
    return {"bytes": nbytes, "flops": flops,
            "bound": "hbm" if t_bytes >= t_flops else "mxu",
            "share_pct": 100.0 * max(t_bytes, t_flops) / device_s}
