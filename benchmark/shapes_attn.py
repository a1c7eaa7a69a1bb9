"""Bytes of the lane decode-attention kernel over a window of a
model with TWO kinds of cache lane, from shapes: the numerator of the
kernel's roofline share (``decode_attention`` over the full layers' lanes and
``decode_attention_window`` over the window layers' rings in a device trace;
one call a layer a decode step).  Kept with the benchmark, beside
``shapes.py``, ``shapes_moe.py``, ``shapes_mla.py`` and ``shapes_ssm.py``.

    share = bytes / peak bytes/s / device time

(``readers.kernel_roofline``.  Bytes alone: a position costs 14,336
operations beside its 2,048 B, 7 a byte where the chip's peaks stand 240
apart, so the bound is never the MXU's.)

A layer-step reads, for every live row, the K and the V vector of each
position the layer's attention sees: all the row holds in a full layer, at
most the window of them in a window layer (``n_kv_heads x head_dim`` numbers
each: 2,048 B a position for SmallThinker's 4 x 128 in bf16).  Beside them a
row brings its queries in and takes its outputs out (``n_heads x head_dim``
each).  The positions a tile reaches past a row's end, and the grid steps of
the rows that sit out, are the kernel's cost, not the algorithm's, and are
not counted.  ``full`` and ``window`` are ``tpu:kv_positions_read_total`` by
its ``lanes`` label: per decode step the positions the live rows hold in a
layer of the kind, summed (the metrics ``kv.full_positions_mean.batch`` and
``kv.window_positions_mean.batch`` are those over ``tpu:dispatch_steps_sum``).
"""

from __future__ import annotations

CACHE_BYTES = 2  # bf16 K and V
ACT_BYTES = 2    # bf16 queries in, attention outputs out


def layers_by_kind(model: dict) -> tuple[int, int]:
    """(full layers, window layers) of the stack: ``layer_pattern`` is the
    period that ``n_layers`` repeats; a model without one is all full."""
    pattern = model.get("layer_pattern") or ["full"]
    n_win = (model["n_layers"] // len(pattern)) * sum(
        1 for kind in pattern if kind == "window")
    return model["n_layers"] - n_win, n_win


def position_bytes(model: dict) -> int:
    """K and V of one position of one layer."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * CACHE_BYTES


def row_step_bytes(model: dict) -> int:
    """Queries in and outputs out of one live row in one layer-step."""
    return 2 * model["n_heads"] * model["head_dim"] * ACT_BYTES


def window_bytes(model: dict, inputs: dict) -> float:
    """Bytes the kernel must move over a whole window, for
    ``kernel_roofline``: ``inputs`` holds the window's growth of ``full``
    and ``window`` (``tpu:kv_positions_read_total`` by ``lanes``: per decode
    step the positions the live rows hold in a layer of the kind) and of
    ``steps`` (``tpu:dispatch_steps_sum``), and ``rows_mean``, the mean live
    rows of a decode step.  Every layer runs the kernel once a step over its
    own kind of lane."""
    n_full, n_win = layers_by_kind(model)
    positions = n_full * inputs["full"] + n_win * inputs["window"]
    row_steps = (n_full + n_win) * inputs["rows_mean"] * inputs["steps"]
    return (positions * position_bytes(model)
            + row_steps * row_step_bytes(model))
