"""Bytes of the lane decode-attention kernel over a window of a model only
SOME of whose layers hold K and V (LFM2: three layers in four run a gated
short convolution and hold none), from shapes: the numerator of the kernel's
roofline share (``decode_attention`` in a device trace; one call an ATTENTION
layer a decode step).  Kept with the benchmark, beside ``shapes_attn.py``,
whose ``layers_by_kind`` counts every layer that is not a window layer as one
with lanes (14 of this stack where 3 hold them).

    share = bytes / peak bytes/s / device time

(``readers.kernel_roofline``.  Bytes alone: a position costs 8 kv heads x 4
queries x 64 x 2 x 2 = 8,192 operations beside its 2,048 B, 4 a byte where
the chip's peaks stand 240 apart, so the bound is never the MXU's, not even
with every 64-wide head's product padded to the 128 lanes of its packed
row.)

A layer-step of an attention layer reads, for every live row, the K and the
V vector of each position the row holds (``n_kv_heads x head_dim`` numbers
each: 2,048 B a position for 8 x 64 in bf16, two heads to a 128-lane row).
Beside them a row brings its queries in and takes its outputs out
(``n_heads x head_dim`` each; the zeros a query is padded with into its
packed row are the kernel's cost, not the algorithm's, like the positions a
tile reaches past a row's end and the grid steps of rows that sit out: not
counted).  ``full`` is ``tpu:kv_positions_read_total{lanes="full"}``: per
decode step the positions the live rows hold in a layer with lanes, summed
(``kv.full_positions_mean.batch`` is that over ``tpu:dispatch_steps_sum``).
The conv layers' own bytes (a row's two carried inputs in and out, 16 KiB a
row a layer) are no kernel's: XLA's fusions move them, and no reader sees
those yet (PERF.md section 7).
"""

from __future__ import annotations

CACHE_BYTES = 2  # bf16 K and V
ACT_BYTES = 2    # bf16 queries in, attention outputs out
LANE_KINDS = ("full", "nope")  # kinds of ``layer_pattern`` that hold lanes


def lane_layers(model: dict) -> int:
    """Layers of the stack that hold K and V lanes: ``layer_pattern`` is
    the period counted from layer 0 of the model; a model without one holds
    them in every layer."""
    pattern = model.get("layer_pattern") or ["full"]
    return sum(pattern[l % len(pattern)] in LANE_KINDS
               for l in range(model["n_layers"]))


def position_bytes(model: dict) -> int:
    """K and V of one position of one layer."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * CACHE_BYTES


def row_step_bytes(model: dict) -> int:
    """Queries in and outputs out of one live row in one layer-step."""
    return 2 * model["n_heads"] * model["head_dim"] * ACT_BYTES


def window_bytes(model: dict, inputs: dict) -> float:
    """Bytes the kernel must move over a whole window, for
    ``kernel_roofline``: ``inputs`` holds the window's growth of ``full``
    (``tpu:kv_positions_read_total{lanes="full"}``: per decode step the
    positions the live rows hold in a layer with lanes) and of ``steps``
    (``tpu:dispatch_steps_sum``), and ``rows_mean``, the mean live rows of
    a decode step.  Every layer with lanes runs the kernel once a step."""
    n = lane_layers(model)
    return n * (inputs["full"] * position_bytes(model)
                + inputs["rows_mean"] * inputs["steps"]
                * row_step_bytes(model))
