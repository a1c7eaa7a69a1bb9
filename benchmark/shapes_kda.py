"""Bytes of the delta-rule decode update and of the latent decode-attention
kernel over a window of a model only SOME of whose layers run each
(Ling-3.0-flash: five layers in six are Kimi Delta Attention and hold a matrix
state, the sixth is latent attention and holds a latent row), from shapes: the
numerators of two kernels' roofline shares (``kda_decode_update`` and
``mla_decode_attention`` in a device trace; one call a layer of the kind a
decode step).  Kept with the benchmark, beside ``shapes_ssm.py`` and
``shapes_mla.py``, whose ``window_bytes`` multiply by ``model["n_layers"]``,
every layer (12 of this stack where 10 and 2 run the kernels).

    share = bytes / peak bytes/s / device time

(``readers.kernel_roofline``.  Bytes alone: the update is float32 on the VPU,
some 9 operations a state entry beside its 8 bytes.)

A layer-step of a KDA layer reads, for every live row, the row's state
``kda_n_heads x kda_head_dim x kda_head_dim`` float32 numbers (32 x 128 x 128:
2 MiB) and writes it back changed, once each way:

    Sd = exp(g) * S (rows);  u = beta (v - k^T Sd);  S' = Sd + k u^T;  o = S'^T q

Beside the state a row brings q, k and g [heads x head_dim] and v [heads x
head_dim] in, beta [heads] in, and takes o [heads x head_dim] out, in
float32.  What the kernel is handed beyond that (k, q and exp(g) as the rows
of one 128 x 128 tile that it transposes, beta * v and beta laid along the
values) and the rows that sit out are the kernel's cost, not the algorithm's,
and are not counted.  ``rows`` is ``tpu:kda_state_rows_total``: live rows,
summed over the decode steps (``kda.state_rows_mean.batch`` is that over
``tpu:dispatch_steps_sum``).
"""

from __future__ import annotations

from benchmark import shapes_mla

STATE_BYTES = 4  # the state is float32, whatever the activations are
VEC_BYTES = 4    # q, k, g, v, beta in and o out as the update takes them


def layers_of(model: dict, kind: str) -> int:
    """Layers of the stack whose kind in ``layer_pattern`` (the period,
    counted from layer 0 of the model) is ``kind``."""
    pattern = model["layer_pattern"]
    return sum(pattern[l % len(pattern)] == kind
               for l in range(model["n_layers"]))


def row_bytes(model: dict) -> int:
    """Bytes one live row's update must move in one layer-step."""
    heads, d = model["kda_n_heads"], model["kda_head_dim"]
    vectors = 5 * heads * d + heads  # q, k, g, v in, o out; beta
    return 2 * heads * d * d * STATE_BYTES + vectors * VEC_BYTES


def window_bytes(model: dict, inputs: dict) -> float:
    """Bytes ``kda_decode_update`` must move over a whole window, for
    ``kernel_roofline``: ``inputs`` holds the window's growth of ``rows``
    (``tpu:kda_state_rows_total``: per decode step the rows whose state it
    rewrites); every KDA layer runs the kernel once a step."""
    return layers_of(model, "kda") * inputs["rows"] * row_bytes(model)


def latent_window_bytes(model: dict, inputs: dict) -> float:
    """Bytes ``mla_decode_attention`` must move over a whole window in a
    stack only some of whose layers are latent attention:
    ``shapes_mla.layer_step_bytes`` (every held position's latent row once,
    the live rows' absorbed queries in and outputs out) times the layers
    whose kind holds a latent row.  ``inputs`` as
    ``shapes_mla.window_bytes`` takes them: the window's growth of
    ``positions`` (``tpu:latent_kv_positions_total``) and of ``steps``
    (``tpu:dispatch_steps_sum``), and ``rows_mean``."""
    return layers_of(model, "mla") * shapes_mla.layer_step_bytes(
        model, inputs["positions"], inputs["rows_mean"] * inputs["steps"])
