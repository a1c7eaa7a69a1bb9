"""Bytes and operations that a step of the model must move, from its shapes.

``model`` is the ``model`` group of a configuration file (the ModelConfig
fields as served).  These functions are the numerator of every roofline
share the benchmark reports; they live here so that no PR that claims a gain
can change them.
"""

from __future__ import annotations

WEIGHT_BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}
SCALE_BYTES = 4  # int8 weights carry one f32 scale per output channel
KV_BYTES = {"bfloat16": 2, "int8": 1, "float32": 4}


def _pad(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _matrix(rows: int, cols: int, weights: str) -> int:
    """Stored bytes of a [rows, cols] projection (cols = output channels)."""
    n = rows * cols * WEIGHT_BYTES[weights]
    if weights == "int8":
        n += cols * SCALE_BYTES
    return n


def layer_weight_bytes(model: dict, weights: str = "int8",
                       experts_read: float | None = None) -> float:
    """Stored bytes of one decoder layer that a step reads.  For a sparse
    layer ``experts_read`` is how many experts' matrices are read (all of
    them when None)."""
    d, hd = model["d_model"], model["head_dim"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    attn = (_matrix(d, q, weights) + 2 * _matrix(d, kv, weights)
            + _matrix(q, d, weights))
    if model.get("attention_bias"):
        attn += (q + 2 * kv) * 2  # bf16 biases
    mlp = 3 * _matrix(d, model["d_ff"], weights)  # gate, up, down
    n_exp = model.get("n_experts", 0)
    if n_exp:
        read = n_exp if experts_read is None else experts_read
        mlp = mlp * read + d * n_exp * 2  # + the bf16 router
    norms = 2 * d * 2
    return attn + mlp + norms


def experts_touched(n_experts: int, per_token: int, rows: float) -> float:
    """Expected number of distinct experts that ``rows`` tokens routed
    uniformly to ``per_token`` experts each touch."""
    return n_experts * (1.0 - (1.0 - per_token / n_experts) ** rows)


def weight_bytes(model: dict, weights: str = "int8",
                 experts_read: float | None = None) -> float:
    """Weight bytes one decode step must read: every layer, the final norm
    and the output head.  The embedding is a gather of one row per batch
    row and is left out."""
    head = _matrix(model["d_model"], _pad(model["vocab_size"], 128), weights)
    return (model["n_layers"] * layer_weight_bytes(model, weights,
                                                   experts_read)
            + head + model["d_model"] * 2)


def kv_bytes_per_token(model: dict, kv: str = "bfloat16") -> int:
    """Bytes of keys and values that one cached position holds, all layers."""
    return (model["n_layers"] * 2 * model["n_kv_heads"] * model["head_dim"]
            * KV_BYTES[kv])


def decode_step_bytes(model: dict, rows: float, context: float,
                      weights: str = "int8", kv: str = "bfloat16",
                      experts_per_token: int = 2,
                      experts_read: float | None = None) -> float:
    """Bytes one decode step over ``rows`` live rows of mean length
    ``context`` must read: the weights as stored (for sparse layers, the
    experts the batch touches) and the cached keys and values in use.
    ``experts_read``: how many experts a sparse layer-step read, where a
    counter says so; otherwise what uniform routing would touch."""
    if not model.get("n_experts"):
        experts_read = None
    elif experts_read is None:
        experts_read = experts_touched(model["n_experts"], experts_per_token,
                                       max(rows, 1.0))
    return (weight_bytes(model, weights, experts_read)
            + rows * context * kv_bytes_per_token(model, kv))


def kv_cache_bytes(model: dict, slots: int, max_seq_len: int,
                   kv: str = "bfloat16") -> int:
    return slots * max_seq_len * kv_bytes_per_token(model, kv)
