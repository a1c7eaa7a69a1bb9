"""Multi-LoRA serving slots: padded adapter buffers with no-recompile swap.

The reference multiplexes LoRA adapters on vLLM via HTTP hot-load
(``tools/dynamic-lora-sidecar/sidecar/sidecar.py:177-213``) with CUDA-side
slot limits (``--max-loras 4``).  On TPU the equivalent must dodge XLA's
recompile-on-shape-change (SURVEY.md §7 "hard parts"): adapters live in
PRE-ALLOCATED buffers of compile-time shape ``[n_layers, n_slots, d,
r_max]`` — loading an adapter is a pure device-buffer donation
(``buffers.at[:, slot].set(...)``).

Adapters with rank r < r_max are zero-padded: padded lanes contribute exactly
0 to the delta, so correctness is rank-independent.  Per-slot ``scale`` holds
alpha/r.  Slot id -1 means "no adapter" (one_hot(-1) == 0 vector -> delta 0),
which lets base-model and adapter requests share one decode batch — the
multiplexing the gateway's LoRA-affinity routing assumes.

The delta is computed BY SLOT (``lora_delta``): two matmuls a target over
all slots at once, each row keeping its own slot's rank block in between.
A program reads every slot's matrices of every target it is HANDED once for
the batch, whatever its rows ask for, and holds no operation for a target
whose ``{t}_a`` / ``{t}_b`` are not in the dict it was handed (``_project``,
``layer_slice``, ``stack_for_scan`` go by the keys).  A prompt program is
always handed all seven, so a base prompt pays the read for nothing.  A
decode block is handed what ``Engine._block_lora_buffers`` decides from the
slot ids it stages: nothing (``lora_bufs=None``, no delta at all) when none of
its rows names an adapter, else the buffers of the targets that some RESIDENT
adapter carries (``LoRAManager.resident_targets``; a target no resident
adapter carries holds exact zeros in every slot).  Most published adapters
carry q and v only, and then five of the seven reads never happen.

What "never a new program" means, then.  Each set of targets is another trace
of the decode program, so:

- never for the engine loop: a block only ever runs a trace that was compiled
  before a row could ask for it (``Engine._retarget``);
- never for a load INSIDE the set (an adapter whose targets a resident one
  already carries): the buffer write it always was;
- one compile, off the loop, for a load that WIDENS the set: ``LoRAManager.
  load`` has the engine compile the wider traces on its own (HTTP) thread
  before it publishes the adapter, so such a load takes a compile longer;
- an unload that narrows the set leaves the wider traces in use until a
  helper thread has compiled the narrower ones.

``tpu:lora_rows_total`` counts the rows that use the read,
``tpu:lora_free_steps_total`` the decode steps that do not make it,
``tpu:lora_target_reads_total`` the targets the other steps were handed.

Targets: the attention projections q/k/v/o and the MLP gate/up/down, matching
what vLLM serves for Llama-family adapters.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

TARGETS = ("q", "k", "v", "o", "gate", "up", "down")


def target_dims(cfg) -> dict[str, tuple[int, int]]:
    """(d_in, d_out) per LoRA target for this architecture."""
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    return {
        "q": (d, cfg.n_heads * hd),
        "k": (d, cfg.n_kv_heads * hd),
        "v": (d, cfg.n_kv_heads * hd),
        "o": (cfg.n_heads * hd, d),
        "gate": (d, cfg.d_ff),
        "up": (d, cfg.d_ff),
        "down": (cfg.d_ff, d),
    }


def init_lora_buffers(cfg, dtype=jnp.bfloat16) -> dict[str, Any]:
    """All-zero slot buffers (zero delta == base model for every slot)."""
    dims = target_dims(cfg)
    bufs: dict[str, Any] = {"scale": jnp.zeros((cfg.max_lora_slots,), jnp.float32)}
    for t in TARGETS:
        d_in, d_out = dims[t]
        bufs[f"{t}_a"] = jnp.zeros(
            (cfg.n_layers, cfg.max_lora_slots, d_in, cfg.max_lora_rank), dtype
        )
        bufs[f"{t}_b"] = jnp.zeros(
            (cfg.n_layers, cfg.max_lora_slots, cfg.max_lora_rank, d_out), dtype
        )
    return bufs


def load_adapter(
    bufs: dict[str, Any],
    cfg,
    slot: int,
    adapter: dict[str, Any],
    alpha: float,
    rank: int,
) -> dict[str, Any]:
    """Write an adapter into ``slot`` (host-side; returns updated buffers).

    ``adapter`` maps target -> {"a": [n_layers, d_in, r], "b": [n_layers, r,
    d_out]} with r <= max_lora_rank; missing targets stay zero.  Scale
    alpha/r is folded into the per-slot scale vector.
    """
    if not 0 <= slot < cfg.max_lora_slots:
        raise ValueError(f"slot {slot} out of range [0, {cfg.max_lora_slots})")
    if rank > cfg.max_lora_rank:
        raise ValueError(f"rank {rank} exceeds max_lora_rank {cfg.max_lora_rank}")
    dims = target_dims(cfg)
    out = dict(bufs)
    for t in TARGETS:
        d_in, d_out = dims[t]
        a_buf = np.zeros((cfg.n_layers, d_in, cfg.max_lora_rank), np.float32)
        b_buf = np.zeros((cfg.n_layers, cfg.max_lora_rank, d_out), np.float32)
        if t in adapter:
            a = np.asarray(adapter[t]["a"], np.float32)
            b = np.asarray(adapter[t]["b"], np.float32)
            if a.shape != (cfg.n_layers, d_in, rank):
                raise ValueError(f"{t}.a shape {a.shape} != {(cfg.n_layers, d_in, rank)}")
            if b.shape != (cfg.n_layers, rank, d_out):
                raise ValueError(f"{t}.b shape {b.shape} != {(cfg.n_layers, rank, d_out)}")
            a_buf[:, :, :rank] = a
            b_buf[:, :rank, :] = b
        dtype = out[f"{t}_a"].dtype
        out[f"{t}_a"] = out[f"{t}_a"].at[:, slot].set(jnp.asarray(a_buf, dtype))
        out[f"{t}_b"] = out[f"{t}_b"].at[:, slot].set(jnp.asarray(b_buf, dtype))
    out["scale"] = out["scale"].at[slot].set(alpha / rank)
    return out


def select_targets(bufs: dict[str, Any], targets) -> dict[str, Any]:
    """``scale`` and the ``{t}_a`` / ``{t}_b`` of ``targets``: the same device
    arrays under the same keys, nothing copied.  A program handed this dict
    computes those targets' deltas and no other (another pytree, so another
    trace of it); all of ``TARGETS`` gives the whole dict back."""
    out = {"scale": bufs["scale"]}
    for t in targets:
        out[f"{t}_a"], out[f"{t}_b"] = bufs[f"{t}_a"], bufs[f"{t}_b"]
    return out


def unload_adapter(bufs: dict[str, Any], cfg, slot: int) -> dict[str, Any]:
    """Zero a slot (slot becomes base-model passthrough)."""
    out = dict(bufs)
    for t in TARGETS:
        out[f"{t}_a"] = out[f"{t}_a"].at[:, slot].set(0.0)
        out[f"{t}_b"] = out[f"{t}_b"].at[:, slot].set(0.0)
    out["scale"] = out["scale"].at[slot].set(0.0)
    return out


# ---------------------------------------------------------------------------
# Application inside the forward pass.  ``layer_bufs`` is the per-layer slice
# {t_a: [n_slots, d_in, r], t_b: [n_slots, r, d_out], scale: [n_slots]}.
# ---------------------------------------------------------------------------


@jax.named_scope("lora")
def lora_delta(
    x: jax.Array,          # [B, S, d_in] or [B, d_in]
    a: jax.Array,          # [n_slots, d_in, r]
    b: jax.Array,          # [n_slots, r, d_out]
    scale: jax.Array,      # [n_slots]
    slot_ids: jax.Array,   # [B] int32, -1 = no adapter
) -> jax.Array:
    """Multi-adapter delta: scale[s] * (x @ a[s]) @ b[s], s = slot_ids[row].

    By slot, not by row: the batch goes once through every slot's ``a``
    (one ``[B, d_in] x [d_in, n_slots * r]`` matmul), a one-hot mask keeps
    each row's own slot's rank block, and the kept blocks meet every slot's
    ``b`` in one ``[B, n_slots * r] x [n_slots * r, d_out]`` matmul.  The
    same ``r`` products per output as the row's own adapter alone, plus
    exact zeros; ``a`` and ``b`` are each read once for the whole batch.
    A slot -1 row's mask is all zero, so its delta is exactly 0.

    Do not mix the matrices per row first (``einsum("bs,sir->bir")``): that
    writes a private ``[B, d_in, r]`` and ``[B, r, d_out]`` copy for every
    row of every target of every layer, which on a v5e cost a fifth of the
    3584 x 18944 matmul a rank-16 delta corrects (PERF.md, PR 34).
    """
    n_slots = a.shape[0]
    onehot = jax.nn.one_hot(slot_ids, n_slots, dtype=x.dtype)  # [B, n_slots]
    s_sel = (onehot.astype(jnp.float32) @ scale).astype(x.dtype)  # [B]
    x3 = x.reshape(x.shape[0], -1, x.shape[-1])  # a decode row: one position
    mid = jnp.einsum("bti,sir->btsr", x3, a) * onehot[:, None, :, None]
    delta = jnp.einsum("btsr,sro->bto", mid, b) * s_sel[:, None, None]
    return delta.reshape(*x.shape[:-1], -1)


def layer_slice(bufs: dict[str, Any], layer: jax.Array | int) -> dict[str, Any]:
    """Per-layer view of the buffers handed, for use inside lax.scan over
    layers."""
    per_layer, out = stack_for_scan(bufs)
    for k, v in per_layer.items():
        out[k] = jax.lax.dynamic_index_in_dim(v, layer, axis=0,
                                              keepdims=False)
    return out


def stack_for_scan(bufs: dict[str, Any]) -> tuple[dict[str, Any], dict[str, Any]]:
    """Split buffers into (per-layer stacked pytree, broadcast pytree) for scan."""
    per_layer = {k: v for k, v in bufs.items() if k != "scale"}
    broadcast = {"scale": bufs["scale"]}
    return per_layer, broadcast
