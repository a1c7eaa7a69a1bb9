"""The LoRA delta by slot (``models/lora.py:lora_delta``).

The batch goes once through every slot's ``a``, a one-hot mask keeps each
row's own slot's rank block, and the kept blocks meet every slot's ``b`` in
one matmul.  Whatever the shape, dtype and slot pattern, every row gets the
delta the per-row form gave it (``reference_lora_delta`` below: a frozen
copy of ``lora_delta`` as it stood before PR 34, which mixed a private copy
of both matrices for every row first), the gradients training takes are the
old form's, and no program holds a per-row copy of an adapter matrix.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_instance_gateway_tpu.models import lora as lora_lib
from llm_instance_gateway_tpu.models import transformer
from llm_instance_gateway_tpu.models.configs import TINY_TEST

# Wide enough that a matmul's summation order shows, small enough for the
# CPU: "gate" is 128 -> 256, "down" 256 -> 128, four slots of rank 16.
CFG = dataclasses.replace(TINY_TEST, d_model=128, d_ff=256, max_lora_rank=16)
LAYER = 1
SHAPES = {"decode": (32,), "prefill": (1, 24), "group": (4, 24)}
PATTERNS = ("all_base", "one_slot", "mixed", "rank8_padded", "unloaded")


def reference_lora_delta(x, a, b, scale, slot_ids):
    """``lora_delta`` as of PR 30, verbatim: each row's own ``a`` and ``b``
    mixed out of the slots, then one small matmul pair a row."""
    n_slots = a.shape[0]
    onehot = jax.nn.one_hot(slot_ids, n_slots, dtype=x.dtype)  # [B, n_slots]
    a_sel = jnp.einsum("bs,sir->bir", onehot, a)  # [B, d_in, r]
    b_sel = jnp.einsum("bs,sro->bro", onehot, b)  # [B, r, d_out]
    s_sel = (onehot.astype(jnp.float32) @ scale).astype(x.dtype)  # [B]
    if x.ndim == 3:
        mid = jnp.einsum("bsi,bir->bsr", x, a_sel)
        delta = jnp.einsum("bsr,bro->bso", mid, b_sel)
        return delta * s_sel[:, None, None]
    mid = jnp.einsum("bi,bir->br", x, a_sel)
    delta = jnp.einsum("br,bro->bo", mid, b_sel)
    return delta * s_sel[:, None]


def make_adapter(seed: int, rank: int) -> dict:
    rng = np.random.default_rng(seed)
    return {t: {"a": rng.standard_normal((CFG.n_layers, d_in, rank)) * 0.3,
                "b": rng.standard_normal((CFG.n_layers, rank, d_out)) * 0.3}
            for t, (d_in, d_out) in lora_lib.target_dims(CFG).items()}


@functools.lru_cache(maxsize=None)
def loaded_buffers(dtype_name: str, rank: int):
    """Every slot holding its own adapter of ``rank``, padded to 16."""
    bufs = lora_lib.init_lora_buffers(CFG, dtype=jnp.dtype(dtype_name))
    for slot in range(CFG.max_lora_slots):
        bufs = lora_lib.load_adapter(bufs, CFG, slot,
                                     make_adapter(100 + slot, rank),
                                     alpha=float(4 + slot), rank=rank)
    return bufs


def layer_operands(bufs, target: str):
    ll = lora_lib.layer_slice(bufs, LAYER)
    return ll[f"{target}_a"], ll[f"{target}_b"], ll["scale"]


def rows_and_slots(shape_name: str, pattern: str, d_in: int, dtype):
    lead = SHAPES[shape_name]
    rng = np.random.default_rng(len(shape_name) * 31 + len(pattern))
    x = jnp.asarray(rng.standard_normal((*lead, d_in)), dtype)
    n = lead[0]
    if pattern == "all_base":
        slots = np.full((n,), -1)
    elif pattern == "one_slot":
        slots = np.full((n,), 2)
    else:  # base rows among every slot's rows
        slots = rng.integers(-1, CFG.max_lora_slots, size=(n,))
        slots[0] = -1
        slots[-1] = 1
    return x, jnp.asarray(slots, jnp.int32)


def assert_same(new, old, dtype) -> None:
    new = np.asarray(new, np.float32)
    old = np.asarray(old, np.float32)
    assert new.shape == old.shape
    top = float(np.abs(old).max())
    if dtype == jnp.float32:
        np.testing.assert_allclose(new, old, rtol=0, atol=1e-5 * max(top, 1e-30))
        return
    # bf16 keeps 8 significant bits: one unit in the last place of a value v
    # is 2**(floor(log2 |v|) - 7).  Outputs that cancel to near nothing are
    # held to the unit of 1/64 of the largest value instead.
    mag = np.maximum(np.maximum(np.abs(old), np.abs(new)), top / 64)
    ulp = np.exp2(np.floor(np.log2(np.maximum(mag, 1e-30))) - 7)
    worst = np.abs(new - old) / ulp
    assert worst.max() <= 1.0, f"{worst.max()} units in the last place"


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_by_slot_equals_per_row(shape_name, dtype, pattern):
    rank = 8 if pattern == "rank8_padded" else 16
    bufs = loaded_buffers(jnp.dtype(dtype).name, rank)
    target = "down" if shape_name == "group" else "gate"
    d_in = lora_lib.target_dims(CFG)[target][0]
    x, slots = rows_and_slots(shape_name, pattern, d_in, dtype)
    a, b, scale = layer_operands(bufs, target)
    new = lora_lib.lora_delta(x, a, b, scale, slots)
    old = reference_lora_delta(x, a, b, scale, slots)
    assert new.dtype == old.dtype == dtype
    assert_same(new, old, dtype)
    base = np.asarray(slots) < 0
    assert not np.asarray(new, np.float32)[base].any()  # exactly 0
    if pattern == "all_base":
        return
    assert np.asarray(new, np.float32)[~base].any()
    if pattern == "rank8_padded" and dtype == jnp.float32:
        # The padded lanes add nothing: the delta is the rank-8 product.
        ad = make_adapter(100 + 1, 8)[target]
        row = int(np.flatnonzero(np.asarray(slots) == 1)[0])
        want = (np.asarray(x[row], np.float32) @ ad["a"][LAYER].astype(np.float32)
                @ ad["b"][LAYER].astype(np.float32)) * (5.0 / 8)
        np.testing.assert_allclose(np.asarray(new[row]), want, rtol=2e-4,
                                   atol=2e-4 * np.abs(want).max())
    if pattern == "unloaded":
        # Slot 1 zeroed between two calls of one program: its rows fall to
        # exactly 0, every other row keeps its bits.
        a2, b2, scale2 = layer_operands(
            lora_lib.unload_adapter(bufs, CFG, 1), target)
        again = lora_lib.lora_delta(x, a2, b2, scale2, slots)
        assert_same(again, reference_lora_delta(x, a2, b2, scale2, slots),
                    dtype)
        gone = np.asarray(slots) == 1
        assert gone.any()
        again, new = np.asarray(again, np.float32), np.asarray(new, np.float32)
        assert not again[gone].any()
        np.testing.assert_array_equal(again[~gone], new[~gone])


@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_gradients_equal_per_row(shape_name):
    """Training differentiates the forward with respect to the buffers."""
    bufs = loaded_buffers("float32", 16)
    x, slots = rows_and_slots(shape_name, "mixed", 128, jnp.float32)
    a, b, scale = layer_operands(bufs, "gate")
    w = jnp.asarray(np.random.default_rng(7).standard_normal(
        (*x.shape[:-1], b.shape[-1])), jnp.float32)

    def loss(fn, a, b):
        return jnp.sum(fn(x, a, b, scale, slots) * w)

    new = jax.grad(functools.partial(loss, lora_lib.lora_delta), (0, 1))(a, b)
    old = jax.grad(functools.partial(loss, reference_lora_delta), (0, 1))(a, b)
    for g_new, g_old in zip(new, old):
        assert_same(g_new, g_old, jnp.float32)
        assert np.asarray(g_old).any()
    # A slot no row uses takes no gradient in either form.
    unused = sorted(set(range(CFG.max_lora_slots)) - set(np.asarray(slots).tolist()))
    for s in unused:
        assert not np.asarray(new[0][s]).any() and not np.asarray(new[1][s]).any()


def _per_row_shapes(cfg, b: int) -> list[str]:
    """``tensor<...>`` prefixes of a per-row copy of any target's ``a``
    (``[B, d_in, r]``) or ``b`` (``[B, r, d_out]``)."""
    r = cfg.max_lora_rank
    out = set()
    for d_in, d_out in lora_lib.target_dims(cfg).values():
        out.add(f"tensor<{b}x{d_in}x{r}x")
        out.add(f"tensor<{b}x{r}x{d_out}x")
    return sorted(out)


def test_decode_program_holds_no_per_row_adapter_copy(monkeypatch):
    """The lowered decode program of a tiny preset with LoRA buffers: no
    array of shape ``[B, d_in, r]`` or ``[B, r, d_out]``.  Rank 3, five
    slots and seven rows, so that no other array of the model has such a
    shape; the frozen per-row form, lowered the same way, has them all."""
    cfg, b = dataclasses.replace(TINY_TEST, max_lora_rank=3,
                                 max_lora_slots=5), 7
    params = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    bufs = jax.eval_shape(
        lambda: lora_lib.init_lora_buffers(cfg, dtype=jnp.float32))
    cache = jax.eval_shape(lambda: transformer.init_decode_cache(
        cfg, b, 32, dtype=jnp.float32))
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32)

    def lowered() -> str:
        fn = functools.partial(transformer.decode_step, cfg)
        return jax.jit(fn).lower(params, cache, i32, i32, bufs, i32).as_text()

    text = lowered()
    assert re.search(rf"tensor<{b}x1x{cfg.max_lora_slots}x3xf32>", text)  # mid
    found = [s for s in _per_row_shapes(cfg, b) if s in text]
    assert not found, found
    monkeypatch.setattr(lora_lib, "lora_delta", reference_lora_delta)
    old = lowered()
    assert all(s in old for s in _per_row_shapes(cfg, b))
