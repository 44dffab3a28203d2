"""LoRA core: merge/unmerge identity, batched == single, pool writes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lora


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def test_merge_unmerge_roundtrip():
    w = _rand((64, 32), 1)
    pair = {"A": _rand((8, 64), 2), "B": _rand((32, 8), 3)}
    merged = lora.merge_lora(w, pair, scale=0.5)
    back = lora.merge_lora(merged, pair, scale=0.5, sign=-1.0)
    np.testing.assert_allclose(np.asarray(back), np.asarray(w), atol=1e-5)


def test_merged_equals_unmerged():
    """Paper Fig. 2: y = x(W + sBA) == xW + s·BAx."""
    x = _rand((4, 64), 4)
    w = _rand((64, 32), 1)
    pair = {"A": _rand((8, 64), 2), "B": _rand((32, 8), 3)}
    merged = x @ lora.merge_lora(w, pair, scale=0.5)
    unmerged = x @ w + lora.lora_delta_single(x, pair["A"], pair["B"], 0.5)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(unmerged),
                               rtol=1e-4, atol=1e-4)


def test_batched_matches_single_per_request():
    """Batch LoRA Inference == running each request with its own adapter."""
    b, s, d_in, d_out, r, n = 5, 7, 48, 40, 4, 3
    x = _rand((b, s, d_in), 0)
    a_stack = _rand((n, r, d_in), 1)
    b_stack = _rand((n, d_out, r), 2)
    ids = jnp.asarray([0, 2, 1, 2, 0], jnp.int32)
    batched = lora.lora_delta_batched(x, a_stack, b_stack, ids, 0.7)
    for i in range(b):
        single = lora.lora_delta_single(x[i], a_stack[ids[i]],
                                        b_stack[ids[i]], 0.7)
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(single), rtol=1e-5,
                                   atol=1e-5)


def test_zero_b_init_is_identity():
    rng = jax.random.PRNGKey(0)
    pair = lora.init_lora_pair(rng, 32, 16, 4)
    x = _rand((3, 32), 5)
    delta = lora.lora_delta_single(x, pair["A"], pair["B"], 2.0)
    np.testing.assert_allclose(np.asarray(delta), 0.0)


@pytest.mark.parametrize("shape,ids", [
    ((3, 7, 48), (0, 2, 1)),          # [B, S, d], B·S=21 not a blk_t multiple
    ((5, 48), (3, 0, 0, 2, 1)),       # decode shape [B, d]
    ((1, 13, 48), (2,)),              # single-request ragged prefill
    ((4, 16, 48), (1, 1, 1, 1)),      # homogeneous batch
])
def test_sgmv_backend_matches_einsum(shape, ids):
    """The Pallas SGMV data path == the gather-einsum reference over
    mixed-adapter batches, including non-multiple-of-blk_t token counts."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    a_stack = jnp.asarray(rng.normal(size=(4, 4, 48)), jnp.float32)
    b_stack = jnp.asarray(rng.normal(size=(4, 40, 4)), jnp.float32)
    ids = jnp.asarray(ids, jnp.int32)
    y_e = lora.lora_delta_batched(x, a_stack, b_stack, ids, 0.7)
    y_k = lora.lora_delta_batched(x, a_stack, b_stack, ids, 0.7,
                                  backend="sgmv", interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_e),
                               rtol=2e-5, atol=1e-4)


def test_sgmv_backend_bf16_x_f32_pool_matches_einsum():
    """The serving-engine dtype mix (bf16 activations, f32 adapter pool):
    both backends must round the adapters to x.dtype before contracting,
    so they agree to bf16 precision — not just in all-f32 configs."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.bfloat16)
    a_stack = jnp.asarray(rng.normal(size=(4, 4, 64)), jnp.float32)
    b_stack = jnp.asarray(rng.normal(size=(4, 32, 4)), jnp.float32)
    ids = jnp.asarray([1, 3, 0], jnp.int32)
    y_e = lora.lora_delta_batched(x, a_stack, b_stack, ids, 0.7)
    y_k = lora.lora_delta_batched(x, a_stack, b_stack, ids, 0.7,
                                  backend="sgmv", interpret=True)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_e, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_apply_lora_mode_backend_dispatch():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32)
    pair = {"A": _rand((3, 4, 32), 1), "B": _rand((3, 24, 4), 2)}
    ids = jnp.asarray([2, 0], jnp.int32)
    d_e = lora.apply_lora(x, pair, lora.LoRAMode("batched", ids, 1.5))
    d_k = lora.apply_lora(x, pair, lora.LoRAMode("batched", ids, 1.5,
                                                 "sgmv", True))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_e),
                               rtol=2e-5, atol=1e-4)


def test_resolve_lora_backend():
    assert lora.resolve_lora_backend("einsum") == "einsum"
    assert lora.resolve_lora_backend("sgmv") == "sgmv"
    auto = lora.resolve_lora_backend("auto")
    assert auto == ("sgmv" if jax.default_backend() == "tpu" else "einsum")
    with pytest.raises(ValueError):
        lora.resolve_lora_backend("punica")


def test_model_forward_sgmv_equals_einsum_f32():
    """Whole-model check (f32 is bit-comparable; bf16 differs by
    accumulation order only): batched forward through every LoRA-bearing
    linear agrees across backends."""
    import dataclasses
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    cfg = dataclasses.replace(reduced_config(get_config("qwen2-0.5b")),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = model.init_lora(jax.random.PRNGKey(1), n_slots=4)
    pool = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(2), x.shape,
                                    x.dtype) * 0.05, pool)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (3, 16),
                                          0, cfg.vocab_size)}
    ids = jnp.asarray([0, 2, 1], jnp.int32)
    out_e, _ = model.forward(params, batch, pool,
                             lora.LoRAMode("batched", ids, cfg.lora.scale))
    out_k, _ = model.forward(params, batch, pool,
                             lora.LoRAMode("batched", ids, cfg.lora.scale,
                                           "sgmv", True))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_e),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(slot=st.integers(0, 3), seed=st.integers(0, 1000))
def test_pool_slot_write_isolated(slot, seed):
    """Writing pool slot i never disturbs slot j≠i (the pre-allocated
    block property of the heterogeneous memory manager)."""
    stack = {"A": _rand((4, 2, 8), seed), "B": _rand((4, 8, 2), seed + 1)}
    item = {"A": _rand((2, 8), seed + 2), "B": _rand((8, 2), seed + 3)}
    new = lora.load_adapter_into_slot(stack, item, slot)
    for k in ("A", "B"):
        np.testing.assert_allclose(np.asarray(new[k][slot]),
                                   np.asarray(item[k]))
        for j in range(4):
            if j != slot:
                np.testing.assert_allclose(np.asarray(new[k][j]),
                                           np.asarray(stack[k][j]))
