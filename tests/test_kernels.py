"""Per-kernel shape/dtype sweeps against the pure-jnp ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref


@pytest.mark.parametrize("B,S,H,KV,hd,causal,bq,bk", [
    (2, 512, 4, 2, 64, True, 128, 128),
    (1, 1024, 8, 8, 128, True, 256, 256),
    (2, 256, 4, 1, 64, False, 128, 128),    # MQA
    (1, 512, 6, 2, 128, True, 512, 256),    # uneven block_q/block_k
    (2, 256, 4, 4, 32, True, 128, 128),     # small head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, H, KV, hd, causal, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_blockwise_attn_matches_plain():
    """The XLA-level online-softmax path used at long seq == plain softmax."""
    from repro.models.attention import _plain_attn, blockwise_attn
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, KV, hd = 2, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    pos = jnp.arange(S, dtype=jnp.int32)
    for kind, win in [("causal", 0), ("swa", 128), ("chunked", 128),
                      ("bidir", 0), ("prefix", 0)]:
        ref = _plain_attn(q, k, v, pos, pos, kind, win, 64)
        out = blockwise_attn(q, k, v, pos, pos, kind, win, 64,
                             q_block=128, kv_block=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
