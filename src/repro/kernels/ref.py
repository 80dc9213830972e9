"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Full f32 products for every distance matmul. At DEFAULT precision a TPU
# multiplies f32 operands in one bf16 pass; the a² − 2ab + b² expansion
# then moves d² by far more than the gap between pairs near ε.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def pairwise_l2(a: jax.Array, b: jax.Array) -> jax.Array:
    """Squared L2 distance matrix: (M, d) × (N, d) → (M, N) float32."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)
    ab = jnp.matmul(a, b.T, precision=MATMUL_PRECISION)
    d2 = a2 - 2.0 * ab + b2.T
    return jnp.maximum(d2, 0.0)


def pairwise_l2_threshold(a: jax.Array, b: jax.Array, eps2: float):
    """(d2, mask) with mask = d2 ≤ eps²."""
    d2 = pairwise_l2(a, b)
    return d2, d2 <= eps2


def bucket_assign(x: jax.Array, centers: jax.Array):
    """Nearest center: (M, d) × (B, d) → (min_d2 (M,), argmin (M,) int32)."""
    d2 = pairwise_l2(x, centers)
    idx = jnp.argmin(d2, axis=1).astype(jnp.int32)
    mind2 = jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]
    return mind2, idx


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, scale: float | None = None) -> jax.Array:
    """Reference attention. q,k,v: (B, H, S, D) (k/v may have fewer heads —
    GQA handled by caller). Returns (B, H, S, D)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        s, t = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)
