"""Pure-jnp oracles for the runahead gather kernels."""
from __future__ import annotations

import jax.numpy as jnp


def gather_ref(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """out[i] = table[idx[i]] — the irregular row gather of Listing 1."""
    return jnp.take(table, idx, axis=0)


def gather_bag_ref(table: jnp.ndarray, idx: jnp.ndarray,
                   weights: jnp.ndarray) -> jnp.ndarray:
    """Padded-CSR aggregation: out[s] = sum_k w[s,k] * table[idx[s,k]]
    (GCN ``aggregate`` / embedding-bag).  idx: [S,K]; weights: [S,K],
    rounded to the table dtype; the sum is taken in float32."""
    rows = jnp.take(table, idx, axis=0)              # [S, K, D]
    out = jnp.einsum("sk,skd->sd", weights.astype(rows.dtype), rows,
                     preferred_element_type=jnp.float32)
    return out.astype(table.dtype)
