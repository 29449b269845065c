"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

The reference has no sequence parallelism (SURVEY.md §5.7 — bucketing
only); this is a required TPU-native capability. Design: blockwise
attention with online softmax (the flash-attention recurrence), where K/V
blocks rotate around the ring of 'sp' devices via ``lax.ppermute`` so each
device sees every KV block while holding only its local Q shard —
attention over sequences N× longer than one device's HBM.

Public papers: Ring Attention (Liu et al. 2023), blockwise parallel
attention; implemented here from the recurrence, shard_map-style.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "sequence_shard"]


def sequence_shard(x, mesh: Mesh, axis_name: str = "sp", seq_dim: int = 2):
    """Place (B, H, T, D) with T sharded over the sp axis."""
    spec = [None] * x.ndim
    spec[seq_dim] = axis_name
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def _online_block(q, k, v, o, m, l, mask=None, scale=1.0):
    """One flash-attention block update: returns (o, m, l) accumulators.
    q:(B,H,Tq,D) k,v:(B,H,Tk,D) o:(B,H,Tq,D) m,l:(B,H,Tq)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (m_new == -inf): exp(-inf - -inf) → nan
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o_new, m_new, l_new


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = False, scale=None):
    """Attention over sequence-sharded q/k/v: (B, H, T_global, D) arrays
    whose T dim is sharded on ``axis_name``. Returns same-sharded output.

    Each ring step computes one local Q×KV block with the online-softmax
    recurrence, then rotates K/V to the next device over ICI (ppermute),
    overlapping compute with the collective (XLA latency-hiding
    scheduler)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    n = mesh.shape[axis_name]

    def local(qb, kb, vb):
        idx = lax.axis_index(axis_name)
        tq = qb.shape[2]
        tk = kb.shape[2]
        o = jnp.zeros(qb.shape[:3] + (vb.shape[-1],), jnp.float32)
        m = jnp.full(qb.shape[:3], -jnp.inf, jnp.float32)
        l = jnp.zeros(qb.shape[:3], jnp.float32)
        # accumulators are device-varying (each sp-rank's differ): annotate
        # so the fori_loop carry type is stable under vma checking
        o, m, l = (lax.pcast(a, axis_name, to="varying")
                   for a in (o, m, l))
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(step, carry):
            kb, vb, o, m, l = carry
            # kv block currently held originated at device (idx - step) % n
            src = (idx - step) % n
            if causal:
                q_pos = idx * tq + jnp.arange(tq)[:, None]
                k_pos = src * tk + jnp.arange(tk)[None, :]
                mask = (q_pos >= k_pos)[None, None]
            else:
                mask = None
            o, m, l = _online_block(qb.astype(jnp.float32),
                                    kb.astype(jnp.float32), vb, o, m, l,
                                    mask=mask, scale=scale)
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
            return kb, vb, o, m, l

        kb2, vb2, o, m, l = lax.fori_loop(0, n, body, (kb, vb, o, m, l))
        out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
        return out.astype(q.dtype)

    spec = P(None, None, axis_name, None)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)
