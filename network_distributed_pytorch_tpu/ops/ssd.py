"""Mamba-2's selective state-space scan (SSD, Dao & Gu 2024) and the two
small ops that frame it in a Mamba-2 mixer: the causal depthwise conv and
the grouped, gated RMSNorm.

The recurrence, per head with state ``S`` of shape (P, N)::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t B_t^T        y_t = S_t C_t + d * x_t

``ssd_scan`` computes it in chunks of ``chunk`` steps (the SSD form): inside
a chunk the outputs are one masked (chunk, chunk) product, ``(C B^T ∘ decay)
· (dt x)``; between chunks a (P, N) state is carried, one multiply-add per
chunk in a ``lax.scan``. Every product is a batched matmul with fp32
accumulation and operands in ``x``'s dtype; ``dt``, the cumulative decay
and every ``exp`` are fp32 (a decay rounded to bf16 compounds over a
sequence). It is plain ``jax.numpy``/``lax`` and differentiates as such;
the recurrence as written is ``benchmark/reference/nemotron_h.py``'s.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv1d(x: jax.Array, kernel: jax.Array, bias: Optional[jax.Array]) -> jax.Array:
    """Depthwise causal convolution over time: ``y_t = sum_j kernel[j] *
    x_{t-K+1+j} + bias``, zeros before the sequence; ``x`` (B, T, C),
    ``kernel`` (K, C) for any K, ``bias`` (C,) or None for a convolution
    without one. Three mixers call it: Mamba-2's (K 4, a bias) and Gated
    DeltaNet's (K 4, none) wrap it in a silu; LFM2's short convolution (K 3,
    none) takes it as it is, between its two gates. K shifted multiply-adds
    in ``x``'s dtype: no convolution op, no (T, K) window."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(x.dtype)
    for j in range(k):
        tap = padded[:, j:j + t] * kernel[j].astype(x.dtype)
        out = tap if out is None else out + tap
    return out


def gated_group_rms_norm(
    y: jax.Array, gate: jax.Array, scale: jax.Array, groups: int, eps: float
) -> jax.Array:
    """``RMSNorm_grouped(y * silu(gate)) * scale``: the gate before the norm,
    the mean square taken over each of ``groups`` slices of the last axis
    (Mamba-2's ``MambaRMSNormGated`` with ``norm_before_gate=False``)."""
    h = (y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32)))
    grouped = h.reshape(h.shape[:-1] + (groups, h.shape[-1] // groups))
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * lax.rsqrt(var + eps)).reshape(h.shape)
    return (normed * scale.astype(jnp.float32)).astype(y.dtype)


def ssd_scan(
    x: jax.Array,   # (B, T, H, P) inputs per head
    dt: jax.Array,  # (B, T, H) step sizes, already softplus'd (> 0)
    a: jax.Array,   # (H,) negative decay rates, -exp(A_log)
    b: jax.Array,   # (B, T, G, N) input projections, one per group of H // G heads
    c: jax.Array,   # (B, T, G, N) output projections
    d: jax.Array,   # (H,) skip weights
    chunk: int,
) -> jax.Array:
    """The SSD scan: ``y`` (B, T, H, P) in ``x``'s dtype. Head ``h`` reads
    group ``h // (H // G)``. ``T`` need not divide by ``chunk``: the tail is
    padded with ``dt = 0`` steps, which neither decay nor feed the state."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    dtype, f32 = x.dtype, jnp.float32
    pad = -t % chunk
    if pad:
        grow = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    nc = (t + pad) // chunk

    # heads (or groups) ahead of time: every product below is batched over them
    xh = x.reshape(bsz, nc, chunk, h, p).transpose(0, 1, 3, 2, 4)      # (B, nc, H, L, P)
    dth = dt.astype(f32).reshape(bsz, nc, chunk, h).transpose(0, 1, 3, 2)  # (B, nc, H, L)
    bg = b.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)      # (B, nc, G, L, N)
    cg = c.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cs = jnp.cumsum(dth * a.astype(f32)[:, None], axis=-1)  # log-decay from the chunk's start, <= 0
    xdt = (xh.astype(f32) * dth[..., None]).astype(dtype)

    # inside a chunk: y_l += sum_{s<=l} (C_l.B_s) exp(cs_l - cs_s) dt_s x_s
    cb = jnp.einsum("bcgln,bcgsn->bcgls", cg, bg, preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cs[..., :, None] - cs[..., None, :], -jnp.inf))
    mixed = decay.reshape(bsz, nc, g, r, chunk, chunk) * cb[:, :, :, None]
    y = jnp.einsum(
        "bchls,bchsp->bchlp", mixed.reshape(bsz, nc, h, chunk, chunk).astype(dtype), xdt,
        preferred_element_type=f32,
    )

    # what each chunk adds to the state by its end, and the chunk's whole decay
    to_end = (xdt.astype(f32) * jnp.exp(cs[..., -1:] - cs)[..., None]).astype(dtype)
    added = jnp.einsum(
        "bcgrlp,bcgln->bcgrpn", to_end.reshape(bsz, nc, g, r, chunk, p), bg,
        preferred_element_type=f32,
    )  # (B, nc, G, R, P, N)
    whole = jnp.exp(cs[..., -1]).reshape(bsz, nc, g, r)

    def carry_state(state, chunk_in):
        added_c, whole_c = chunk_in
        return state * whole_c[..., None, None] + added_c, state  # emit the state BEFORE the chunk

    _, before = lax.scan(
        carry_state, jnp.zeros_like(added[:, 0]),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # (B, nc, G, R, P, N)

    # across chunks: y_l += exp(cs_l) C_l . S_before
    carried = jnp.einsum(
        "bcgln,bcgrpn->bcgrlp", cg, before.astype(dtype), preferred_element_type=f32
    ).reshape(bsz, nc, h, chunk, p)
    y = y + carried * jnp.exp(cs)[..., None] + xh.astype(f32) * d.astype(f32)[:, None, None]
    y = y.transpose(0, 1, 3, 2, 4).reshape(bsz, nc * chunk, h, p)
    return y[:, :t].astype(dtype)
