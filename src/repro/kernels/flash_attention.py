"""Pallas TPU kernel: causal GQA flash attention (forward).

The assigned LM architectures are dominated by attention at the 32k-prefill
and 500k-decode shapes, so this is the framework's model-side compute hot
spot.  Blocked online-softmax in the canonical TPU form:

  grid = (batch*q_heads, q_blocks, kv_blocks)   kv innermost ("arbitrary")
  q block (1, bq, D) and out block revisit the same VMEM tile across the kv
  loop; running (max, sum, acc) live in VMEM scratch; init at kv==0, final
  normalization at kv==last.  Causal blocks strictly above the diagonal are
  predicated off with pl.when (TPU skips the MXU work, the paper-style
  "don't touch what you don't need" discipline applied to compute).

GQA is handled in the index maps: query head h reads kv head h // group —
no jnp.repeat materialization (the XLA reference pays that gather; the
kernel reads the shared KV block straight from VMEM).

Numerics follow the standard flash recipe in f32 accumulation; tests sweep
(Sq, Skv, heads, D, dtype) and assert allclose vs ref.flash_attention_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, bq: int, bk: int, sq: int, skv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal frontier: queries are the LAST sq positions of the skv context
    offset = skv - sq
    block_needed = True
    if causal:
        block_needed = ki * bk <= qi * bq + (bq - 1) + offset

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # [bq, D]
        k = k_ref[0].astype(jnp.float32)                      # [bk, D]
        v = v_ref[0].astype(jnp.float32)                      # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                             # [bq, bk]
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]                                   # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)            # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # rows with everything masked stay at -inf; exp guard keeps them 0
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(m_new == NEG_INF, 0.0, jnp.exp(s - m_new))  # [bq, bk]
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        norm = jnp.where(l > 0.0, 1.0 / l, 0.0)
        o_ref[0] = (acc_ref[...] * norm).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [B, Hq, Sq, D]
    k: jnp.ndarray,  # [B, Hkv, Skv, D]
    v: jnp.ndarray,  # [B, Hkv, Skv, D]
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)

    def kv_index(bh, qi, ki):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // group, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, sq=Sq, skv=Skv
        ),
        grid=(B * Hq, Sq // bq, Skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, D)
