"""Mixture-of-Experts layer: top-k router + shared experts + EP dispatch.

The EP dispatch path is the paper's machinery verbatim: tokens are records,
experts' owners are destination shards, and the exchange is the same
fixed-capacity bucketed all_to_all (distributed/collectives.py) that powers
the graph redistribute step.  One primitive, two workloads — the sense in
which the paper's k:1 scatter-gather is a first-class framework feature.

Two dispatch modes (DistContext.moe_dispatch):

  "dense"     no EP: every device computes every expert on a capacity-
              gathered token block.  Exact for smoke tests / single device;
              compute scales with num_experts, so only for small configs.

  "alltoall"  expert parallelism over the "model" mesh axis.  Inside
              shard_map, the sequence dim is sharded over "model" (each
              model-rank owns distinct tokens), tokens are bucketed by the
              owner of their routed expert and exchanged (capacity
              all_to_all), each rank runs its local experts as batched
              einsums, results return and combine with router weights.
              Top-k assignments are uniform-ish after routing, the same
              load regime as post-relabel redistribute; capacity_factor
              absorbs the skew, drops are surfaced in aux stats.

Aux outputs: load-balance loss (Switch-style), router z-loss, drop count.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.collectives import capacity_all_to_all, return_all_to_all
from .nn import DistContext, ParamFactory, shard


def init_moe(f: ParamFactory, path: str, cfg, lead=()):
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    la = ("layers",) * len(lead)
    p = {
        "router": f.param(f"{path}/router", (*lead, d, E), (*la, "embed", None)),
        "w_gate": f.param(f"{path}/w_gate", (*lead, E, d, ff), (*la, "experts", "embed", None)),
        "w_up": f.param(f"{path}/w_up", (*lead, E, d, ff), (*la, "experts", "embed", None)),
        "w_down": f.param(f"{path}/w_down", (*lead, E, ff, d), (*la, "experts", None, "embed")),
    }
    if cfg.num_shared_experts:
        sff = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": f.param(f"{path}/shared/w_gate", (*lead, d, sff), (*la, "embed", "ff")),
            "w_up": f.param(f"{path}/shared/w_up", (*lead, d, sff), (*la, "embed", "ff")),
            "w_down": f.param(f"{path}/shared/w_down", (*lead, sff, d), (*la, "ff", "embed")),
        }
    return p


def _route(p, cfg, x_tokens: jnp.ndarray):
    """x_tokens [T, d] -> (weights [T,k], experts [T,k], aux dict)."""
    logits = (x_tokens @ p["router"]).astype(jnp.float32)     # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.experts_per_tok)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux + router z-loss
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)                              # mean prob per expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32), axis=1), axis=0
    )                                                         # mean assignment per expert
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return weights.astype(x_tokens.dtype), experts, {"lb_loss": lb_loss, "z_loss": z_loss}


def _expert_ffn(w_gate, w_up, w_down, x):
    """Batched per-expert SwiGLU: x [E, C, d] with stacked weights [E, ...]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", x, w_up
    )
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_dense(p, cfg, x_tokens, weights, experts):
    """Every-device-every-expert reference: capacity-gather tokens per expert.

    capacity = ceil(T*k/E)*4 keeps smoke-scale drops at zero; the dense path
    exists for correctness, not perf.
    """
    T = x_tokens.shape[0]
    E, k = cfg.num_experts, cfg.experts_per_tok
    cap = max(8, (T * k * 4) // E)
    flat_expert = experts.reshape(-1)                        # [T*k]
    flat_tok = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_expert, stable=True)
    se, st = flat_expert[order], flat_tok[order]
    start = jnp.searchsorted(se, jnp.arange(E))
    rank = jnp.arange(T * k) - start[se]
    slot = jnp.where(rank < cap, se * cap + rank, E * cap)
    gather_tok = jnp.zeros((E * cap + 1,), jnp.int32).at[slot].set(st.astype(jnp.int32), mode="drop")
    valid = jnp.zeros((E * cap + 1,), jnp.bool_).at[slot].set(True, mode="drop")
    gx = x_tokens[gather_tok[:-1]].reshape(E, cap, -1)       # [E, C, d]
    gx = gx * valid[:-1].reshape(E, cap, 1).astype(gx.dtype)
    out = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], gx).reshape(E * cap, -1)
    # scatter-combine with router weights
    flat_w = weights.reshape(-1)[order]
    src_pos = jnp.where(rank < cap, slot, E * cap)
    contrib = out[jnp.clip(src_pos, 0, E * cap - 1)] * flat_w[:, None]
    contrib = jnp.where((rank < cap)[:, None], contrib, 0)
    y = jnp.zeros_like(x_tokens).at[st].add(contrib.astype(x_tokens.dtype))
    dropped = jnp.sum(rank >= cap)
    return y, dropped


def _bucket_local(recv, local_e, e_local: int, cap2: int):
    """Stable-bucket received tokens by local expert -> [e_local, cap2, d]."""
    order = jnp.argsort(local_e, stable=True)
    se = local_e[order]
    start = jnp.searchsorted(se, jnp.arange(e_local, dtype=se.dtype))
    rank = jnp.arange(se.shape[0], dtype=jnp.int32) - start[jnp.clip(se, 0, e_local - 1)]
    ok = (se < e_local) & (rank < cap2)
    slot = jnp.where(ok, se * cap2 + rank, e_local * cap2)
    gx = jnp.zeros((e_local * cap2 + 1, recv.shape[-1]), recv.dtype).at[slot].set(
        recv[order], mode="drop"
    )
    return gx[:-1].reshape(e_local, cap2, -1), order, slot, ok


def _moe_alltoall(p_local, cfg, x_tokens, weights, experts, axis: str, ep: int, capacity: int):
    """EP dispatch inside shard_map.  x_tokens [T_loc, d] distinct per rank;
    p_local holds this rank's expert slab [E_local, ...].

    The routed expert id rides along as an extra payload column (f32 holds
    small ints exactly) so dispatch is ONE exchange, not two.

    cfg.moe_dispatch_int8: ship the token activations as int8 with one f32
    scale per row (DeepSeek-V3-style quantized dispatch) — ~2x less a2a
    traffic than bf16 at <0.8% relative activation error (tested), applied
    on BOTH directions of the exchange.  This is payload compression of the
    paper's k:1 scatter-gather records.
    """
    T, d = x_tokens.shape
    E, k = cfg.num_experts, cfg.experts_per_tok
    e_local = E // ep
    flat_expert = experts.reshape(-1).astype(jnp.int32)       # [T*k]
    xk = jnp.repeat(x_tokens, k, axis=0)                      # [T*k, d]

    def q8(rows):
        amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(rows / scale), -127, 127).astype(jnp.int8), scale

    if cfg.moe_dispatch_int8:
        q, scale = q8(xk)
        # int8 tokens ride in one exchange; (scale, expert) in a narrow f32 one
        ex = capacity_all_to_all(q, flat_expert // e_local, axis=axis, capacity=capacity)
        side = jnp.concatenate(
            [scale, (flat_expert % e_local).astype(jnp.float32)[:, None]], axis=-1)
        ex_side = capacity_all_to_all(side, flat_expert // e_local, axis=axis, capacity=capacity)
        recv_tok = (ex.data.reshape(-1, d).astype(jnp.float32)
                    * ex_side.data.reshape(-1, 2)[:, :1]).astype(x_tokens.dtype)
        recv_e = ex_side.data.reshape(-1, 2)[:, 1]
        recv_valid = ex.valid.reshape(-1)
    else:
        payload = jnp.concatenate(
            [xk, (flat_expert % e_local).astype(x_tokens.dtype)[:, None]], axis=-1)
        ex = capacity_all_to_all(payload, flat_expert // e_local, axis=axis, capacity=capacity)
        recv = ex.data.reshape(-1, d + 1)                     # [ep*cap, d+1]
        recv_tok, recv_e = recv[:, :d], recv[:, -1]
        recv_valid = ex.valid.reshape(-1)

    local_e = jnp.where(recv_valid, recv_e.astype(jnp.int32), e_local)
    cap2 = max(8, int(recv_tok.shape[0] * 2 // max(e_local, 1)))
    gx, order, slot, ok = _bucket_local(recv_tok, local_e, e_local, cap2)
    out = _expert_ffn(p_local["w_gate"], p_local["w_up"], p_local["w_down"], gx)
    out_flat = out.reshape(e_local * cap2, d)
    # un-bucket back to received-slot order
    res = jnp.zeros((recv_tok.shape[0], d), x_tokens.dtype).at[order].set(
        jnp.where(ok[:, None], out_flat[jnp.clip(slot, 0, e_local * cap2 - 1)], 0)
    )
    if cfg.moe_dispatch_int8:
        rq, rscale = q8(res)
        back_q = return_all_to_all(
            rq.reshape(ex.data.shape[0], ex.data.shape[1], d), ex.position, axis=axis)
        back_s = return_all_to_all(
            rscale.reshape(ex.data.shape[0], ex.data.shape[1], 1), ex.position, axis=axis)
        back = back_q.astype(jnp.float32) * back_s
    else:
        back = return_all_to_all(
            res.reshape(ex.data.shape[0], ex.data.shape[1], d), ex.position, axis=axis)
    y = jnp.sum(back.reshape(T, k, d).astype(jnp.float32)
                * weights[..., None].astype(jnp.float32), axis=1)
    return y.astype(x_tokens.dtype), ex.dropped


def _moe_gather_ep(p_local, cfg, x_tokens, weights, experts, axis: str, ep: int):
    """Gather-style EP for small token counts (decode): tokens are
    REPLICATED over the model axis; each rank computes only the assignments
    routed to its local experts and the partial outputs psum over the axis.
    Communication = one psum of [T, d] — cheaper than all_to_all when T is
    a decode batch."""
    T, d = x_tokens.shape
    E, k = cfg.num_experts, cfg.experts_per_tok
    e_local = E // ep
    r = jax.lax.axis_index(axis)
    flat_expert = experts.reshape(-1).astype(jnp.int32)
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    mine = (flat_expert // e_local) == r
    local_e = jnp.where(mine, flat_expert % e_local, e_local)
    cap = max(8, int(2 * T * k // ep))
    payload = jnp.concatenate(
        [x_tokens[flat_tok], flat_tok.astype(x_tokens.dtype)[:, None]], axis=-1
    )
    gx, order, slot, ok = _bucket_local(payload, local_e, e_local, cap)
    out = _expert_ffn(p_local["w_gate"], p_local["w_up"], p_local["w_down"], gx[..., :d])
    tok_ids = gx[..., d].astype(jnp.int32).reshape(-1)
    out_flat = out.reshape(e_local * cap, d)
    gw = weights.reshape(-1)[order]
    valid_slots = jnp.zeros((e_local * cap + 1,), jnp.bool_).at[slot].set(ok, mode="drop")[:-1]
    contrib = jnp.where(valid_slots[:, None], out_flat, 0)
    # weight each slot by its router weight (scatter weights into slots)
    wslots = jnp.zeros((e_local * cap + 1,), weights.dtype).at[slot].set(
        jnp.where(ok, gw, 0), mode="drop"
    )[:-1]
    y_partial = jnp.zeros((T, d), x_tokens.dtype).at[tok_ids].add(
        (contrib * wslots[:, None]).astype(x_tokens.dtype), mode="drop"
    )
    dropped = jax.lax.psum(jnp.sum(mine & ~_in_capacity(local_e, e_local, cap)), axis)
    return jax.lax.psum(y_partial, axis), dropped


def _in_capacity(local_e, e_local, cap):
    order = jnp.argsort(local_e, stable=True)
    se = local_e[order]
    start = jnp.searchsorted(se, jnp.arange(e_local, dtype=se.dtype))
    rank = jnp.arange(se.shape[0], dtype=jnp.int32) - start[jnp.clip(se, 0, e_local - 1)]
    ok_sorted = rank < cap
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    return ok_sorted[inv]


def moe_ffn(p, cfg, x: jnp.ndarray, dist: Optional[DistContext]) -> Tuple[jnp.ndarray, dict]:
    """Full MoE sublayer on [B, S, d].  Returns (y, aux)."""
    B, S, d = x.shape

    if dist is not None and dist.moe_dispatch == "alltoall":
        mesh = dist.mesh
        axis = "model"
        ep = mesh.shape[axis]
        assert cfg.num_experts % ep == 0, (cfg.num_experts, ep)
        dp_axes = tuple(a for a in mesh.axis_names if a != axis)
        use_a2a = S % ep == 0 and S >= ep  # decode (S=1): gather-EP instead
        aux_specs = {"lb_loss": P(), "z_loss": P(), "dropped": P()}

        if use_a2a:
            cap = int(
                cfg.moe_capacity_factor
                * (B // _axes_size(mesh, dp_axes)) * (S // ep) * cfg.experts_per_tok / ep
            ) + 8

            def per_shard(p_shard, xs):
                Bl, Sl, _ = xs.shape
                toks = xs.reshape(Bl * Sl, d)
                w, e, aux = _route(p_shard, cfg, toks)
                y, dropped = _moe_alltoall(p_shard, cfg, toks, w, e, axis, ep, cap)
                # reduce so P() out_specs replication is statically true:
                # lb/z vary over every axis (tokens sharded over data AND
                # model); dropped is already psum'd over `axis` inside the
                # exchange, so only the dp axes remain.
                aux = {**{k: jax.lax.pmean(v, tuple(mesh.axis_names))
                          for k, v in aux.items()},
                       "dropped": jax.lax.psum(dropped, dp_axes).astype(jnp.float32)}
                return y.reshape(Bl, Sl, d), aux

            x_spec = P(dp_axes, axis, None)
        else:

            def per_shard(p_shard, xs):
                Bl, Sl, _ = xs.shape
                toks = xs.reshape(Bl * Sl, d)
                w, e, aux = _route(p_shard, cfg, toks)
                y, dropped = _moe_gather_ep(p_shard, cfg, toks, w, e, axis, ep)
                # tokens are replicated over `axis` here: aux is invarying
                # over model already, dropped was psum'd over model inside
                aux = {**{k: jax.lax.pmean(v, dp_axes) for k, v in aux.items()},
                       "dropped": jax.lax.psum(dropped, dp_axes).astype(jnp.float32)}
                return y.reshape(Bl, Sl, d), aux

            x_spec = P(dp_axes, None, None)  # replicated over model axis

        specs_p = {
            "router": P(*(None,) * p["router"].ndim),
            "w_gate": _expert_spec(p["w_gate"], axis),
            "w_up": _expert_spec(p["w_up"], axis),
            "w_down": _expert_spec(p["w_down"], axis),
        }
        routed_p = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
        fn = jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(specs_p, x_spec),
            out_specs=(x_spec, aux_specs),
        )
        y, aux = fn(routed_p, x)
    else:
        toks = x.reshape(B * S, d)
        w, e, aux = _route(p, cfg, toks)
        y, dropped = _moe_dense(p, cfg, toks, w, e)
        aux = {**aux, "dropped": dropped}
        y = y.reshape(B, S, d)

    if "shared" in p:
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        y = y + h @ sp["w_down"]
    return y, aux


def _expert_spec(w, axis: str) -> P:
    return P(axis, *(None,) * (w.ndim - 1))


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
