"""Explicit all-to-all MoE dispatch/combine (shard_map).

§Perf iteration 9 showed GSPMD cannot be coaxed out of the model-axis
all-reduce of dense (T_local, D) partials that dominates the MoE train
cells (~3.4 TB/device on llama4 train_4k).  This module is the structural
fix: tokens are sharded over the model axis too (the sequence dim), and
dispatch/combine are `jax.lax.all_to_all` exchanges whose payload is
1/|model| of the all-reduce's — the real-system MoE wiring (Switch/GShard)
expressed with jax-native collectives.

Layout (inside shard_map over {batch axes b, model axis m}):
  x        (B, L, D)   P(b, m, None)   — L sharded over m: T_loc tokens
  router   (D, E)      replicated
  experts  (E, D, F)   P(m, None, None) — E_loc experts per m-shard
Per device: route locally -> bucket (token, k) pairs by target expert
shard (fixed per-target capacity, drops over it) -> all_to_all tokens to
expert owners -> per-expert FFN (inverse-permutation gather, same
machinery as models.moe) -> all_to_all results back -> weighted combine
(reshape-sum).  all_to_all is differentiable, so the backward pass is the
mirrored exchange automatically.

Numerics match ``models.moe.moe_block`` up to capacity-drop differences
(capacity here is per (source shard, target shard), there per expert) —
the equivalence test uses generous capacity so no drops occur on either
side (tests/test_moe_a2a.py, 8 fake devices).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _inv_permute(slot: jax.Array, n_slots: int, n_src: int) -> jax.Array:
    """slot (n_src,) -> inv (n_slots,) with inv[slot[i]] = i; n_src marks
    empty slots.  (The 1-D int scatter from models.moe.)"""
    return jnp.full((n_slots,), n_src, jnp.int32).at[slot].set(
        jnp.arange(n_src, dtype=jnp.int32), mode="drop")


def moe_ffn_a2a(p: dict, xt: jax.Array, *, n_experts: int, top_k: int,
                axis: str, capacity_factor: float = 1.5
                ) -> Tuple[jax.Array, jax.Array]:
    """Per-device body (call inside shard_map).  xt: (T_loc, D); p holds
    ``router`` (D, E) replicated and ``w_gate/w_in/w_out`` local expert
    slices (E_loc, D, F)/(E_loc, F, D).  Returns (out (T_loc, D), aux)."""
    T, D = xt.shape
    E, K = n_experts, top_k
    m = jax.lax.axis_size(axis)
    E_loc = E // m

    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, K)                      # (T, K)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (global mean via psum)
    f = jnp.zeros(E).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(jax.lax.pmean(f, axis)
                      * jax.lax.pmean(probs.mean(0), axis))

    flat_e = jax.lax.stop_gradient(idx.reshape(-1))       # (TK,)
    flat_w = w.reshape(-1).astype(xt.dtype)
    TK = T * K
    target = flat_e // E_loc                              # dest m-shard
    e_local = flat_e % E_loc

    # --- bucket by target shard (fixed per-target capacity Cs) ----------
    Cs = int(math.ceil(TK / m * capacity_factor))
    onehot_t = jax.nn.one_hot(target, m, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot_t, axis=0) - onehot_t)
    pos_t = jnp.take_along_axis(pos, target[:, None], 1)[:, 0]
    keep = pos_t < Cs
    slot = jnp.where(keep, target * Cs + pos_t, m * Cs)   # m*Cs = dropped

    inv = _inv_permute(slot, m * Cs, TK)                  # slot -> (t,k)
    src_tok = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], 0)
    tok_idx = jnp.minimum(inv // K, T)                    # token row (T=pad)
    send_tok = jnp.take(src_tok, jnp.where(inv < TK, tok_idx, T), axis=0)
    send_e = jnp.where(inv < TK, jnp.take(e_local, jnp.minimum(inv, TK - 1)),
                       E_loc)                             # E_loc = invalid
    send_tok = send_tok.reshape(m, Cs, D)
    send_e = send_e.reshape(m, Cs).astype(jnp.int32)

    # --- exchange: every shard ships its buckets to the expert owners ---
    recv_tok = jax.lax.all_to_all(send_tok, axis, split_axis=0,
                                  concat_axis=0, tiled=True)  # (m*Cs? , D)
    recv_e = jax.lax.all_to_all(send_e, axis, split_axis=0, concat_axis=0,
                                tiled=True).reshape(-1)       # (m*Cs,)
    recv_tok = recv_tok.reshape(m * Cs, D)

    # --- local per-expert FFN (inverse-permutation gather) --------------
    R = m * Cs
    Ce = int(math.ceil(R / E_loc * capacity_factor))
    valid = recv_e < E_loc
    onehot_e = jax.nn.one_hot(jnp.where(valid, recv_e, E_loc), E_loc + 1,
                              dtype=jnp.int32)[:, :E_loc]
    pos_e = (jnp.cumsum(onehot_e, axis=0) - onehot_e)
    pos_r = jnp.take_along_axis(pos_e, jnp.minimum(recv_e, E_loc - 1)[:, None],
                                1)[:, 0]
    keep_r = valid & (pos_r < Ce)
    slot_r = jnp.where(keep_r, recv_e * Ce + pos_r, E_loc * Ce)
    inv_r = _inv_permute(slot_r, E_loc * Ce, R)
    buf = jnp.take(jnp.concatenate([recv_tok, jnp.zeros((1, D),
                                                        recv_tok.dtype)], 0),
                   jnp.minimum(inv_r, R), axis=0).reshape(E_loc, Ce, D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    y = jnp.einsum("ecf,efd->ecd", h.astype(xt.dtype), p["w_out"])
    y = y.reshape(E_loc * Ce, D)

    # back to recv layout, then return exchange
    y_recv = jnp.where(keep_r[:, None],
                       jnp.take(y, jnp.minimum(slot_r, E_loc * Ce - 1),
                                axis=0), 0.0)
    back = jax.lax.all_to_all(y_recv.reshape(m, Cs, D), axis, split_axis=0,
                              concat_axis=0, tiled=True).reshape(m * Cs, D)

    # --- combine at the source: weight and reshape-sum over k -----------
    safe = jnp.minimum(slot, m * Cs - 1)
    contrib = jnp.where(keep[:, None],
                        flat_w[:, None] * jnp.take(back, safe, axis=0), 0.0)
    out = contrib.reshape(T, K, D).sum(axis=1).astype(xt.dtype)
    return out, aux


def moe_block_a2a(p: dict, x: jax.Array, mesh: Mesh, *, n_experts: int,
                  top_k: int, batch_axes=("data",), model_axis: str = "model",
                  capacity_factor: float = 1.5):
    """shard_map wrapper: x (B, L, D) sharded (batch_axes, model_axis);
    expert weights sharded on the expert dim; router replicated."""
    b = tuple(batch_axes)

    def body(router, wg, wi, wo, xs):
        B, Ll, D = xs.shape
        out, aux = moe_ffn_a2a(
            {"router": router, "w_gate": wg, "w_in": wi, "w_out": wo},
            xs.reshape(B * Ll, D), n_experts=n_experts, top_k=top_k,
            axis=model_axis, capacity_factor=capacity_factor)
        return out.reshape(B, Ll, D), jax.lax.pmean(
            jax.lax.pmean(aux, model_axis), b[0]) if b else aux

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(b, model_axis, None)),
        out_specs=(P(b, model_axis, None), P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_in"], p["w_out"], x)
