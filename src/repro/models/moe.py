"""Mixture-of-Experts with CMP-style capacity-slot dispatch.

Dispatch is the gather/scatter formulation (sort-by-expert + positional slot
assignment) rather than a [T, E, C] one-hot einsum: the one-hot materializes
tokens x experts x capacity and is infeasible at 1M-token global batches; the
gather form keeps memory at O(E x C x D) and lowers to all-to-all style
collectives under expert sharding.

CMP correspondence (DESIGN.md §4): expert capacity slots are a cyclic slot
pool — tokens claim slots in *token order* (earliest-claim FIFO property),
overflow tokens are dropped deterministically (bounded capacity = protection
window), and slots are implicitly reclaimed every step (window = 1 step).
``assign_slots`` is the deterministic analogue of the paper's claim CAS and is
also exercised against :mod:`repro.core.slotpool` in tests.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.domain import window_admit
from repro.models.layers import swiglu


def assign_slots(expert_ids: jax.Array, num_experts: int, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """FIFO capacity-slot assignment.

    expert_ids: [A] int32 (A = tokens*k, flattened claim requests in token order).
    Returns (slot [A] int32 in [0, E*C) or E*C for dropped, keep [A] bool).
    Token order is claim order: the j-th request for expert e gets slot (e, j);
    requests beyond capacity are dropped (earliest-claim wins, as in the
    paper's AVAILABLE->CLAIMED transition).
    """
    e = num_experts
    a = expert_ids.shape[0]
    # Stable sort keeps token order within each expert => earliest-claim FIFO.
    order = jnp.argsort(expert_ids, stable=True)
    sorted_e = expert_ids[order]
    cnt = jnp.bincount(expert_ids, length=e)
    starts = jnp.cumsum(cnt) - cnt  # exclusive prefix
    pos_sorted = jnp.arange(a, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    pos = jnp.zeros((a,), jnp.int32).at[order].set(pos_sorted)
    # Bounded capacity IS the protection window (domain.window_admit): the
    # j-th claim on an expert is admitted iff j < C, exactly as a slot whose
    # position fell outside the window is not.
    keep = window_admit(pos, capacity)
    slot = jnp.where(keep, expert_ids * capacity + pos, e * capacity)
    return slot.astype(jnp.int32), keep


def moe_block(
    x: jax.Array,  # [B, S, D]
    p: dict,       # router [D, E]; wg/wu [E, D, F]; wd [E, F, D]
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    min_capacity: int = 8,
    act: str = "silu",
    groups: int = 1,
) -> jax.Array:
    B, S, D = x.shape
    if groups > 1 and B % groups == 0:
        # Group-local dispatch (§Perf): sort/gather/scatter stay within a
        # token group, so under batch sharding they never cross shards —
        # the all-concat gathers of global dispatch disappear. Capacity is
        # per-group (slightly higher drop variance, standard trade).
        xg = x.reshape(groups, B // groups, S, D)
        yg, aux = jax.vmap(
            lambda xx: moe_block(xx, p, num_experts=num_experts, top_k=top_k,
                                 capacity_factor=capacity_factor,
                                 min_capacity=min_capacity, act=act, groups=1)
        )(xg)
        return yg.reshape(B, S, D), jnp.mean(aux)
    T = B * S
    E, k = num_experts, top_k
    xt = x.reshape(T, D)

    # --- routing ---
    logits = (xt @ p["router"]).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)  # [T, k]
    gates = gates / jnp.clip(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    # --- slot claim (CMP earliest-claim) ---
    # Capacity floor keeps tiny decode batches dropless; cap at T*k (dropless
    # upper bound) keeps small-model shapes tight.
    capacity = min(T * k, max(min_capacity, int(T * k * capacity_factor / E)))
    flat_ids = ids.reshape(-1)  # [T*k], token-major = claim order
    slot, keep = assign_slots(flat_ids, E, capacity)

    # --- dispatch: gather token rows into [E*C, D] expert buffers ---
    flat_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    token_for_slot = jnp.full((E * capacity,), T, dtype=jnp.int32)
    token_for_slot = token_for_slot.at[slot].set(flat_token, mode="drop")
    x_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    xin = x_pad[token_for_slot].reshape(E, capacity, D)

    # --- expert MLPs (grouped over E; shards over the expert/model axis) ---
    g = jnp.einsum("ecd,edf->ecf", xin, p["wg"])
    u = jnp.einsum("ecd,edf->ecf", xin, p["wu"])
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    out_ec = jnp.einsum("ecf,efd->ecd", a * u, p["wd"])  # [E, C, D]

    # --- combine: gather each request's slot output, weight, scatter-add ---
    out_pad = jnp.concatenate(
        [out_ec.reshape(E * capacity, D), jnp.zeros((1, D), out_ec.dtype)], axis=0
    )
    per_req = out_pad[slot]  # [T*k, D] (dropped -> zeros row)
    per_req = per_req * gates.reshape(-1)[:, None].astype(per_req.dtype)
    y = jnp.zeros((T, D), per_req.dtype).at[flat_token].add(per_req)

    # --- aux: load-balancing loss term (Switch-style) ---
    me = jnp.mean(probs, axis=0)  # [E]
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    return y.reshape(B, S, D).astype(x.dtype), aux


def held_moe_block(
    x: jax.Array,  # [B, S, D]
    p: dict,       # router [D, E]; wg/wu [G, D, F]; wd [G, F, D]; shared?
    *,
    num_experts: int,
    top_k: int,
    expert_offset: int = 0,
    norm_topk_prob: bool = True,
    act: str = "silu",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless expert layer of one expert-parallel rank: the router scores
    all ``num_experts`` (E) experts in float32, softmax over all of them,
    and each token keeps its top ``top_k``; this rank holds the G experts
    ``expert_offset .. expert_offset + G - 1`` and computes every claim on
    them, grouped by expert (``jax.lax.ragged_dot``: each held expert's
    weights multiply only the rows routed to it). A claim on an expert held
    elsewhere adds nothing here. The gates are the raw probabilities
    unless ``norm_topk_prob``. A shared expert (``p["shared"]``), if any,
    runs on every token.

    Returns (y [B, S, D], aux load-balancing term, claims on held experts).
    """
    B, S, D = x.shape
    T, k = B * S, top_k
    G = p["wg"].shape[0]
    xt = x.reshape(T, D)

    logits = jnp.matmul(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)     # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                         # [T, k]
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    local = ids - expert_offset
    held = (local >= 0) & (local < G)
    # held claims sorted by expert (token order within one), the rest last
    group = jnp.where(held, local, G).reshape(-1)                # [T*k]
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=G + 1)[:G].astype(jnp.int32)
    xs = xt[order // k]                                          # [T*k, D]
    g = jax.lax.ragged_dot(xs, p["wg"], sizes)
    u = jax.lax.ragged_dot(xs, p["wu"], sizes)
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    out = jax.lax.ragged_dot(a * u, p["wd"], sizes)               # [T*k, D]
    n_held = jnp.sum(sizes)
    # rows past the held claims belong to no group: zero them explicitly
    out = jnp.where((jnp.arange(T * k) < n_held)[:, None], out, 0)
    per_claim = out[jnp.argsort(order)].reshape(T, k, D)
    w = jnp.where(held, gates, 0.0).astype(per_claim.dtype)
    y = jnp.einsum("tkd,tk->td", per_claim, w)
    if "shared" in p:
        y = y + swiglu(xt, p["shared"], act)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], num_experts, dtype=jnp.float32), axis=0)
    aux = num_experts * jnp.sum(me * ce)
    return y.reshape(B, S, D).astype(x.dtype), aux, n_held
