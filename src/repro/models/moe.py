"""Mixture-of-Experts FFN with permutation-gather token dispatch and expert
parallelism.

Memory discipline (hard-won, see EXPERIMENTS.md §Dry-run):
  * the classic GShard (T, E, C) one-hot dispatch tensor is O(T*E*C) —
    hopeless at arctic scale (1M tokens, 128 experts);
  * a row-scatter `zeros(E*C, d).at[slot].set(x)` is O(T*d) in theory, but
    XLA's scatter partitioning materializes u32 index masks of the operand
    size (70 GiB/chip on arctic train_4k);
  * therefore: dispatch/combine are row GATHERS through a precomputed
    slot<->token permutation (1-D u32 scatters only), wrapped in a
    custom_vjp whose backward is a gather by the inverse permutation —
    the mapping is injective, so scatter-add never appears in either pass.

Slot assignment is sort-based (argsort over expert ids + segment starts), so
no (T, E) cumsum tensor exists either. Experts shard over the model axis
(EP); the router runs in fp32 and returns a Switch-style load-balance aux.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, dt


def moe_init(key, cfg: ModelConfig):
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    scale = d**-0.5

    def expert_mats(k, din, dout):
        return (jax.random.normal(k, (E, din, dout), jnp.float32) * din**-0.5).astype(dt(cfg))

    return {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * scale).astype(jnp.float32),
        "w_gate": expert_mats(ks[1], d, f),
        "w_up": expert_mats(ks[2], d, f),
        "w_down": expert_mats(ks[3], f, d),
    }


# ---------------------------------------------------------------------------
# permutation gather with gather-based VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def permute_rows(x, fwd_idx, inv_idx, n_out: int):
    """out[j] = x[fwd_idx[j]] (rows); out-of-range index -> zero row.

    fwd_idx: (n_out,) indices into x's rows (sentinel = x.shape[0]).
    inv_idx: (x.shape[0],) inverse mapping (sentinel = n_out) — used only by
    the backward pass. The mapping must be injective on valid entries.
    """
    del inv_idx
    return jnp.take(x, fwd_idx, axis=0, mode="fill", fill_value=0)


def _permute_fwd(x, fwd_idx, inv_idx, n_out):
    return permute_rows(x, fwd_idx, inv_idx, n_out), (inv_idx, x.shape[0])


def _permute_bwd(n_out, res, g):
    inv_idx, n_in = res
    dx = jnp.take(g, inv_idx, axis=0, mode="fill", fill_value=0)
    return dx, None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


class MoEOut(NamedTuple):
    y: jax.Array
    aux_loss: jax.Array  # load-balance loss (Switch LB: E * sum_e f_e * p_e)


def _route(params, xt, E: int, k: int):
    """fp32 routing: (top_p, top_e, aux)."""
    T = xt.shape[0]
    logits = xt.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e[:, 0]].add(1.0) / T
    aux = E * jnp.sum(me * ce)
    return top_p, top_e, aux


def _expert_ffn(xe, wg, wu, wd, constrain):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum("ecd,edf->ecf", xe, wu)
    h = constrain(h, "moe_ffn")
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_apply_ep(params, x: jax.Array, cfg: ModelConfig, constrain) -> MoEOut:
    """Expert-parallel MoE via shard_map: the paper's local-compute + one-psum
    pattern. Tokens stay on their (pod, data) shard, every model shard holds
    E/tp experts and a full replica of the local tokens; each chip slots its
    local tokens for its local experts (1-D sort/gather work only), runs the
    expert FFN, combines locally, and a single psum over "model" produces the
    output. No all-to-all, no cross-shard row gathers.

    Capacity is per-(data-shard, expert): C_loc = cf * T_loc * k / E.
    """
    mesh = constrain.mesh
    cdt = dt(cfg, "compute")
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    tp = mesh.shape.get("model", 1)
    E_loc = E // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    T_loc = (B // dp) * S  # tokens per data shard
    C = max(8, int(cfg.capacity_factor * T_loc * k / E))
    C = -(-C // 8) * 8

    from jax.sharding import PartitionSpec as P  # local import: keep module light

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def local_fn(x_loc, router_w, wg, wu, wd):
        # x_loc: (B_loc, S, d) local tokens (full S per model rank by design);
        # reshape to (T_loc, d) locally — see moe_apply_ep_a2a for why
        xt = x_loc.reshape(T_loc, d)
        top_p, top_e, aux = _route({"router": router_w}, xt, E, k)
        my_first = jax.lax.axis_index("model").astype(jnp.int32) * E_loc
        flat_e = top_e.reshape(T_loc * k).astype(jnp.int32) - my_first  # local ids
        mine = (flat_e >= 0) & (flat_e < E_loc)
        key = jnp.where(mine, flat_e, E_loc)  # foreign pairs sort to the end
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sorted_e = key[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(E_loc, dtype=jnp.int32)).astype(jnp.int32)
        pos_sorted = jnp.arange(T_loc * k, dtype=jnp.int32) - seg_start[sorted_e]
        keep = (sorted_e < E_loc) & (pos_sorted < C)
        slot_sorted = jnp.where(keep, sorted_e * C + pos_sorted, E_loc * C)
        slot_of_pair = jnp.full((T_loc * k,), E_loc * C, jnp.int32).at[order].set(slot_sorted)
        pair_of_slot = jnp.full((E_loc * C,), T_loc * k, jnp.int32).at[
            slot_sorted
        ].set(order, mode="drop")

        xp = jnp.repeat(xt.astype(cdt), k, axis=0)  # (T_loc*k, d)
        xe = permute_rows(xp, pair_of_slot, slot_of_pair, E_loc * C)
        ye = _expert_ffn(xe.reshape(E_loc, C, d), wg.astype(cdt), wu.astype(cdt),
                         wd.astype(cdt), lambda t, s: t)
        ye_pairs = permute_rows(ye.reshape(E_loc * C, d), slot_of_pair, pair_of_slot,
                                T_loc * k)
        w = (top_p.reshape(T_loc * k) * (slot_of_pair < E_loc * C)).astype(cdt)
        y = jnp.sum((ye_pairs * w[:, None]).reshape(T_loc, k, d), axis=1)
        y = jax.lax.psum(y.astype(cdt), "model")  # the one collective, in bf16 (B2)
        if batch_axes:  # aux is per-data-shard: average over the data axes
            aux = jax.lax.psum(aux, batch_axes) / dp
        return y.reshape(x_loc.shape), aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(bspec, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )
    y, aux = fn(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return MoEOut(y, aux.astype(jnp.float32))


def moe_apply_ep_a2a(params, x: jax.Array, cfg: ModelConfig, constrain) -> MoEOut:
    """All-to-all expert parallelism (perf iteration B4, §Perf; GLaM-style).

    Tokens shard over (pod, data, model) — each chip routes only T_chip =
    T/(dp*tp) tokens. Pairs sort by destination model-rank into fixed
    (tp, C_send, d) buffers; one all_to_all delivers them to the expert
    owner, which re-sorts into per-expert queues, runs the FFN, and a
    reverse all_to_all returns the results to the token owners. Both
    directions are pure gathers + a2a (differentiable: a2a^T = a2a), so no
    scatter pathology and the per-chip MoE activation footprint drops 16x
    vs the dispatch-free path. Two capacity stages (send-side C_send per
    destination rank, expert-side C_recv per expert) bound the buffers.
    """
    mesh = constrain.mesh
    cdt = dt(cfg, "compute")
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    tp = mesh.shape.get("model", 1)
    E_loc = E // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    T_chip = (B // dp) * S // tp
    cf = cfg.capacity_factor
    C_send = -(-max(8, int(cf * T_chip * k / tp)) // 8) * 8
    C_recv = -(-max(8, int(cf * tp * C_send / E_loc)) // 8) * 8

    from jax.sharding import PartitionSpec as P

    all_axes = batch_axes + ("model",)
    bspec = all_axes if len(all_axes) > 1 else all_axes[0]

    def _slot(ids, n_buckets: int, cap: int, n_items: int):
        """Sort-based slotting: ids (n_items,) in [0, n_buckets) or >= for
        'drop'. Returns (slot_of_item, item_of_slot) with sentinels."""
        key = jnp.where(ids < n_buckets, ids, n_buckets)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sorted_b = key[order]
        seg = jnp.searchsorted(sorted_b, jnp.arange(n_buckets, dtype=jnp.int32)).astype(jnp.int32)
        pos = jnp.arange(n_items, dtype=jnp.int32) - seg[sorted_b]
        keep = (sorted_b < n_buckets) & (pos < cap)
        slot_sorted = jnp.where(keep, sorted_b * cap + pos, n_buckets * cap)
        slot_of_item = jnp.full((n_items,), n_buckets * cap, jnp.int32).at[order].set(slot_sorted)
        item_of_slot = jnp.full((n_buckets * cap,), n_items, jnp.int32).at[
            slot_sorted
        ].set(order, mode="drop")
        return slot_of_item, item_of_slot

    def local_fn(x_loc, router_w, wg, wu, wd):
        # x_loc: (B_loc, S/tp, d) — reshape to tokens LOCALLY (a global
        # (B,S,d)->(B*S,d) merge across differently-sharded dims triggers
        # GSPMD involuntary full rematerialization: 28 GiB/chip on arctic)
        xt = x_loc.reshape(T_chip, d)
        top_p, top_e, aux = _route({"router": router_w}, xt, E, k)
        flat_e = top_e.reshape(T_chip * k).astype(jnp.int32)
        dest = flat_e // E_loc  # destination model rank per pair

        # ---- send side: pairs -> (tp, C_send) buffers -------------------
        s_of_pair, pair_of_s = _slot(dest, tp, C_send, T_chip * k)
        xp = jnp.repeat(xt.astype(cdt), k, axis=0)
        send = permute_rows(xp, pair_of_s, s_of_pair, tp * C_send)  # (tp*C_send, d)
        # expert-local id rides along (sentinel E_loc for empty slots)
        e_send = jnp.full((tp * C_send,), E_loc, jnp.int32).at[
            jnp.where(s_of_pair < tp * C_send, s_of_pair, tp * C_send)
        ].set(flat_e % E_loc, mode="drop")

        recv = jax.lax.all_to_all(send.reshape(tp, C_send, d), "model", 0, 0, tiled=False)
        e_recv = jax.lax.all_to_all(e_send.reshape(tp, C_send), "model", 0, 0,
                                    tiled=False).reshape(tp * C_send)

        # ---- expert side: recv slots -> per-expert queues ---------------
        r_of_slotq, slotq_of_r = _slot(e_recv, E_loc, C_recv, tp * C_send)
        xe = permute_rows(recv.reshape(tp * C_send, d), slotq_of_r, r_of_slotq,
                          E_loc * C_recv)
        ye = _expert_ffn(xe.reshape(E_loc, C_recv, d), wg.astype(cdt), wu.astype(cdt),
                         wd.astype(cdt), lambda t, s: t)
        back = permute_rows(ye.reshape(E_loc * C_recv, d), r_of_slotq, slotq_of_r,
                            tp * C_send)

        # ---- reverse a2a + combine --------------------------------------
        ret = jax.lax.all_to_all(back.reshape(tp, C_send, d), "model", 0, 0,
                                 tiled=False).reshape(tp * C_send, d)
        y_pairs = permute_rows(ret, s_of_pair, pair_of_s, T_chip * k)
        w = (top_p.reshape(T_chip * k) * (s_of_pair < tp * C_send)).astype(cdt)
        y = jnp.sum((y_pairs * w[:, None]).reshape(T_chip, k, d), axis=1)
        aux = jax.lax.psum(aux, all_axes) / (dp * tp)
        return y.reshape(x_loc.shape), aux

    bonly = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(bonly, "model", None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(bonly, "model", None), P()),
        check_vma=False,
    )
    x = constrain(x, "act_embed")  # (B, S, d): batch x seq(model) sharded
    y, aux = fn(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return MoEOut(y, aux.astype(jnp.float32))


def moe_apply(params, x: jax.Array, cfg: ModelConfig, constrain=lambda t, s: t) -> MoEOut:
    """x: (B, S, d) -> (B, S, d). Dispatch: a2a EP when tokens divide over
    (batch x model) (training/prefill), dispatch-free EP otherwise (decode /
    tiny batches), dense gather path off-mesh."""
    mesh = getattr(constrain, "mesh", None)
    if mesh is not None and mesh.shape.get("model", 1) > 1 and cfg.num_experts % mesh.shape["model"] == 0:
        tp = mesh.shape["model"]
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape[a]
        B, S, _ = x.shape
        T_loc = (B // dp) * S if B % dp == 0 else 0
        if T_loc and T_loc % tp == 0 and T_loc // tp >= 64:
            return moe_apply_ep_a2a(params, x, cfg, constrain)
        return moe_apply_ep(params, x, cfg, constrain)
    return moe_apply_dense(params, x, cfg, constrain)


def moe_apply_dense(params, x: jax.Array, cfg: ModelConfig, constrain=lambda t, s: t) -> MoEOut:
    """Single-device / no-EP path: global-capacity slotting, same math."""
    cdt = dt(cfg, "compute")
    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = max(8, int(cfg.capacity_factor * T * k / E))
    C = -(-C // 8) * 8
    xt = x.reshape(T, d)

    # --- routing (fp32) ---
    logits = xt.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_e[:, 0]].add(1.0) / T
    aux = E * jnp.sum(me * ce)

    # --- sort-based slot assignment: all 1-D integer work ---
    flat_e = top_e.reshape(T * k).astype(jnp.int32)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)  # (T*k,)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=jnp.int32)).astype(jnp.int32)  # (E,)
    pos_sorted = jnp.arange(T * k, dtype=jnp.int32) - seg_start[sorted_e]
    keep_sorted = pos_sorted < C
    slot_sorted = jnp.where(keep_sorted, sorted_e * C + pos_sorted, E * C)
    # slot per (token, choice) pair, original order
    slot_of_pair = jnp.zeros((T * k,), jnp.int32).at[order].set(slot_sorted)  # (T*k,)
    # inverse: which pair fills each slot (sentinel T*k = empty)
    pair_of_slot = jnp.full((E * C,), T * k, jnp.int32).at[
        jnp.where(keep_sorted, slot_sorted, E * C)
    ].set(order, mode="drop")

    # --- dispatch: gather pair rows into (E, C, d) slots ---
    # pair view (token repeated k times) keeps the slot<->pair map injective,
    # so both directions of permute_rows are gathers; repeat's own backward
    # is a cheap reshape-sum over k.
    xp = jnp.repeat(xt.astype(cdt), k, axis=0)  # (T*k, d)
    xe = permute_rows(xp, pair_of_slot, slot_of_pair, E * C)  # (E*C, d)
    xe = constrain(xe.reshape(E, C, d), "moe_tokens")

    # --- expert FFN: batched over E (sharded over model axis) ---
    wg = params["w_gate"].astype(cdt)
    wu = params["w_up"].astype(cdt)
    wd = params["w_down"].astype(cdt)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum("ecd,edf->ecf", xe, wu)
    h = constrain(h, "moe_ffn")
    ye = jnp.einsum("ecf,efd->ecd", h, wd)  # (E, C, d)
    ye = constrain(ye, "moe_tokens").reshape(E * C, d)

    # --- combine: gather each pair's slot row; dropped pairs -> zero row ---
    ye_pairs = permute_rows(ye, slot_of_pair, pair_of_slot, T * k)  # (T*k, d)
    w = (top_p.reshape(T * k) * (slot_of_pair < E * C)).astype(cdt)
    y = jnp.sum((ye_pairs * w[:, None]).reshape(T, k, d), axis=1)
    return MoEOut(y.reshape(B, S, d), aux.astype(jnp.float32))
