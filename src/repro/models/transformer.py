"""Decoder-only LM assembly with pattern-period layer scanning.

Heterogeneous layer patterns (gemma3's 5 local : 1 global, rwkv/hybrid
mixes) conflict with a naive scan-over-layers: a scan body must be static,
but window sizes / mixer types vary per layer. The resolution here: tile the
pattern across num_layers and split the stack into *segments* of repeated
periods —

    gemma3-4b (34L, pattern LLLLLG):  [5 x (L L L L L G)] + [1 x (L L L L)]

Each segment is one lax.scan over its repeat count; the body statically
unrolls the (short) period, so every layer keeps its compile-time window and
the HLO contains no masked-away wasted attention FLOPs and no dual-branch
conditionals. Homogeneous models degenerate to the classic scan (period 1).
Parameters are stacked (repeat, *param) per segment — FSDP-sharded leading
dims all-gather per scan step, which is what the XLA latency-hiding
scheduler overlaps with compute.

The same segment structure drives train, prefill, and decode (caches are
stacked per segment), plus rwkv6 (ssm) and recurrentgemma (hybrid) mixers.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.layers import (
    chunked_softmax_xent,
    dt,
    embed_init,
    embed_lookup,
    logits_from,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    unembed_init,
)

PyTree = Any
AUX_LOSS_WEIGHT = 0.01


class Segment(NamedTuple):
    repeat: int
    windows: Tuple[int, ...]  # per position in the period
    mixers: Tuple[str, ...]  # "attn" | "rglru" | "rwkv"


def segments(cfg: ModelConfig) -> List[Segment]:
    windows = cfg.layer_windows()
    mixers = cfg.layer_mixers()
    L = cfg.num_layers
    if not cfg.scan_layers:  # fully unrolled: one repeat-1 segment per layer
        return [Segment(1, (windows[i],), (mixers[i],)) for i in range(L)]
    p = max(len(cfg.window_pattern), len(cfg.mixer_pattern))
    k, r = divmod(L, p)
    segs = []
    if k:
        segs.append(Segment(k, windows[:p], mixers[:p]))
    if r:
        segs.append(Segment(1, windows[L - r :], mixers[L - r :]))
    return segs


# ---------------------------------------------------------------------------
# per-layer init/apply
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, mixer: str) -> PyTree:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, PyTree] = {"ln1": rmsnorm_init(d, cfg), "ln2": rmsnorm_init(d, cfg)}
    if mixer == "attn":
        p["attn"] = attn.attn_init(ks[0], cfg)
    elif mixer == "rglru":
        p["rglru"] = rglru_mod.rglru_init(ks[0], cfg)
    elif mixer == "rwkv":
        p["rwkv"] = rwkv_mod.timemix_init(ks[0], cfg)
    else:
        raise ValueError(mixer)
    if mixer == "rwkv":
        p["cmix"] = rwkv_mod.chanmix_init(ks[1], cfg)
    elif cfg.num_experts:
        p["moe"] = moe_mod.moe_init(ks[1], cfg)
        if cfg.moe_dense_residual:
            p["mlp"] = mlp_init(ks[2], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], cfg)
    return p


class LayerState(NamedTuple):
    """Decode-time state for one layer (exactly one field is 'active')."""

    kv: attn.KVCache | None
    rglru: rglru_mod.RGLRUState | None
    rwkv_tm: rwkv_mod.TimeMixState | None
    cmix_prev: jax.Array | None


def _layer_state_init(cfg: ModelConfig, mixer: str, window: int, B: int, S_ctx: int) -> LayerState:
    cdt = dt(cfg, "compute")
    if mixer == "attn":
        return LayerState(attn.init_cache(cfg, B, S_ctx, window, cdt), None, None, None)
    if mixer == "rglru":
        return LayerState(None, rglru_mod.rglru_state_init(cfg, B, cdt), None, None)
    return LayerState(
        None, None, rwkv_mod.timemix_state_init(cfg, B, cdt), jnp.zeros((B, cfg.d_model), cdt)
    )


def _layer_apply(
    params: PyTree,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    mixer: str,
    window: int,
    mode: str,  # "train" | "decode"
    state: LayerState | None,
    cur_pos: jax.Array | None,
    constrain=lambda t, s: t,
) -> tuple[jax.Array, LayerState | None, jax.Array]:
    """Returns (x_out, new_state, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    # B3 (§Perf): pin the norm output to the sequence-sharded layout — else
    # GSPMD hoists the S all-gather above the fp32 norm chain and the norm
    # math runs on full-S replicated-over-model tensors (16x traffic).
    h = constrain(rmsnorm(params["ln1"], x, cfg.norm_eps), "act_embed")
    new_state = state
    if mixer == "attn":
        if mode == "train":
            if state is not None:  # prefill: also build the cache
                out, (k, v) = attn.attn_apply_train(
                    params["attn"], h, positions, cfg, window=window,
                    constrain=constrain, return_kv=True,
                )
                cache = attn.cache_from_prefill(state.kv, k, v, positions, window)
                new_state = state._replace(kv=cache)
            else:
                out = attn.attn_apply_train(
                    params["attn"], h, positions, cfg, window=window, constrain=constrain
                )
        else:
            out, kv = attn.attn_apply_decode(
                params["attn"], h, cur_pos, state.kv, cfg, window=window, constrain=constrain
            )
            new_state = state._replace(kv=kv)
    elif mixer == "rglru":
        st = state.rglru if state is not None else rglru_mod.rglru_state_init(cfg, x.shape[0], x.dtype)
        fn = rglru_mod.rglru_apply_train if mode == "train" else rglru_mod.rglru_apply_decode
        out, st = fn(params["rglru"], h, st, cfg, constrain=constrain)
        new_state = state._replace(rglru=st) if state is not None else None
    else:  # rwkv
        st = state.rwkv_tm if state is not None else rwkv_mod.timemix_state_init(cfg, x.shape[0], x.dtype)
        fn = rwkv_mod.timemix_apply_chunked if mode == "train" else rwkv_mod.timemix_apply_decode
        out, st = fn(params["rwkv"], h, st, cfg, constrain=constrain)
        new_state = state._replace(rwkv_tm=st) if state is not None else None
    # remat policy anchor: saving the mixer output means the backward never
    # re-runs the attention/wkv forward (perf iteration A3, §Perf)
    out = jax.ad_checkpoint.checkpoint_name(out, "mixer_out")
    x = x + out.astype(x.dtype)
    x = constrain(x, "act_embed")

    h = constrain(rmsnorm(params["ln2"], x, cfg.norm_eps), "act_embed")
    if mixer == "rwkv":
        prev = state.cmix_prev if state is not None else jnp.zeros_like(h[:, -1])
        out, prev = rwkv_mod.chanmix_apply(params["cmix"], h, prev, cfg)
        if state is not None:
            new_state = new_state._replace(cmix_prev=prev)
    elif cfg.num_experts:
        moe_out = moe_mod.moe_apply(params["moe"], h, cfg, constrain=constrain)
        out, aux = moe_out.y, moe_out.aux_loss
        if cfg.moe_dense_residual:
            out = out + mlp_apply(params["mlp"], h, cfg, constrain=constrain)
    else:
        out = mlp_apply(params["mlp"], h, cfg, constrain=constrain)
    x = x + out.astype(x.dtype)
    return constrain(x, "act_embed"), new_state, aux


# ---------------------------------------------------------------------------
# whole-model init / apply
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> PyTree:
    segs = segments(cfg)
    keys = jax.random.split(key, len(segs) + 2)
    params: Dict[str, PyTree] = {"embed": embed_init(keys[0], cfg)}
    if not cfg.tie_embeddings:
        params["unembed"] = unembed_init(keys[1], cfg)
    params["final_norm"] = rmsnorm_init(cfg.d_model, cfg)
    for si, seg in enumerate(segs):
        lkeys = jax.random.split(keys[2 + si], seg.repeat * len(seg.windows)).reshape(
            seg.repeat, len(seg.windows), 2
        )
        rows = []
        for rep in range(seg.repeat):
            row = [
                _layer_init(lkeys[rep, j], cfg, seg.mixers[j]) for j in range(len(seg.windows))
            ]
            # stack period positions into leading axis only if homogeneous;
            # period positions may have different mixers => keep as tuple
            rows.append(tuple(row))
        # stack over repeats: map over period positions
        stacked = tuple(
            jax.tree.map(lambda *xs: jnp.stack(xs), *(rows[r][j] for r in range(seg.repeat)))
            for j in range(len(seg.windows))
        )
        params[f"seg{si}"] = stacked
    return params


def _backbone(
    params: PyTree,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,
    states: PyTree | None,
    cur_pos: jax.Array | None,
    constrain=lambda t, s: t,
):
    """Runs all segments. states (if given) mirrors the segment structure:
    states[f"seg{si}"] = tuple over period positions of stacked LayerStates."""
    segs = segments(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_states: Dict[str, PyTree] = {}

    for si, seg in enumerate(segs):
        seg_params = params[f"seg{si}"]
        seg_state = states[f"seg{si}"] if states is not None else None

        def body(carry, xs, _seg=seg):
            xc, aux_c = carry
            # keep the saved residual stack in the carry's own dtype: without
            # the barrier XLA hoists the rmsnorm f32-convert into the saved
            # buffer, doubling the remat stack (32 GiB on rwkv6 train_4k).
            xc = jax.lax.optimization_barrier(xc)
            layer_params, layer_state = xs
            out_states = []
            for j in range(len(_seg.windows)):
                st_j = layer_state[j] if layer_state is not None else None
                xc, st_j, aux = _layer_apply(
                    layer_params[j],
                    xc,
                    positions,
                    cfg,
                    mixer=_seg.mixers[j],
                    window=_seg.windows[j],
                    mode=mode,
                    state=st_j,
                    cur_pos=cur_pos,
                    constrain=constrain,
                )
                out_states.append(st_j)
            return (xc, aux_c + aux), tuple(out_states) if layer_state is not None else None

        # perf iteration A3 (refuted, §Perf): saving mixer outputs via
        # save_only_these_names cost +0.44 GiB and no traffic win — the
        # backward's own d(attention) passes dominate, not the recompute.
        body_fn = jax.checkpoint(body) if (cfg.remat and mode == "train") else body
        (x, aux_total), seg_new_state = jax.lax.scan(
            body_fn, (x, aux_total), (seg_params, seg_state)
        )
        new_states[f"seg{si}"] = seg_new_state

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, (new_states if states is not None else None), aux_total


def init_decode_state(cfg: ModelConfig, B: int, S_ctx: int) -> PyTree:
    """Stacked per-segment decode states (KV caches / recurrent states)."""
    segs = segments(cfg)
    states: Dict[str, PyTree] = {}
    for si, seg in enumerate(segs):
        per_pos = []
        for j in range(len(seg.windows)):
            one = _layer_state_init(cfg, seg.mixers[j], seg.windows[j], B, S_ctx)
            per_pos.append(jax.tree.map(lambda x: jnp.stack([x] * seg.repeat), one))
        states[f"seg{si}"] = tuple(per_pos)
    return states


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _input_embeddings(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Token embeddings, with optional multimodal prefix (stub frontends)."""
    x = embed_lookup(params["embed"], batch["tokens"], cfg)
    if "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(x.dtype) * (cfg.d_model**0.5)
        x = jnp.concatenate([fe, x], axis=1)
    return x


def train_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
               constrain=lambda t, s: t) -> tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE (+ MoE aux). batch: tokens (B,S[,frontend])."""
    x = _input_embeddings(params, batch, cfg)
    x = constrain(x, "act_embed")
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, _, aux = _backbone(params, x, positions, cfg, mode="train", states=None,
                          cur_pos=None, constrain=constrain)

    P = x.shape[1] - batch["tokens"].shape[1]  # frontend prefix length
    x_text = x[:, P:, :]
    labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
    mask = batch.get("loss_mask", jnp.ones_like(batch["tokens"], jnp.float32))
    mask = mask.astype(jnp.float32).at[:, -1].set(0.0)
    ce = chunked_softmax_xent(x_text, labels, mask, params["embed"],
                              params.get("unembed"), cfg, constrain=constrain)
    loss = ce + AUX_LOSS_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


def prefill(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            constrain=lambda t, s: t, total_slots: int | None = None):
    """Full-context forward building decode caches; returns (last_logits, states).

    total_slots: KV-cache capacity (>= prefill length + planned decode steps);
    defaults to prefill length + 1.
    """
    x = _input_embeddings(params, batch, cfg)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    states = init_decode_state(cfg, B, total_slots or S + 1)
    x, states, _ = _backbone(params, x, positions, cfg, mode="train", states=states,
                             cur_pos=None, constrain=constrain)
    logits = logits_from(params["embed"], params.get("unembed"), x[:, -1:, :], cfg)
    return logits[:, 0], states


def decode_step(params, tokens: jax.Array, cur_pos: jax.Array, states: PyTree,
                cfg: ModelConfig, constrain=lambda t, s: t):
    """One-token serve step. tokens: (B, 1); cur_pos: scalar absolute position.
    Returns (logits (B, V), new_states)."""
    x = embed_lookup(params["embed"], tokens, cfg)
    B = x.shape[0]
    positions = jnp.broadcast_to(cur_pos[None, None], (B, 1)).astype(jnp.int32)
    x, states, _ = _backbone(params, x, positions, cfg, mode="decode", states=states,
                             cur_pos=cur_pos, constrain=constrain)
    logits = logits_from(params["embed"], params.get("unembed"), x, cfg)
    return constrain(logits[:, 0].astype(jnp.float32), "logits"), states
