"""Mixture-of-Experts: top-k token-choice routing with sort-based grouped
dispatch (MegaBlocks-style) — static shapes, dry-run friendly, EP-shardable.

The dispatch is, relationally, a D2D join + group-by between the token
matrix and the expert assignment matrix — the MoE analogue of the paper's
single-dimension join with a sparsity-inducing merge (DESIGN.md §4): only
the (token, expert) pairs selected by the router are computed, with a
capacity bound playing the role of the paper's block-skip.

Sharding: expert weight tensors carry the "experts" logical axis → EP over
the tensor axis when n_experts divides it; otherwise the per-expert ffn dim
carries "ffn" → expert-tensor-parallel (ETP).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.module import ParamSpec


def moe_spec(cfg: ModelConfig, layers: Optional[int] = None) -> Dict:
    assert cfg.moe is not None
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff, m.n_experts
    lead = (layers,) if layers else ()
    la: Tuple[Optional[str], ...] = ("layers",) if layers else ()
    return {
        "router": ParamSpec(lead + (d, e), la + ("embed", None)),
        "w_gate": ParamSpec(lead + (e, d, f), la + ("experts", "embed",
                                                    "ffn")),
        "w_up": ParamSpec(lead + (e, d, f), la + ("experts", "embed",
                                                  "ffn")),
        "w_down": ParamSpec(lead + (e, f, d), la + ("experts", "ffn",
                                                    "embed")),
    }


def _capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def apply_moe(p, cfg: ModelConfig, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B,S,d] → (y [B,S,d], aux_loss scalar).

    Sort-based grouped dispatch:
      1. router logits → top-k (expert_idx, weight) per token
      2. flatten to T·k assignments, sort by expert id
      3. positions within each expert group via rank arithmetic; drop
         beyond capacity
      4. scatter token activations into [G, E, C, d]; batched expert einsum
      5. gather back, weight, and segment-sum per token

    With ``moe.grouped_dispatch`` (PERF) the token pool is split per batch
    row (G = B): every sort/scatter/gather is then embarrassingly parallel
    along the DP axes — the baseline's global argsort over B·S·k
    assignments (an all-gather at scale) disappears, at the cost of
    per-group instead of global capacity (what production MoE systems do).
    """
    from repro.sharding.ctx import shard_act
    m = cfg.moe
    dt = cfg.compute_dtype
    b, s, d = x.shape
    g = b if m.grouped_dispatch else 1
    t = (b * s) // g
    e_num = m.n_experts
    k = m.top_k
    xt = x.reshape(g, t, d)
    gi = jnp.arange(g)[:, None]                            # group index

    logits = jnp.einsum("gtd,de->gte", xt, p["router"].astype(dt)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                    # [G, T, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch-style), averaged over groups
    me = probs.mean(axis=1)                                # [G, E]
    ce = jnp.zeros((g, e_num), jnp.float32).at[
        gi, idx.reshape(g, t * k)].add(1.0 / (t * k))
    aux = e_num * jnp.mean(jnp.sum(me * ce, axis=-1)) * m.router_aux_weight

    # --- dispatch / expert compute / combine ---------------------------------
    c = _capacity(t, m)
    w_gate, w_up, w_down = (p["w_gate"].astype(dt), p["w_up"].astype(dt),
                            p["w_down"].astype(dt))

    from repro.sharding.ctx import current
    ctx = current()
    import os
    # NOTE: the partial-manual shard_map dispatch is the *right* TPU design
    # (DP-local scatters), but XLA 0.8's CPU pipeline crashes compiling its
    # transpose ("Invalid binary instruction opcode copy" in
    # hlo_instruction.cc) — kept behind a flag until the toolchain moves;
    # the constraint-pinned combine below recovers most of the win
    # (EXPERIMENTS.md §Perf, granite iteration 2).
    if (m.grouped_dispatch and ctx is not None
            and os.environ.get("REPRO_MOE_SHARDMAP") == "1"):
        # PERF: run the scatter/gather dispatch DP-locally under a
        # partial-manual shard_map (manual over batch axes, auto over the
        # tensor axis). GSPMD cannot shard batched scatters — without this
        # it replicates the [G,T·k,d] dispatch tensors and all-reduces them
        # every layer (measured 34 GB/layer/chip; EXPERIMENTS.md §Perf).
        mesh, rules = ctx
        ba = tuple(a for a in rules.batch if a in mesh.shape)
        from jax.sharding import PartitionSpec as P
        n_shards = 1
        for a in ba:
            n_shards *= mesh.shape[a]
        if ba and g % n_shards == 0:
            fn = jax.shard_map(
                lambda xt_, idx_, gate_, wg_, wu_, wd_: _dispatch_block(
                    xt_, idx_, gate_, wg_, wu_, wd_, m=m, dt=dt, c=c,
                    inside_manual=True),
                mesh=mesh, axis_names=set(ba),
                in_specs=(P(ba), P(ba), P(ba), P(), P(), P()),
                out_specs=P(ba), check_vma=False)
            out = fn(xt, idx, gate, w_gate, w_up, w_down)
            return out.reshape(b, s, d), aux
    out = _dispatch_block(xt, idx, gate, w_gate, w_up, w_down, m=m, dt=dt,
                          c=c)
    return out.reshape(b, s, d), aux


def _dispatch_block(xt, idx, gate, w_gate, w_up, w_down, *, m, dt, c,
                    inside_manual=False):
    """Sort-based dispatch + expert einsum + combine over [G, T, ...].

    ``inside_manual``: running under shard_map with the batch axes manual —
    sharding constraints may then only mention the (auto) tensor axis.
    """
    from repro.sharding.ctx import shard_act
    batch_lg = None if inside_manual else "batch"
    g, t, d = xt.shape
    k = m.top_k
    e_num = m.n_experts
    gi = jnp.arange(g)[:, None]
    flat_e = idx.reshape(g, t * k)
    flat_tok = jnp.broadcast_to(
        jnp.repeat(jnp.arange(t), k)[None], (g, t * k))
    flat_g = gate.reshape(g, t * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=-1)
    st = jnp.take_along_axis(flat_tok, order, axis=-1)
    sg = jnp.take_along_axis(flat_g, order, axis=-1)
    # rank within each expert group: start offset of expert e is the count
    # of assignments with expert id < e
    starts = jnp.sum(se[:, None, :] < jnp.arange(e_num)[None, :, None],
                     axis=-1)                              # [G, E]
    pos = jnp.arange(t * k)[None] - jnp.take_along_axis(starts, se, axis=1)
    keep = pos < c
    target = jnp.where(keep, se * c + pos, e_num * c)      # drop slot
    gathered = jnp.take_along_axis(xt, st[..., None], axis=1).astype(dt)
    buf = jnp.zeros((g, e_num * c + 1, d), dt)
    buf = buf.at[gi, target].set(gathered, mode="drop")
    grouped = shard_act(buf[:, :-1].reshape(g, e_num, c, d),
                        batch_lg, "act_experts", None, None)

    # --- expert FFN (batched einsum over the expert axis) ------------------
    g_ = jnp.einsum("gecd,edf->gecf", grouped, w_gate)
    u_ = jnp.einsum("gecd,edf->gecf", grouped, w_up)
    h = shard_act(jax.nn.silu(g_) * u_, batch_lg, "act_experts", None,
                  "act_ffn")
    y_e = jnp.einsum("gecf,efd->gecd", h, w_down)

    # --- combine ------------------------------------------------------------
    flat_y = shard_act(y_e.reshape(g, e_num * c, d), batch_lg, None, None)
    safe_target = jnp.minimum(target, e_num * c - 1)
    per_assign = jnp.where(
        keep[..., None],
        jnp.take_along_axis(flat_y, safe_target[..., None], axis=1), 0.0)
    # pin the gathered assignments to the DP axes — without this GSPMD
    # replicates the [G, T·k, d] tensor and all-reduces it per layer
    # (measured: 34 GB/layer/chip on granite; EXPERIMENTS.md §Perf)
    per_assign = shard_act(per_assign, batch_lg, None, None)
    out = jnp.zeros((g, t, d), dt).at[gi, st].add(
        per_assign * sg[..., None].astype(dt))
    return shard_act(out, batch_lg, None, None)
