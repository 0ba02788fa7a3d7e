"""Top-k MoE FFN with sort-based capacity dispatch (GShard-style, dropless
up to the capacity factor).

Dispatch: flatten (token, k) assignments, stable-sort by expert, compute
position-in-expert from group starts, drop past-capacity assignments to a
phantom slot, gather tokens into [E, C, d], run the batched SwiGLU expert
FFN, and combine back with the (renormalized) router gates. All shapes are
static — no ragged tensors — so the same code jit-compiles for the smoke
tests and for expert-parallel sharding (experts over the 'model' axis; the
token gather/scatter across the data<->expert shardings lowers to
all-to-all, which is exactly the paper-family dispatch collective).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import shard, silu, trunc_normal

__all__ = ["moe_init", "moe_param_specs", "moe_apply", "moe_apply_local_ep"]


def moe_init(key, d_model: int, d_ff: int, n_experts: int, dtype):
    ks = jax.random.split(key, 4)
    return {
        "router": trunc_normal(ks[0], (d_model, n_experts)).astype(jnp.float32),
        "w_gate": trunc_normal(ks[1], (n_experts, d_model, d_ff)).astype(dtype),
        "w_up": trunc_normal(ks[2], (n_experts, d_model, d_ff)).astype(dtype),
        "w_down": trunc_normal(ks[3], (n_experts, d_ff, d_model)).astype(dtype),
    }


def moe_param_specs(tp, *, stacked: bool = False):
    lead = (None,) if stacked else ()
    return {
        "router": P(*lead, None, None),
        "w_gate": P(*lead, tp, None, None),  # expert-parallel
        "w_up": P(*lead, tp, None, None),
        "w_down": P(*lead, tp, None, None),
    }


def moe_apply(
    p,
    x: jnp.ndarray,  # [T, d]
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    rules=None,
    shard_capacity: bool = False,
) -> jnp.ndarray:
    t, d = x.shape
    e, k = n_experts, top_k
    c = max(int(capacity_factor * t * k / e), 1)

    # router (f32 for numerics)
    logits = x.astype(jnp.float32) @ p["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_ids = jax.lax.top_k(probs, k)  # [T, K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # sort assignments by expert
    flat_e = expert_ids.reshape(-1)  # [T*K]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[sorted_e].add(1)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < c
    slot = jnp.where(keep, sorted_e * c + pos_in_e, e * c)  # overflow -> pad

    tok = (order // k).astype(jnp.int32)
    gate_sorted = gates.reshape(-1)[order]

    # dispatch tables ([E*C+1]; the +1 row swallows drops & empty slots)
    disp_tok = jnp.full((e * c + 1,), t, jnp.int32).at[slot].set(
        jnp.where(keep, tok, t)
    )
    disp_gate = jnp.zeros((e * c + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, gate_sorted, 0.0)
    )

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = x_pad[disp_tok[: e * c]].reshape(e, c, d)
    if rules is not None and rules.tp:
        # baseline EP shards experts only; ``shard_capacity`` additionally
        # shards the capacity axis over the data axes — without it every
        # data replica redundantly computes the full expert batch
        # (measured 16x wasted FLOPs in §Perf).
        cap_ax = rules.dp if shard_capacity else None
        xe = shard(xe, P(rules.tp, cap_ax, None))

    h = silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["w_up"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # [E, C, d]
    if rules is not None and rules.tp:
        cap_ax = rules.dp if shard_capacity else None
        ye = shard(ye, P(rules.tp, cap_ax, None))

    # combine: scatter-add weighted expert outputs back to tokens
    ye_flat = ye.reshape(e * c, d) * disp_gate[: e * c, None].astype(ye.dtype)
    y = jnp.zeros((t + 1, d), ye.dtype).at[disp_tok[: e * c]].add(ye_flat)
    return y[:t].astype(x.dtype)


# --------------------------------------------------------------------------
# shard_map expert parallelism with LOCAL dispatch (§Perf iteration 3).
#
# Key observation: in this framework's LM sharding the activations are
# replicated across the 'model' axis (P(dp, None)), so every model column
# already HOLDS every token of its data row. Expert dispatch therefore
# needs NO communication at all: each column selects the tokens routed to
# ITS E/M experts locally, runs them, and the only collective is ONE psum
# of the [T_loc, d] output per MoE layer — the same cost as a dense
# tensor-parallel MLP. This removes both the 16x replicated-compute waste
# (baseline dense dispatch) and the all-gather storm GSPMD emits for the
# capacity-sharded gather (iterations 1/2, measured in EXPERIMENTS.md).
# --------------------------------------------------------------------------
def moe_apply_local_ep(
    p,
    x: jnp.ndarray,  # [T, d] global (inside jit)
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    rules,
    mesh,
) -> jnp.ndarray:
    t, d = x.shape
    e, k = n_experts, top_k
    model_axes = tuple(rules.model)
    data_axes = tuple(rules.data)
    m = 1
    for a in model_axes:
        m *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    assert e % m == 0, (e, m)
    e_loc = e // m

    # routing outside the shard_map (small, differentiable, GSPMD-sharded)
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_ids = jax.lax.top_k(probs, k)  # [T, K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    dp = data_axes if data_axes else None
    tp = model_axes if model_axes else None
    model_axis_name = model_axes if len(model_axes) > 1 else model_axes[0]

    def body(x_loc, eids_loc, gates_loc, wg, wu, wd):
        # x_loc [T_loc, d]; wg/wu/wd [E_loc, ...] (this column's experts)
        t_loc = x_loc.shape[0]
        c = max(int(capacity_factor * t_loc * k / e), 1)
        col = jax.lax.axis_index(model_axis_name)
        e_lo = col * e_loc
        flat_e = eids_loc.reshape(-1)  # [T_loc*K] global expert ids
        mine = (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
        local_e = jnp.where(mine, flat_e - e_lo, e_loc)  # e_loc = drop bucket
        order = jnp.argsort(local_e, stable=True)
        sorted_le = local_e[order]
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[sorted_le].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * k, dtype=jnp.int32) - starts[sorted_le]
        keep = (sorted_le < e_loc) & (pos < c)
        slot = jnp.where(keep, sorted_le * c + pos, e_loc * c)
        tok = (order // k).astype(jnp.int32)
        gate_sorted = gates_loc.reshape(-1)[order]

        disp_tok = jnp.full((e_loc * c + 1,), t_loc, jnp.int32).at[slot].set(
            jnp.where(keep, tok, t_loc))
        disp_gate = jnp.zeros((e_loc * c + 1,), jnp.float32).at[slot].set(
            jnp.where(keep, gate_sorted, 0.0))
        x_pad = jnp.concatenate([x_loc, jnp.zeros((1, d), x_loc.dtype)], 0)
        xe = x_pad[disp_tok[: e_loc * c]].reshape(e_loc, c, d)
        h = silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
            "ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * c, d)
        ye = ye * disp_gate[: e_loc * c, None].astype(ye.dtype)
        y = jnp.zeros((t_loc + 1, d), ye.dtype).at[
            disp_tok[: e_loc * c]].add(ye)[:t_loc]
        # the ONLY collective: combine partial expert outputs across columns
        return jax.lax.psum(y, model_axis_name)

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, None), P(dp, None, None), P(dp, None, None),
                  P(tp, None, None), P(tp, None, None), P(tp, None, None)),
        out_specs=P(dp, None),
        check_vma=False,
    )(x, expert_ids[:, None, :], gates[:, None, :],
      p["w_gate"], p["w_up"], p["w_down"])
    return out.astype(x.dtype)
