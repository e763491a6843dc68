"""Mixture-of-Experts FFN: token-choice top-k routing, capacity-based
sort-and-scatter dispatch, expert parallelism over the 'model' mesh axis.

Design (DESIGN.md §5 "EP-as-TP"): expert weights are sharded on the expert
axis over 'model'.  Dispatch is a pure-jnp sort/scatter into an (E, C, D)
capacity buffer (constrained to the same expert sharding); the expert matmuls
are then fully local to each model shard; the combine scatter-add brings
results back to token order.  No ragged all-to-all is required — the
collective footprint matches a Megatron FFN (gather of the (E,C,D) blocks),
which the dry-run HLO makes visible and §Perf iterates on.

Tokens overflowing an expert's capacity ``C = ceil(T*k/E * cap_factor)`` are
dropped (pass through via the residual), the standard TPU MoE strategy.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed.sharding import constraint
from repro.models.params import PSpec
from repro.models.layers import mlp_abstract, mlp_apply


def moe_abstract(cfg: ModelConfig) -> Dict[str, PSpec]:
    m: MoEConfig = cfg.moe
    d, fe = cfg.d_model, m.d_expert
    p: Dict[str, PSpec] = {
        "router": PSpec((d, m.n_experts), (None, None), dtype="float32"),
        "w1": PSpec((m.n_experts, d, fe), ("tp", "fsdp", None)),
        "w3": PSpec((m.n_experts, d, fe), ("tp", "fsdp", None)),
        "w2": PSpec((m.n_experts, fe, d), ("tp", None, "fsdp")),
    }
    if m.n_shared:
        p["shared"] = mlp_abstract(cfg, d_ff=m.n_shared * fe)
    return p


def _capacity(tokens: int, m: MoEConfig) -> int:
    c = int(tokens * m.top_k / m.n_experts * m.capacity_factor)
    return max((c + 7) // 8 * 8, 8)


def _routing(p, xs, m: MoEConfig):
    logits = jnp.einsum("td,de->te", xs.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)               # (T,k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    e = m.n_experts
    frac_assign = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1),
        axis=0) / m.top_k
    frac_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_assign * frac_prob) * m.aux_loss_weight
    return top_w, top_e, aux


def _rank_in_expert(e_flat: jax.Array, tk: int) -> jax.Array:
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - first.astype(jnp.int32)
    return jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted)


def _expert_ffn(buf, p, cdt):
    g = jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w3"].astype(cdt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w2"].astype(cdt))


def moe_apply(p, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> (out, aux_loss)."""
    from repro.distributed.sharding import get_current_mesh
    m: MoEConfig = cfg.moe
    mesh = get_current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if m.dispatch == "shard_map" and tp > 1 and m.n_experts % tp == 0:
        return _moe_apply_shard_map(p, x, cfg, mesh, tp)
    return _moe_apply_gspmd(p, x, cfg)


def _moe_apply_gspmd(p, x: jax.Array, cfg: ModelConfig):
    """Baseline: GSPMD partitions the capacity-buffer scatter/gather."""
    m: MoEConfig = cfg.moe
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.n_experts
    cap = _capacity(t, m)
    xs = x.reshape(t, d)
    top_w, top_e, aux = _routing(p, xs, m)

    e_flat = top_e.reshape(-1)                                  # (T*k,)
    w_flat = top_w.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pos = _rank_in_expert(e_flat, t * k)
    keep = pos < cap
    slot = jnp.where(keep, e_flat * cap + pos, e * cap)         # OOB -> dropped

    buf = jnp.zeros((e * cap, d), cdt)
    buf = buf.at[slot].add(xs[tok_flat].astype(cdt) *
                           keep[:, None].astype(cdt), mode="drop")
    buf = constraint(buf.reshape(e, cap, d), "tp", None, None)
    y = _expert_ffn(buf, p, cdt)
    y = constraint(y, "tp", None, None).reshape(e * cap, d)

    gathered = y[jnp.clip(slot, 0, e * cap - 1)]
    w_keep = (w_flat * keep).astype(cdt)[:, None]
    out = jnp.zeros((t, d), cdt).at[tok_flat].add(gathered * w_keep)

    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg).reshape(t, d)
    return constraint(out.reshape(b, s, d), "dp", None, None), aux


def _moe_apply_shard_map(p, x: jax.Array, cfg: ModelConfig, mesh, tp: int):
    """EP-as-TP manual dispatch (§Perf): each model shard builds only its
    local (E/tp, C, D) buffer from replicated tokens — zero dispatch
    collectives; the combine is a single (T,D) psum, identical to a Megatron
    FFN's.  Routing (and the aux loss) stays outside in GSPMD-land."""
    from repro.distributed.sharding import spec as shspec
    from jax.sharding import PartitionSpec as P
    m: MoEConfig = cfg.moe
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.n_experts
    e_local = e // tp
    cap = _capacity(t, m)
    xs = x.reshape(t, d)
    top_w, top_e, aux = _routing(p, xs, m)

    tok_spec = shspec("dp", None)       # tokens sharded over data parallelism
    route_spec = shspec("dp", None)
    w_specs = (P("model", None, None),) * 3

    def body(xs_l, te_l, tw_l, w1_l, w3_l, w2_l):
        t_l = xs_l.shape[0]
        cap_l = _capacity(t_l, m)      # per-DP-shard capacity (local tokens)
        lo = jax.lax.axis_index("model") * e_local
        e_flat = te_l.reshape(-1)
        w_flat = tw_l.reshape(-1)
        tok_flat = jnp.repeat(jnp.arange(t_l, dtype=jnp.int32), k)
        pos = _rank_in_expert(e_flat, t_l * k)
        local = (pos < cap_l) & (e_flat >= lo) & (e_flat < lo + e_local)
        slot = jnp.where(local, (e_flat - lo) * cap_l + pos, e_local * cap_l)
        buf = jnp.zeros((e_local * cap_l, d), cdt)
        buf = buf.at[slot].add(xs_l[tok_flat].astype(cdt) *
                               local[:, None].astype(cdt), mode="drop")
        y = _expert_ffn(buf.reshape(e_local, cap_l, d),
                        {"w1": w1_l, "w3": w3_l, "w2": w2_l}, cdt)
        y = y.reshape(e_local * cap_l, d)
        gathered = y[jnp.clip(slot, 0, e_local * cap_l - 1)]
        w_keep = (w_flat * local).astype(cdt)[:, None]
        out_l = jnp.zeros((t_l, d), cdt).at[tok_flat].add(gathered * w_keep)
        return jax.lax.psum(out_l, "model")

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, route_spec, route_spec) + w_specs,
        out_specs=tok_spec, check_vma=False,
    )(xs, top_e, top_w, p["w1"], p["w3"], p["w2"])

    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg).reshape(t, d)
    return constraint(out.reshape(b, s, d), "dp", None, None), aux


def moe_reference(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Dense oracle: every token through its top-k experts, no capacity.
    Used by tests to validate the dispatch path."""
    m = cfg.moe
    cdt = jnp.dtype(cfg.compute_dtype)
    b, s, d = x.shape
    xs = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xs.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(xs, cdt)
    for j in range(m.top_k):
        # compute every expert on every token, select (oracle only; O(E*T))
        g = jnp.einsum("td,edf->etf", xs.astype(cdt), p["w1"].astype(cdt))
        u = jnp.einsum("td,edf->etf", xs.astype(cdt), p["w3"].astype(cdt))
        y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, p["w2"].astype(cdt))
        sel = y[top_e[:, j], jnp.arange(xs.shape[0])]
        out = out + sel * top_w[:, j:j + 1].astype(cdt)
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg).reshape(-1, d)
    return out.reshape(b, s, d)
