"""Mixture-of-Experts FFN (mixtral / olmoe / jamba).

Two interchangeable implementations with identical math:

  * ``moe_dense``  — baseline: every expert computes every token, outputs
    weighted by the top-k router probabilities.  Simple, fully shardable by
    pjit (experts 2D-sharded over data x model), but compiles E/k more FLOPs
    than a token actually needs.  This surplus is *visible* in the roofline
    table (MODEL_FLOPS / HLO_FLOPs << 1) and is the target of the §Perf
    hillclimb.
  * ``moe_ragged`` — optimized: tokens sorted by expert, grouped matmuls via
    ``jax.lax.ragged_dot`` compute only routed tokens (FLOP-exact, with
    top-k expansion).  Used under shard_map expert parallelism.

Router: softmax over expert logits, top-k, renormalized — the mixtral
formulation (olmoe normalizes the same way).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .schema import ParamDef

from jax import shard_map

F32 = jnp.float32


def moe_schema(cfg: ArchConfig):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": ParamDef((d, E), ("embed", None), jnp.float32),
        "w_gate": ParamDef((E, d, f), ("expert", "embed", "moe_mlp")),
        "w_up": ParamDef((E, d, f), ("expert", "embed", "moe_mlp")),
        "w_down": ParamDef((E, f, d), ("expert", "moe_mlp", "embed")),
    }


def router_probs(p, x, cfg: ArchConfig):
    """top-k routing -> (weights [B,S,k] f32, indices [B,S,k] i32)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i


def _noshard(x, axes):
    return x


def moe_dense(p, x, cfg: ArchConfig, shard=_noshard):
    """All-experts compute, router-weighted combine (baseline)."""
    top_p, top_i = router_probs(p, x, cfg)
    g = shard(jnp.einsum("bsd,edf->besf", x, p["w_gate"]),
              ("batch", "expert_act", "seq", "mlp_act"))
    u = shard(jnp.einsum("bsd,edf->besf", x, p["w_up"]),
              ("batch", "expert_act", "seq", "mlp_act"))
    h = jax.nn.silu(g) * u
    y = shard(jnp.einsum("besf,efd->besd", h, p["w_down"]),
              ("batch", "expert_act", "seq", None))        # [B,E,S,d]
    # combine: sum over the k selected experts
    onehot = jax.nn.one_hot(top_i, cfg.n_experts, dtype=F32)  # [B,S,k,E]
    w = jnp.einsum("bske,bsk->bse", onehot, top_p)            # [B,S,E]
    return jnp.einsum("besd,bse->bsd", y.astype(F32),
                      w).astype(x.dtype)


def moe_ragged(p, x, cfg: ArchConfig):
    """Sorted dispatch + grouped matmul: FLOP-exact top-k MoE."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    top_p, top_i = router_probs(p, x, cfg)
    T = B * S * k
    xt = jnp.repeat(x.reshape(B * S, d), k, axis=0)           # [T, d]
    eid = top_i.reshape(-1)                                   # [T]
    gates = top_p.reshape(-1)

    order = jnp.argsort(eid)                                  # stable
    xs = xt[order]
    group_sizes = jnp.bincount(eid, length=E).astype(jnp.int32)

    gg = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    uu = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    hh = jax.nn.silu(gg) * uu
    yy = jax.lax.ragged_dot(hh, p["w_down"], group_sizes)     # [T, d]

    inv = jnp.argsort(order)
    y = yy[inv] * gates[:, None].astype(yy.dtype)
    return y.reshape(B, S, k, d).sum(axis=2).astype(x.dtype)


# --- ragged FFN with exact ragged gradients ---------------------------------
# jax.lax.ragged_dot's builtin VJP computes weight gradients densely (one
# [cap, d] x [cap, f] matmul per expert => E_loc x the active flops — measured
# in EXPERIMENTS.md §Perf iteration 3).  This custom VJP keeps every term
# ragged: dX via ragged_dot with transposed weights, dW via ragged_dot_general
# in ragged-CONTRACTING mode (groups over the token dim).

def _ragged_outer(a, b, group_sizes):
    """[m,p], [m,q], groups over m -> [E,p,q] (sum of outer products)."""
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(a, b, group_sizes, dn)


@jax.custom_vjp
def _ragged_ffn(xs, wg, wu, wd, group_sizes):
    gg = jax.lax.ragged_dot(xs, wg, group_sizes)
    uu = jax.lax.ragged_dot(xs, wu, group_sizes)
    return jax.lax.ragged_dot(jax.nn.silu(gg) * uu, wd, group_sizes)


def _ragged_ffn_fwd(xs, wg, wu, wd, group_sizes):
    gg = jax.lax.ragged_dot(xs, wg, group_sizes)
    uu = jax.lax.ragged_dot(xs, wu, group_sizes)
    hh = jax.nn.silu(gg) * uu
    yy = jax.lax.ragged_dot(hh, wd, group_sizes)
    return yy, (xs, wg, wu, wd, gg, uu, hh, group_sizes)


def _ragged_ffn_bwd(res, dy):
    import numpy as np
    xs, wg, wu, wd, gg, uu, hh, gs = res
    wgt = jnp.swapaxes(wg, 1, 2)
    wut = jnp.swapaxes(wu, 1, 2)
    wdt = jnp.swapaxes(wd, 1, 2)
    dhh = jax.lax.ragged_dot(dy, wdt, gs)
    dwd = _ragged_outer(hh, dy, gs)
    sg = jax.nn.silu(gg)
    dsilu = jax.nn.sigmoid(gg) * (1 + gg * (1 - jax.nn.sigmoid(gg)))
    dgg = dhh * uu * dsilu
    duu = dhh * sg
    dxs = jax.lax.ragged_dot(dgg, wgt, gs) \
        + jax.lax.ragged_dot(duu, wut, gs)
    dwg = _ragged_outer(xs, dgg, gs)
    dwu = _ragged_outer(xs, duu, gs)
    dgs = np.zeros(gs.shape, jax.dtypes.float0)
    return dxs, dwg, dwu, dwd, dgs


_ragged_ffn.defvjp(_ragged_ffn_fwd, _ragged_ffn_bwd)


def moe_ep_ragged(p, x, cfg: ArchConfig, *, mesh, dp_axes,
                  expert_axis: str = "model"):
    """Expert-parallel ragged MoE under shard_map (the §Perf optimization).

    Experts shard on the model axis (replicated across data); each chip
    sorts ITS tokens by local expert, computes a capacity-bounded ragged
    matmul over only routed tokens (C = T*k*E_loc/E * capacity_factor rows
    — the 1/E_loc * cf of the dense-dispatch flops), and a single psum over
    the expert axis combines each token's top-k partial outputs.  Tokens
    beyond capacity are dropped (standard MoE capacity semantics).
    """
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_exp_shards = sizes[expert_axis]
    E_loc = E // n_exp_shards
    n_data = 1
    for a in dp_axes:
        n_data *= sizes[a]
    T_loc = (B // n_data) * S
    cap = int(T_loc * k * E_loc / E * cfg.capacity_factor) + 1

    def body(x_loc, router, wg, wu, wd):
        Bl, S_, d_ = x_loc.shape
        T = Bl * S_
        xt = x_loc.reshape(T, d_)
        logits = jnp.einsum("td,de->te", xt.astype(F32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        eid = top_i.reshape(-1)
        gates = top_p.reshape(-1)
        shard = jax.lax.axis_index(expert_axis)
        eloc = eid - shard * E_loc
        valid = (eloc >= 0) & (eloc < E_loc)
        # sort: local experts ascending, non-local last; take capacity rows
        order = jnp.argsort(jnp.where(valid, eloc, E_loc))
        sel = order[:cap]
        sel_valid = valid[sel]
        es = jnp.where(sel_valid, eloc[sel], E_loc - 1)
        # rows must stay grouped: invalid rows sit at the tail, and we fold
        # them into the last group with zero gates (bounded waste <= cap)
        group_sizes = jnp.bincount(es, length=E_loc).astype(jnp.int32)
        tok = sel // k                       # owning token of each row
        xs = xt[tok]                         # gather ONLY capacity rows
        gs = jnp.where(sel_valid, gates[sel], 0.0)

        yy = _ragged_ffn(xs, wg, wu, wd, group_sizes)
        yy = yy.astype(F32) * gs[:, None]

        # combine: scatter-add straight into [T, d] (duplicate tokens sum)
        out = jnp.zeros((T, d_), F32).at[tok].add(yy)
        out = jax.lax.psum(out, expert_axis)
        return out.reshape(Bl, S_, d_).astype(x_loc.dtype)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=P(dp_axes, None, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_fsliced_ragged(p, x, cfg: ArchConfig, *, mesh, dp_axes,
                       f_axis: str = "model"):
    """f-sliced ragged MoE (§Perf-4's confirmed design): every model shard
    computes its d_ff slice of ALL routed rows.

    Unlike expert-parallel dispatch this needs no E % model divisibility and
    no capacity (total routed rows = T*k exactly — zero token drops): tokens
    sort by expert once per shard, three ragged matmuls run over the local
    f-slice, and one psum over the f axis completes the down-projection.
    FSDP weight gathers happen at the shard_map boundary (in_specs request
    f-sharded weights; the data-axis shards re-assemble d).
    """
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k

    def body(x_loc, router, wg, wu, wd):
        Bl, S_, d_ = x_loc.shape
        T = Bl * S_
        xt = x_loc.reshape(T, d_)
        logits = jnp.einsum("td,de->te", xt.astype(F32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        eid = top_i.reshape(-1)                       # [T*k]
        gates = top_p.reshape(-1)

        order = jnp.argsort(eid)                      # every row computed
        tok = order // k
        xs = xt[tok]
        group_sizes = jnp.bincount(eid, length=E).astype(jnp.int32)

        yy = _ragged_ffn(xs, wg, wu, wd, group_sizes)  # f-slice partials
        # combine in the model dtype: halves the [T*k, d] dispatch buffers
        # AND the f-axis psum bytes (§Perf-4 iter 3); the k-way token sum
        # and the f-slice psum are short reductions, bf16-safe
        yy = yy * gates[order][:, None].astype(yy.dtype)
        out = jnp.zeros((T, d_), yy.dtype).at[tok].add(yy)
        out = jax.lax.psum(out, f_axis)               # complete d_ff sums
        return out.reshape(Bl, S_, d_).astype(x_loc.dtype)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  P(None, None, f_axis), P(None, None, f_axis),
                  P(None, f_axis, None)),
        out_specs=P(dp_axes, None, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe(p, x, cfg: ArchConfig, impl="dense", shard=_noshard):
    if callable(impl):
        return impl(p, x, cfg)
    if impl == "ragged":
        return moe_ragged(p, x, cfg)
    return moe_dense(p, x, cfg, shard=shard)


def moe_flops_per_token(cfg: ArchConfig, active_only: bool = True) -> int:
    """2*d*f*3 matmuls, per selected expert."""
    e = cfg.top_k if active_only else cfg.n_experts
    return 6 * cfg.d_model * cfg.d_ff * e
