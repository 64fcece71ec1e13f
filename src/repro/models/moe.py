"""Mixture-of-Experts MLP with capacity-based top-k routing and
expert-parallel-friendly layout.

Dispatch is sort-free one-hot/capacity based (the MaxText/GSPMD idiom): a
dispatch tensor [tokens, experts, capacity] routes token activations into an
[experts, capacity, d_model] buffer whose expert axis shards over "model"
(EP). Tokens beyond an expert's capacity are dropped (their combine weight
is zero) — standard capacity-factor semantics; aux load-balancing and
router-z losses are returned for the training loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import mlp_apply, mlp_init
from .partition import ParamMeta, hint


def moe_init(rng, cfg: ModelConfig):
    e = cfg.moe
    ks = jax.random.split(rng, 5)
    d, f = cfg.d_model, e.d_ff_expert
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": ParamMeta(
            jax.random.normal(ks[0], (d, e.n_experts), dt) * d ** -0.5,
            ("embed", "experts")),
        "wi": ParamMeta(jax.random.normal(ks[1], (e.n_experts, d, f), dt)
                        * d ** -0.5, ("experts", "embed", "ff")),
        "wg": ParamMeta(jax.random.normal(ks[2], (e.n_experts, d, f), dt)
                        * d ** -0.5, ("experts", "embed", "ff")),
        "wo": ParamMeta(jax.random.normal(ks[3], (e.n_experts, f, d), dt)
                        * f ** -0.5, ("experts", "ff", "embed")),
    }
    if e.shared_expert:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.d_ff, gated=True)
    return p


def _capacity(n_tokens: int, e) -> int:
    c = int(n_tokens * e.top_k * e.capacity_factor / e.n_experts)
    return max(4, (c + 3) // 4 * 4)


def moe_apply(p, cfg: ModelConfig, x):
    """x [B, S, D] -> (out [B, S, D], aux-losses dict). Dispatch routing per
    cfg.moe.dispatch ('einsum' global-capacity baseline vs 'local'
    shard_map expert parallelism)."""
    if cfg.moe.dispatch == "local":
        from .partition import current
        ctx = current()
        if ctx is not None and _local_dispatch_applicable(cfg, ctx[0]):
            return moe_apply_local(p, cfg, x, ctx[0])
    return moe_apply_einsum(p, cfg, x)


def moe_apply_einsum(p, cfg: ModelConfig, x):
    """Baseline: GSPMD one-hot/scatter dispatch, GLOBAL capacity."""
    e = cfg.moe
    B, S, D = x.shape
    n_tok = B * S
    cap = _capacity(n_tok, e)
    cd = jnp.dtype(cfg.compute_dtype)

    xt = x.reshape(n_tok, D)
    logits = (xt.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))          # [T, E] fp32
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, e.top_k)   # [T, k]
    if e.top_k > 1:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # position of each (token, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, e.n_experts, dtype=jnp.int32)  # [T,k,E]
    flat = onehot.reshape(n_tok * e.top_k, e.n_experts)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat)      # [T*k, E]
    pos = (pos_in_expert * flat).sum(-1).reshape(n_tok, e.top_k)
    keep = pos < cap
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # dispatch [T, k] -> [E, cap, D] via scatter
    tok_idx = jnp.broadcast_to(jnp.arange(n_tok)[:, None],
                               (n_tok, e.top_k))
    eid = gate_idx.reshape(-1)
    cpos = jnp.where(keep, pos, cap).reshape(-1)           # dropped -> slot cap
    buf = jnp.zeros((e.n_experts, cap + 1, D), cd)
    buf = buf.at[eid, cpos].add(xt.astype(cd)[tok_idx.reshape(-1)])
    buf = hint(buf[:, :cap], "experts", None, "embed")     # [E, cap, D]

    # expert computation (E sharded over "model")
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(cd))
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cd))
    h = jax.nn.silu(g) * h
    h = hint(h, "experts", None, "ff")
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cd))  # [E, cap, D]

    # combine: gather each kept (token, k) result and weight by its gate
    y_tok = y[eid, jnp.clip(cpos, 0, cap - 1)]             # [T*k, D]
    y_tok = y_tok * (gate_vals.reshape(-1, 1).astype(cd))
    out = jnp.zeros((n_tok, D), cd).at[tok_idx.reshape(-1)].add(y_tok)

    if e.shared_expert:
        shared = mlp_apply(p["shared"], cfg, x)        # [B, S, D] (3-D hints)
        out = out + shared.reshape(n_tok, D).astype(cd)

    # aux losses (Switch-style load balance + router z)
    me = probs.mean(0)                                     # [E]
    ce = jnp.zeros((e.n_experts,), jnp.float32).at[gate_idx.reshape(-1)].add(
        1.0) / (n_tok * e.top_k)
    aux = {
        "moe_aux": e.aux_coef * e.n_experts * jnp.sum(me * ce),
        "moe_z": e.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# shard_map local-capacity dispatch (§Perf hillclimb: qwen3-moe x train_4k)
# ---------------------------------------------------------------------------

def _local_dispatch_applicable(cfg: ModelConfig, mesh) -> bool:
    names = mesh.axis_names
    if "model" not in names:
        return False
    if cfg.moe.n_experts % mesh.shape["model"] != 0:
        return False
    return True


def moe_apply_local(p, cfg: ModelConfig, x, mesh):
    """Expert-parallel MoE with PER-DATA-SHARD capacity via shard_map.

    Why (hypothesis confirmed in EXPERIMENTS.md §Perf): the einsum/scatter
    baseline computes each token's position within its expert's capacity as
    a cumsum over the GLOBAL flattened token dim. That dim is sharded over
    ("pod","data"), so XLA lowers the prefix sum into collective-permute
    chains and replicates the dispatch buffers (~80 GB/layer collectives,
    99 GiB temp). Computing capacity per data shard makes routing entirely
    local; the only cross-chip traffic left is
      * the FSDP all-gather of the layer's expert weights over "data", and
      * ONE psum of the combined output [T_local, D] over "model",
    i.e. exactly a tensor-parallel MLP's collective footprint.

    Every (data s, model m) chip: routes its replicated copy of shard s's
    tokens, builds dispatch buffers ONLY for its local experts, runs them,
    scatters results back to token rows, and psums over "model".
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    e = cfg.moe
    B, S, D = x.shape
    cd = jnp.dtype(cfg.compute_dtype)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_mp = mesh.shape["model"]
    e_loc = e.n_experts // n_mp
    has_data = "data" in mesh.axis_names

    # specs must match the rule-engine placement of the expert weights:
    # wi/wg [experts->model, embed->data, ff]; wo [experts->model, ff,
    # embed->data] (ff lost "model" to the expert dim — no axis reuse).
    d_ax = "data" if has_data else None
    wi_spec = P("model", d_ax, None)
    wo_spec = P("model", None, d_ax)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    def body(xb, router, wi, wg, wo):
        # xb [B_loc, S, D] (replicated over model); w* are local shards
        T = xb.shape[0] * S
        xt = xb.reshape(T, D)
        cap = max(4, int(T * e.top_k * e.capacity_factor / e.n_experts)
                  // 4 * 4)
        m = jax.lax.axis_index("model")
        e0 = m * e_loc

        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, e.top_k)     # [T, k]
        if e.top_k > 1:
            gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

        # keep only MY experts; position via LOCAL cumsum per expert
        local_e = gate_idx - e0                                  # [T, k]
        mine = (local_e >= 0) & (local_e < e_loc)
        le = jnp.where(mine, local_e, e_loc)                     # dump row
        onehot = jax.nn.one_hot(le.reshape(-1), e_loc + 1,
                                dtype=jnp.int32)                 # [T*k, E1]
        pos = (jnp.cumsum(onehot, axis=0) - onehot)
        cpos = (pos * onehot).sum(-1)                            # [T*k]
        keep = mine.reshape(-1) & (cpos < cap)
        cpos = jnp.where(keep, cpos, cap)
        le_flat = jnp.where(keep, le.reshape(-1), e_loc)

        # FSDP: un-shard my experts' weights over "data"
        if has_data:
            wi_f = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wg_f = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        else:
            wi_f, wg_f, wo_f = wi, wg, wo

        tok = jnp.broadcast_to(jnp.arange(T)[:, None],
                               (T, e.top_k)).reshape(-1)
        buf = jnp.zeros((e_loc + 1, cap + 1, D), cd)
        buf = buf.at[le_flat, cpos].add(xt.astype(cd)[tok])
        buf = buf[:e_loc, :cap]

        h = jnp.einsum("ecd,edf->ecf", buf, wi_f.astype(cd))
        g = jnp.einsum("ecd,edf->ecf", buf, wg_f.astype(cd))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                       wo_f.astype(cd))                          # [E1,cap,D]

        y_tok = y[jnp.clip(le_flat, 0, e_loc - 1),
                  jnp.clip(cpos, 0, cap - 1)]                    # [T*k, D]
        w = jnp.where(keep, gate_vals.reshape(-1), 0.0).astype(cd)
        partial = jnp.zeros((T, D), cd).at[tok].add(y_tok * w[:, None])
        out = jax.lax.psum(partial, "model")

        # aux losses: identical on every model chip; average over data
        me_ = probs.mean(0)
        ce_ = jnp.zeros((e.n_experts,), jnp.float32).at[
            gate_idx.reshape(-1)].add(1.0) / (T * e.top_k)
        aux_lb = e.aux_coef * e.n_experts * jnp.sum(me_ * ce_)
        aux_z = e.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2)
        if dp:
            aux_lb = jax.lax.pmean(aux_lb, dp)
            aux_z = jax.lax.pmean(aux_z, dp)
        return out.reshape(xb.shape), aux_lb, aux_z

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(), wi_spec, wi_spec, wo_spec),
        out_specs=(P(dp_spec, None, None), P(), P()),
        check_vma=False)
    out, aux_lb, aux_z = fn(x, p["router"], p["wi"], p["wg"], p["wo"])
    aux = {"moe_aux": aux_lb, "moe_z": aux_z}
    if e.shared_expert:
        out = out + mlp_apply(p["shared"], cfg, x).astype(out.dtype)
    return out, aux
