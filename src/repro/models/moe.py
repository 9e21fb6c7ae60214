"""Mixture-of-Experts block: token-choice top-k routing with per-expert
capacity, expert-parallel over the 'model' mesh axis.

Dispatch strategy (GSPMD-friendly, no manual all-to-all):
  activations are kept replicated across the 'model' axis; each expert shard
  gathers the top-C tokens routed to its local experts, runs the expert FFN
  [E_local, C, d], and scatter-adds weighted results back, which XLA lowers
  to a psum across the expert axis.  Capacity selection is a per-expert
  ``top_k`` over token scores (static shapes — dropped tokens beyond C fall
  back to the residual path, exactly GShard semantics).

Experts that do not divide the model axis are padded with phantom experts
(router logits -inf -> zero combine weight; ~E_pad/E extra expert FLOPs,
recorded in DESIGN.md).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.init import ParamSpec
from repro.sharding.api import constrain, current_context


def padded_n_experts(cfg: ModelConfig) -> int:
    assert cfg.moe is not None
    e = cfg.moe.n_experts
    ctx = current_context()
    tp = 1
    if ctx is not None:
        tp = ctx.mesh.shape.get("model", 1)
    return -(-e // tp) * tp


def moe_specs(cfg: ModelConfig, prefix: str, stacked=None, n_experts_padded=None) -> dict:
    assert cfg.moe is not None
    m = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert or cfg.d_ff
    e = n_experts_padded or m.n_experts
    lead = (stacked,) if stacked else ()
    lax_ = ("layers",) if stacked else ()
    dt = cfg.param_dtype
    specs = {
        f"{prefix}/router": ParamSpec(lead + (d, e), lax_ + ("embed_nofsdp", "experts"),
                                      "lecun", dt),
        f"{prefix}/we_gate": ParamSpec(lead + (e, d, f), lax_ + ("experts", "embed", None),
                                       "lecun", dt),
        f"{prefix}/we_up": ParamSpec(lead + (e, d, f), lax_ + ("experts", "embed", None),
                                     "lecun", dt),
        f"{prefix}/we_down": ParamSpec(lead + (e, f, d), lax_ + ("experts", None, "embed"),
                                       "lecun", dt),
    }
    if m.n_shared_experts:
        fs = m.n_shared_experts * f
        specs.update({
            f"{prefix}/ws_gate": ParamSpec(lead + (d, fs), lax_ + ("embed", "ffn"), "lecun", dt),
            f"{prefix}/ws_up": ParamSpec(lead + (d, fs), lax_ + ("embed", "ffn"), "lecun", dt),
            f"{prefix}/ws_down": ParamSpec(lead + (fs, d), lax_ + ("ffn", "embed"), "lecun", dt),
            f"{prefix}/shared_gate": ParamSpec(lead + (d, 1), lax_ + ("embed_nofsdp", None),
                                               "lecun", dt),
        })
    return specs


_CHUNK_TOKENS = 8192   # per-device token budget for dispatch buffers


def _shard_map_combine(ctx, ye, sel_idx, t, d):
    """Scatter expert outputs locally per expert shard, then psum tokens.

    RETIRED (§Perf MOE-3): measured 2x MORE wire than the plain scatter-add
    under GSPMD on qwen3 train_4k — kept for the record; not called."""
    import jax
    from jax.sharding import PartitionSpec as P

    def combine(ye_l, idx_l):
        # ye_l: [e_local, C, d]; idx_l: [e_local, C]
        out_l = jnp.zeros((t, d), ye_l.dtype).at[idx_l.reshape(-1)].add(
            ye_l.reshape(-1, d))
        return jax.lax.psum(out_l, "model")

    other = tuple(a for a in ctx.mesh.axis_names if a != "model")
    fn = jax.shard_map(
        combine, mesh=ctx.mesh,
        in_specs=(P("model", None, None), P("model", None)),
        out_specs=P(), check_vma=False)
    return fn(ye, sel_idx)


def moe_block(
    cfg: ModelConfig, x: jax.Array, p: dict, prefix: str, *, train: bool
) -> Tuple[jax.Array, dict]:
    """x: [b, s, d] -> (out [b, s, d], aux losses dict).

    Long sequences are processed in sequential SEQ chunks (lax.scan) so the
    [E, C, d] dispatch buffers stay bounded regardless of sequence length —
    capacity C scales with the chunk (GShard-style local capacity).  Chunking
    along seq keeps the batch dim's 'data' sharding intact."""
    assert cfg.moe is not None
    b, s, d = x.shape
    ctx = current_context()
    dp = 1
    if ctx is not None:
        for a in ctx.data_axes:
            dp *= ctx.mesh.shape.get(a, 1)
    per_dev = (b * s) // max(dp, 1)
    n_chunks = 1
    while (per_dev // n_chunks > _CHUNK_TOKENS and s % (n_chunks * 2) == 0
           and s // (n_chunks * 2) >= 1):
        n_chunks *= 2
    if n_chunks > 1:
        sc = s // n_chunks
        xc = jnp.moveaxis(x.reshape(b, n_chunks, sc, d), 1, 0)

        def chunk_fn(carry, xci):
            out_i, aux_i = _moe_tokens(cfg, xci, p, prefix, train=train)
            return carry, (out_i, aux_i)

        if cfg.probe_unroll:  # cost-probe mode: no hidden while-loop work
            outs, auxs = [], []
            for c in range(n_chunks):
                _, (o_c, a_c) = chunk_fn(0, xc[c])
                outs.append(o_c)
                auxs.append(a_c)
            outs = jnp.stack(outs)
            auxs = {k: jnp.stack([a[k] for a in auxs]) for k in auxs[0]}
        else:
            _, (outs, auxs) = jax.lax.scan(chunk_fn, 0, xc)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, d)
        aux = {k: jnp.mean(v) for k, v in auxs.items()}
        return out, aux
    return _moe_tokens(cfg, x, p, prefix, train=train)


def _moe_tokens(
    cfg: ModelConfig, x: jax.Array, p: dict, prefix: str, *, train: bool
) -> Tuple[jax.Array, dict]:
    """x: [b, s, d] chunk -> (out [b, s, d], aux)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    w_router = p[f"{prefix}/router"]
    e_pad = w_router.shape[-1]
    e_real = m.n_experts

    logits = jnp.einsum("td,de->te", xf, w_router.astype(xf.dtype)).astype(jnp.float32)
    if e_pad > e_real:
        phantom = jnp.arange(e_pad) >= e_real
        logits = jnp.where(phantom[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)                     # [t, e]

    top_p, top_i = jax.lax.top_k(probs, m.top_k)                # [t, k]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # per-(token, expert) combine weight (0 if not routed)
    onehot = jax.nn.one_hot(top_i, e_pad, dtype=jnp.float32)    # [t, k, e]
    combine_te = jnp.einsum("tk,tke->te", top_p, onehot)        # [t, e]

    # capacity: top-C tokens per expert by combine weight
    cf = m.capacity_factor if train else m.eval_capacity_factor
    cap = max(int(t * m.top_k * cf / e_real), 4)
    cap = min(cap, t)
    scores_et = combine_te.T                                    # [e, t]
    sel_w, sel_idx = jax.lax.top_k(scores_et, cap)              # [e, C]
    sel_w = jnp.where(sel_w > 0, sel_w, 0.0)                    # drop non-routed

    xe = jnp.take(xf, sel_idx.reshape(-1), axis=0)              # [e*C, d]
    xe = xe.reshape(e_pad, cap, d)
    xe = constrain(xe, "experts", "expert_cap", None)

    wg = p[f"{prefix}/we_gate"].astype(xe.dtype)
    wu = p[f"{prefix}/we_up"].astype(xe.dtype)
    wd = p[f"{prefix}/we_down"].astype(xe.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
        "ecd,edf->ecf", xe, wu)
    h = constrain(h, "experts", "expert_cap", None)
    ye = jnp.einsum("ecf,efd->ecd", h, wd)                      # [e, C, d]
    ye = ye * sel_w[..., None].astype(ye.dtype)

    # combine: scatter-add back to tokens (psum across expert shards).
    # GSPMD all-reduces the [E*C, d] dispatch buffer here (~5x the minimal
    # [t, d] wire) — §Perf MOE-3 tried an explicit shard_map local-scatter +
    # psum and MEASURED WORSE (2.2 -> 4.1 TiB: the replicated-out psum and
    # its backward gathers dominate); the scatter formulation stands.
    out = jnp.zeros((t, d), ye.dtype).at[sel_idx.reshape(-1)].add(
        ye.reshape(-1, d))
    out = constrain(out, "batch", None)

    # shared experts (always-on) + learned gate (qwen2-moe style)
    if m.n_shared_experts:
        from repro.models.mlp import mlp
        xs = x
        g = jax.nn.silu(jnp.einsum("bsd,df->bsf", xs, p[f"{prefix}/ws_gate"].astype(xs.dtype)))
        u = jnp.einsum("bsd,df->bsf", xs, p[f"{prefix}/ws_up"].astype(xs.dtype))
        hs = constrain(g * u, "batch", "seq_nosp", "ffn")
        ys = jnp.einsum("bsf,fd->bsd", hs, p[f"{prefix}/ws_down"].astype(xs.dtype))
        gate = jax.nn.sigmoid(
            jnp.einsum("bsd,do->bso", xs, p[f"{prefix}/shared_gate"].astype(xs.dtype)))
        out = out + (gate * ys).reshape(t, d)

    # aux losses: load balance (Switch) + router z-loss
    me = jnp.mean(combine_te, axis=0) * e_real                  # frac prob mass
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, e_pad, dtype=jnp.float32), 1), axis=0) * e_real / m.top_k
    aux = {
        "moe_load_balance": jnp.sum(me[:e_real] * ce[:e_real]) / e_real,
        "moe_z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    return out.reshape(b, s, d).astype(x.dtype), aux
