"""Mixture-of-Experts FFN with shard-local sort-based dispatch.

Tokens are routed *locally on each shard* — every shard sorts its own
tokens by expert, packs them into capacity-bounded per-expert segments
with pure gathers (no O(T^2) one-hot dispatch einsum), and runs batched
expert matmuls.  Three sharded paths, selected by the active strategy
(DESIGN.md §5 / distributed.sharding):

  token path (fsdp / fsdp_dp / tp_sp) — tokens arrive pre-sharded over
      the token axes; expert weights are ZeRO-gathered inside the
      shard_map; if TP is on, expert-F partials psum once at the end.
  megatron path (megatron_sp) — the residual stream is sequence-sharded
      over 'model': the body all-gathers the sequence once, routes the
      full local batch identically on every model rank, computes with
      the F-shard, and returns via psum_scatter — one AG + one RS of the
      activations per MoE layer, collective-free inside.

Without an active mesh (CPU smoke tests) the same body runs unsharded.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import _dense_init, init_linear
from repro.distributed import sharding as shd


def init_moe(key, cfg: ArchConfig):
    assert cfg.moe is not None
    m, d = cfg.moe, cfg.d_model
    ks = jax.random.split(key, 7)
    dt = cfg.param_dtype
    E, F = m.n_routed, m.d_ff_expert
    params: dict[str, Any] = {
        "router": _dense_init(ks[0], d, (d, E), jnp.float32),
        "w1": _dense_init(ks[1], d, (E, d, F), dt),
        "w3": _dense_init(ks[2], d, (E, d, F), dt),
        "w2": _dense_init(ks[3], F, (E, F, d), dt),
    }
    specs = {
        "router": P(None, None),
        "w1": P(None, "fsdp_expert", "tp"),
        "w3": P(None, "fsdp_expert", "tp"),
        "w2": P(None, "tp", "fsdp_expert"),
    }
    if m.n_shared:
        Fs = m.n_shared * F  # fused shared experts (mathematically identical)
        params.update({
            "sw1": init_linear(ks[4], d, Fs, dt),
            "sw3": init_linear(ks[5], d, Fs, dt),
            "sw2": init_linear(ks[6], Fs, d, dt),
        })
        specs.update({"sw1": P("fsdp_expert", "tp"),
                      "sw3": P("fsdp_expert", "tp"),
                      "sw2": P("tp", "fsdp_expert")})
    return params, specs


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k / m.n_routed * m.capacity_factor)
    c = max(8, min(n_tokens, (c + 7) // 8 * 8))
    return c


def _gather_weights(fsdp_axes, tp_axis, router, w1, w3, w2, shared):
    """ZeRO-3: reassemble the expert weights' storage shards (the TP dim,
    if any, stays sharded — it is contracted with a psum)."""
    if fsdp_axes:
        w1 = jax.lax.all_gather(w1, fsdp_axes, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axes, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=2, tiled=True)
        if shared:
            sw1, sw3, sw2 = shared
            sw1 = jax.lax.all_gather(sw1, fsdp_axes, axis=0, tiled=True)
            sw3 = jax.lax.all_gather(sw3, fsdp_axes, axis=0, tiled=True)
            sw2 = jax.lax.all_gather(sw2, fsdp_axes, axis=1, tiled=True)
            shared = (sw1, sw3, sw2)
    return router, w1, w3, w2, shared


def _moe_math(cfg: ArchConfig, x, router, w1, w3, w2, shared,
              reduce_axes):
    """Shard-local routing + expert compute.  x: (T, D).  Returns the
    (possibly TP-partial) output and psum-averaged aux losses."""
    m = cfg.moe
    T, D = x.shape
    E = m.n_routed
    C = _capacity(T, cfg)

    # ---- routing (fp32) ----
    logits = x.astype(jnp.float32) @ router          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)       # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses ----
    counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac_routed = counts / (T * m.top_k)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac_routed * mean_prob) * m.aux_loss_coef
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))) \
        * m.router_z_coef
    if reduce_axes:
        n = jax.lax.psum(1.0, reduce_axes)
        aux = jax.lax.psum(aux, reduce_axes) / n
        zloss = jax.lax.psum(zloss, reduce_axes) / n

    # ---- sort-based dispatch ----
    e_flat = idx.reshape(-1)                          # (T*k,)
    tok_of_slot = jnp.arange(T * m.top_k) // m.top_k
    order = jnp.argsort(e_flat)                       # stable groups by expert
    sorted_e = e_flat[order]
    sorted_tok = tok_of_slot[order]
    sorted_gate = gates.reshape(-1)[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(T * m.top_k) - first
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # OOB -> dropped

    buf = jnp.zeros((E * C, D), x.dtype).at[slot].add(
        x[sorted_tok], mode="drop").reshape(E, C, D)

    # ---- expert compute (TP on F when sharded; partial over tp) ----
    h = jnp.einsum("ecd,edf->ecf", buf, w1)
    g = jnp.einsum("ecd,edf->ecf", buf, w3)
    h = jax.nn.silu(g) * h
    out_buf = jnp.einsum("ecf,efd->ecd", h, w2).reshape(E * C, D)

    # ---- combine: weighted scatter-add back to token order ----
    padded = jnp.concatenate([out_buf, jnp.zeros((1, D), out_buf.dtype)])
    vals = padded[jnp.where(keep, slot, E * C)]
    vals = vals * (sorted_gate * keep).astype(vals.dtype)[:, None]
    out = jnp.zeros((T, D), vals.dtype).at[sorted_tok].add(vals)

    # ---- shared experts (dense path, fused) ----
    if shared:
        sw1, sw3, sw2 = shared
        hs = jax.nn.silu(x @ sw3) * (x @ sw1)
        out = out + hs @ sw2
    return out, aux, zloss


def _token_body(cfg, fsdp_axes, tp_axis, x, router, w1, w3, w2, *shared):
    """Per-shard MoE over pre-sharded tokens.  x: (T_local, D)."""
    router, w1, w3, w2, shared = _gather_weights(
        fsdp_axes, tp_axis, router, w1, w3, w2, shared)
    out, aux, zloss = _moe_math(cfg, x, router, w1, w3, w2, shared,
                                reduce_axes=fsdp_axes)
    if tp_axis:  # combine TP partials once, for routed + shared together
        out = jax.lax.psum(out, tp_axis)
    return out, aux, zloss


def _megatron_body(cfg, fsdp_axes, tp_axis, x, router, w1, w3, w2,
                   *shared):
    """Sequence-sharded residual stream: AG once, RS once.
    x: (B_local, S_local, D) with S sharded over tp_axis."""
    B, S_loc, D = x.shape
    x_full = jax.lax.all_gather(x, tp_axis, axis=1, tiled=True)
    T = B * x_full.shape[1]
    router, w1, w3, w2, shared = _gather_weights(
        fsdp_axes, tp_axis, router, w1, w3, w2, shared)
    out2, aux, zloss = _moe_math(cfg, x_full.reshape(T, D), router,
                                 w1, w3, w2, shared,
                                 reduce_axes=fsdp_axes)
    out3 = out2.reshape(B, x_full.shape[1], D)
    out = jax.lax.psum_scatter(out3, tp_axis, scatter_dimension=1,
                               tiled=True)
    return out, aux, zloss


def moe_ffn(cfg: ArchConfig, p, x: jax.Array):
    """x: (B, S, D) -> (out, aux_loss).  Dispatch is shard-local."""
    B, S, D = x.shape
    shared = tuple(p[k] for k in ("sw1", "sw3", "sw2") if k in p)
    rules = shd.active_rules()
    if rules is None:
        out, aux, zloss = _moe_math(
            cfg, x.reshape(B * S, D), p["router"], p["w1"], p["w3"],
            p["w2"], shared if shared else None, reduce_axes=None)
        return out.reshape(B, S, D).astype(x.dtype), aux + zloss

    t = rules.table
    fsdp_e = t["fsdp_expert"]
    tp = t["tp"]
    w_specs = [P(None, None),
               P(None, fsdp_e, tp), P(None, fsdp_e, tp),
               P(None, tp, fsdp_e)]
    if shared:
        w_specs += [P(fsdp_e, tp), P(fsdp_e, tp), P(tp, fsdp_e)]

    if rules.strategy == "megatron_sp":
        dp = t["dp"]
        body = functools.partial(_megatron_body, cfg, fsdp_e, tp)
        out, aux, zloss = jax.shard_map(
            body, mesh=rules.mesh,
            in_specs=tuple([P(dp, tp, None)] + w_specs),
            out_specs=(P(dp, tp, None), P(), P()),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"], *shared)
        return out.astype(x.dtype), aux + zloss

    tok = rules.token_axes
    tok_spec = tok if len(tok) > 1 else tok[0]
    body = functools.partial(_token_body, cfg, fsdp_e, tp)
    out, aux, zloss = jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=tuple([P(tok_spec, None)] + w_specs),
        out_specs=(P(tok_spec, None), P(), P()),
        check_vma=False,
    )(x.reshape(B * S, D), p["router"], p["w1"], p["w3"], p["w2"],
      *shared)
    return out.reshape(B, S, D).astype(x.dtype), aux + zloss
