"""Mixture-of-Experts: top-k router + two execution paths.

* ``dense`` — every expert on every token, combined by router weights.
  Exact (no capacity drops); O(E/k) overcompute.  Reference/oracle path and
  the default for tiny smoke configs.
* ``ep`` — expert parallelism over the mesh ``model`` axis via shard_map:
  sort-based capacity dispatch -> all_to_all -> grouped per-expert matmul ->
  all_to_all return -> weighted combine.  This is the DeepSeek/GShard-style
  schedule adapted to TPU ICI: the dispatch buffers are the dominant
  collective bytes at large E (visible in the roofline's all-to-all term).

Sequence enters sequence-sharded over the model axis (SP), so each device
dispatches only its local tokens — dispatch traffic per device is
T_local * k * d_model, independent of the expert count.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import context as dctx
from repro.models import common


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init(key, cfg, dtype=jnp.float32):
    from repro.configs.base import eff_d_expert
    m = cfg.moe
    d = cfg.d_model
    fe = eff_d_expert(cfg)
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": jax.random.normal(ks[0], (d, m.num_experts), jnp.float32) * scale,
        "wi": jax.random.normal(ks[1], (m.num_experts, d, fe), dtype) * scale,
        "wg": jax.random.normal(ks[2], (m.num_experts, d, fe), dtype) * scale,
        "wo": jax.random.normal(ks[3], (m.num_experts, fe, d), dtype)
              / jnp.sqrt(fe),
    }
    if m.num_shared_experts:
        fs = fe * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": common.linear_init(k1, d, fs, dtype=dtype),
            "wg": common.linear_init(k2, d, fs, dtype=dtype),
            "wo": common.linear_init(k3, fs, d, dtype=dtype),
        }
    return p


def _route(x2d, router_w, m):
    """x2d: (T, D) -> gates (T, k), sel (T, k), aux_loss (scalar, f32)."""
    logits = (x2d.astype(jnp.float32) @ router_w)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + router z-loss
    me = probs.mean(axis=0)
    onehot = jax.nn.one_hot(sel, m.num_experts, dtype=jnp.float32).sum(axis=1)
    ce = onehot.mean(axis=0) / m.top_k
    lb = m.num_experts * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, sel, m.router_aux_weight * lb + 1e-4 * z


def _expert_ffn(h_tokens, wi, wg, wo, act):
    """h_tokens: (E, C, D); w*: (E, D, F)/(E, F, D) -> (E, C, D)."""
    hi = jnp.einsum("ecd,edf->ecf", h_tokens, wi)
    hg = jnp.einsum("ecd,edf->ecf", h_tokens, wg)
    h = common.act_fn(act)(hg) * hi
    return jnp.einsum("ecf,efd->ecd", h, wo)


# ---------------------------------------------------------------------------
# Dense path (reference)
# ---------------------------------------------------------------------------

def apply_dense(params, cfg, x):
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    gates, sel, aux = _route(xf, params["router"], m)
    hi = jnp.einsum("td,edf->tef", xf, params["wi"].astype(x.dtype))
    hg = jnp.einsum("td,edf->tef", xf, params["wg"].astype(x.dtype))
    h = common.act_fn(cfg.act)(hg) * hi
    y_all = jnp.einsum("tef,efd->ted", h, params["wo"].astype(x.dtype))
    mask = jax.nn.one_hot(sel, m.num_experts, dtype=jnp.float32)  # (T,k,E)
    comb = jnp.einsum("tk,tke->te", gates, mask).astype(x.dtype)
    y = jnp.einsum("te,ted->td", comb, y_all)
    y = y + _shared(params, cfg, xf)
    return y.reshape(b, s, d), aux


def _shared(params, cfg, xf):
    if "shared" not in params:
        return 0.0
    h = common.linear_apply(params["shared"]["wi"], xf, quant=cfg.quant, bf16_grads=cfg.bf16_grads)
    g = common.linear_apply(params["shared"]["wg"], xf, quant=cfg.quant, bf16_grads=cfg.bf16_grads)
    return common.linear_apply(params["shared"]["wo"],
                               common.act_fn(cfg.act)(g) * h, quant=cfg.quant, bf16_grads=cfg.bf16_grads)


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map + all_to_all)
# ---------------------------------------------------------------------------

def _ep_local(xf, router_w, wi, wg, wo, *, cfg, n_shards, ep_axis):
    """Per-device body. xf: (T_loc, D); wi/wg/wo: local (E_loc, ...) shards."""
    m = cfg.moe
    t, d = xf.shape
    e, k = m.num_experts, m.top_k
    e_loc = e // n_shards
    cap = int(-(-t * k * m.capacity_factor // e))  # per (device, expert)

    gates, sel, aux = _route(xf, router_w, m)
    fe = sel.reshape(-1)                               # (T*k,) expert ids
    ft = jnp.arange(t * k) // k                        # token ids
    fg = gates.reshape(-1)
    order = jnp.argsort(fe)                            # stable
    fe_s, ft_s, fg_s = fe[order], ft[order], fg[order]
    counts = jnp.bincount(fe, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[fe_s]
    valid = pos < cap
    slot = jnp.where(valid, fe_s * cap + pos, e * cap)  # sentinel drops
    buf = jnp.zeros((e * cap + 1, d), xf.dtype).at[slot].set(xf[ft_s])[:-1]

    # dispatch: rows e_loc*j .. e_loc*(j+1) go to shard j
    buf = buf.reshape(n_shards, e_loc * cap, d)
    if m.dispatch_fp8:
        # DeepSeek-V3-style fp8 dispatch: halves the dominant a2a wire term;
        # post-norm activations are O(1) so e4m3's +-448 range is ample.
        recv = jax.lax.all_to_all(buf.astype(jnp.float8_e4m3fn), ep_axis,
                                  split_axis=0, concat_axis=0,
                                  tiled=True).astype(xf.dtype)
    else:
        recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    tok = recv.reshape(n_shards, e_loc, cap, d).transpose(1, 0, 2, 3)
    tok = tok.reshape(e_loc, n_shards * cap, d)
    y = _expert_ffn(tok, wi.astype(xf.dtype), wg.astype(xf.dtype),
                    wo.astype(xf.dtype), cfg.act)
    y = y.reshape(e_loc, n_shards, cap, d).transpose(1, 0, 2, 3)
    y = y.reshape(n_shards, e_loc * cap, d)
    back = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                              tiled=True).reshape(e * cap, d)

    gathered = back[jnp.minimum(slot, e * cap - 1)]    # (T*k, D)
    w = (fg_s * valid).astype(xf.dtype)[:, None]
    out = jnp.zeros((t, d), xf.dtype).at[ft_s].add(gathered * w)
    out = out + _shared_local(xf, cfg)
    return out, jax.lax.pmean(aux, ep_axis)


def _shared_local(xf, cfg):
    return 0.0  # shared experts are handled outside the shard_map (TP path)


def apply_ep(params, cfg, x, mesh):
    """x: (B, S, D) batch-sharded + seq-sharded over 'model' (SP)."""
    m = cfg.moe
    b, s, d = x.shape
    dp = dctx.data_axes(mesh)
    n_shards = mesh.shape["model"]
    assert m.num_experts % n_shards == 0, (m.num_experts, n_shards)

    def body(xloc, router_w, wi, wg, wo):
        bl, sl, _ = xloc.shape
        out, aux = _ep_local(xloc.reshape(-1, d), router_w, wi, wg, wo,
                             cfg=cfg, n_shards=n_shards, ep_axis="model")
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, "model", None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp, "model", None), P()),
    )(x, params["router"], params["wi"], params["wg"], params["wo"])
    if "shared" in params:
        xf = x.reshape(-1, d)
        out = out + _shared(params, cfg, xf).reshape(b, s, d)
    return out, aux


# ---------------------------------------------------------------------------
# Decode path: tokens are few (B x 1) — replicate tokens over the model axis,
# each shard runs its local experts on the tokens routed to them, psum.
# No all_to_all: dispatch traffic is just the output psum (B x D per layer).
# ---------------------------------------------------------------------------

def _ep_decode_local(xf, router_w, wi, wg, wo, *, cfg, n_shards, ep_axis):
    m = cfg.moe
    t, d = xf.shape
    e, k = m.num_experts, m.top_k
    e_loc = e // n_shards
    shard = jax.lax.axis_index(ep_axis)
    e_off = shard * e_loc
    cap = max(1, int(-(-t * k * max(m.capacity_factor, 4.0) // e)))

    gates, sel, aux = _route(xf, router_w, m)
    fe = sel.reshape(-1) - e_off                      # local expert ids
    ft = jnp.arange(t * k) // k
    fg = gates.reshape(-1)
    local = (fe >= 0) & (fe < e_loc)
    fe_key = jnp.where(local, fe, e_loc)              # sentinel bucket
    order = jnp.argsort(fe_key)
    fe_s, ft_s, fg_s, loc_s = (fe_key[order], ft[order], fg[order], local[order])
    counts = jnp.bincount(fe_key, length=e_loc + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[fe_s]
    valid = loc_s & (pos < cap)
    slot = jnp.where(valid, fe_s * cap + pos, e_loc * cap)
    buf = jnp.zeros((e_loc * cap + 1, d), xf.dtype).at[slot].set(xf[ft_s])[:-1]
    y = _expert_ffn(buf.reshape(e_loc, cap, d), wi.astype(xf.dtype),
                    wg.astype(xf.dtype), wo.astype(xf.dtype), cfg.act)
    y = y.reshape(e_loc * cap, d)
    gathered = y[jnp.minimum(slot, e_loc * cap - 1)]
    w = (fg_s * valid).astype(xf.dtype)[:, None]
    out = jnp.zeros((t, d), xf.dtype).at[ft_s].add(gathered * w)
    out = jax.lax.psum(out, ep_axis)
    return out, jax.lax.pmean(aux, ep_axis)


def apply_ep_decode(params, cfg, x, mesh):
    m = cfg.moe
    b, s, d = x.shape
    dp = dctx.data_axes(mesh)
    n_shards = mesh.shape["model"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if b % dp_size == 0 else None

    def body(xloc, router_w, wi, wg, wo):
        bl, sl, _ = xloc.shape
        out, aux = _ep_decode_local(xloc.reshape(-1, d), router_w, wi, wg, wo,
                                    cfg=cfg, n_shards=n_shards, ep_axis="model")
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
    )(x, params["router"], params["wi"], params["wg"], params["wo"])
    if "shared" in params:
        out = out + _shared(params, cfg, x.reshape(-1, d)).reshape(b, s, d)
    return out, aux


def apply(params, cfg, x):
    """Dispatch on impl + ambient mesh + shape."""
    m = cfg.moe
    mesh = dctx.current_mesh()
    impl = m.impl
    n = dctx.model_axis_size(mesh)
    ep_ok = (mesh is not None and n > 1 and m.num_experts % n == 0
             and m.num_experts >= n)
    if impl == "auto":
        impl = "ep" if ep_ok else "dense"
    if impl == "ep" and ep_ok:
        dp_size = 1
        for a in dctx.data_axes(mesh):
            dp_size *= mesh.shape[a]
        if (x.shape[1] % n == 0 and x.shape[1] >= n
                and x.shape[0] % dp_size == 0):
            return apply_ep(params, cfg, x, mesh)
        return apply_ep_decode(params, cfg, x, mesh)
    return apply_dense(params, cfg, x)
