"""Mixture-of-Experts FFN with the paper's two dispatch disciplines.

Theorem 1 at pod scale (DESIGN.md §3): a token routed to top-k experts is a
multi-key tuple (f_MK = router).

* ``dispatch="sn"`` — shared-nothing: the GShard/Switch dispatch-combine
  einsum pair.  Each token is *copied* into the capacity buffer of every
  expert it routes to (duplication factor ~ top_k) and the SPMD partitioner
  moves the copies across the expert axis (all-to-all family) — the
  SPE-default baseline, like the paper's Flink.
* ``dispatch="vsn"`` — virtual shared-nothing: shard_map owner-computes.
  Tokens never move: each (data, expert)-shard already observes its data
  shard's token block (the replicated view = shared TB), masks in the tokens
  routed to *its* experts, computes, and the partial outputs meet in one
  psum over the expert axis.  No duplication, no capacity-drop skew from
  cross-shard imbalance, deterministic.

Both paths share the router and per-expert SwiGLU weights; capacity
overflow is counted (``aux["dropped"]``), never silent.  Shared experts
(deepseek-style) are plain TP MLPs applied outside the dispatch.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.models.config import ModelConfig
from repro.models.layers import init_dense, swiglu
from repro.models.sharding import current_mesh, resolve, shard


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    p = {
        "router": init_dense(ks[0], (d, m.n_experts), dtype=jnp.float32),
        "wg": init_dense(ks[1], (m.n_experts, d, m.d_ff_expert), dtype=dtype),
        "wu": init_dense(ks[2], (m.n_experts, d, m.d_ff_expert), dtype=dtype),
        "wd": init_dense(ks[3], (m.n_experts, m.d_ff_expert, d), dtype=dtype),
    }
    if m.n_shared:
        f = m.d_ff_expert * m.n_shared
        p["shared_wg"] = init_dense(ks[4], (d, f), dtype=dtype)
        p["shared_wu"] = init_dense(ks[5], (d, f), dtype=dtype)
        p["shared_wd"] = init_dense(ks[6], (f, d), dtype=dtype)
    return p


def _route(x, router, top_k: int):
    """Router: returns (weights [N, k], experts [N, k]) with renormalized
    softmax over the selected experts (deepseek/qwen3 convention)."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w.astype(jnp.float32), idx


def _expert_ffn(xe, wg, wu, wd):
    g = jnp.einsum("ecd,edf->ecf", xe, wg)
    u = jnp.einsum("ecd,edf->ecf", xe, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


# --------------------------------------------------------------------------
# SN: GShard dispatch/combine einsums (token copies cross the expert axis)
# --------------------------------------------------------------------------

def _sn_moe(p, x2, cfg: ModelConfig):
    """Sort-based dispatch: each (token, choice) pair is *copied* into its
    expert's capacity buffer (duplication = top_k, Theorem 1); the copies
    cross the expert axis under GSPMD (all-to-all family)."""
    m = cfg.moe
    n, d = x2.shape
    e = m.n_experts
    cap = max(int(m.top_k * n * m.capacity_factor / e), 1)

    w, idx = _route(x2, p["router"], m.top_k)            # [N,k]
    nk = n * m.top_k
    flat_e = idx.reshape(nk)
    flat_t = jnp.repeat(jnp.arange(n), m.top_k)
    flat_w = w.reshape(nk)

    order = jnp.argsort(flat_e, stable=True)             # FIFO per expert
    se, stok, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=e)
    start = jnp.cumsum(counts) - counts                  # exclusive prefix
    pos = jnp.arange(nk) - start[se]                     # slot within expert
    keep = pos < cap
    dropped = nk - jnp.sum(keep.astype(jnp.int32))

    slot = jnp.where(keep, se * cap + pos, e * cap)      # overflow -> drop
    xe_flat = jnp.zeros((e * cap, d), x2.dtype).at[slot].set(
        x2[stok], mode="drop")
    xe = shard(xe_flat.reshape(e, cap, d), "experts", None, "embed")
    he = _expert_ffn(xe, p["wg"], p["wu"], p["wd"])
    he = shard(he, "experts", None, "embed")
    he_flat = he.reshape(e * cap, d)
    contrib = he_flat[jnp.minimum(slot, e * cap - 1)].astype(jnp.float32)
    contrib = contrib * (sw * keep)[:, None]
    y = jnp.zeros((n, d), jnp.float32).at[stok].add(contrib)
    return y.astype(x2.dtype), dropped


# --------------------------------------------------------------------------
# VSN: owner-computes over the shared token block (shard_map + psum)
# --------------------------------------------------------------------------

def _vsn_body(x_loc, router, wg, wu, wd, *, cfg: ModelConfig, axis: str,
              n_shards: int):
    m = cfg.moe
    n, d = x_loc.shape
    e_loc = m.n_experts // n_shards
    shard_id = jax.lax.axis_index(axis)
    lo = shard_id * e_loc
    cap = max(int(m.top_k * n * m.capacity_factor / m.n_experts), 1)

    w, idx = _route(x_loc, router, m.top_k)              # [N,k] global ids
    # responsibility mask: my experts only (f_mu(key) == j, Alg. 4 L23)
    local = (idx >= lo) & (idx < lo + e_loc)             # [N,k]
    # [E_loc, N]: which tokens hit my expert el
    hit = jnp.zeros((e_loc, n), bool)
    wmat = jnp.zeros((e_loc, n), jnp.float32)
    for kk in range(m.top_k):                            # top_k is small/static
        sel = jnp.where(local[:, kk], idx[:, kk] - lo, e_loc)
        oh = jax.nn.one_hot(sel, e_loc, dtype=jnp.float32).T  # [E_loc, N]
        hit = hit | (oh > 0)
        wmat = wmat + oh * w[:, kk][None, :]

    order = jnp.argsort(~hit, axis=1, stable=True)       # routed-first, FIFO
    take = order[:, :cap]                                # [E_loc, C]
    took = jnp.take_along_axis(hit, take, axis=1)        # [E_loc, C]
    dropped = jnp.sum(hit) - jnp.sum(took)
    xe = x_loc[take] * took[..., None].astype(x_loc.dtype)
    he = _expert_ffn(xe, wg, wu, wd)                     # [E_loc, C, D]
    we = jnp.take_along_axis(wmat, take, axis=1) * took  # [E_loc, C]
    y = jnp.zeros((n, d), jnp.float32)
    y = y.at[take.reshape(-1)].add(
        (he.astype(jnp.float32) * we[..., None]).reshape(-1, d))
    # partial outputs meet across the expert axis: the one collective.
    # §Perf A1: reduce in bf16 — each token receives <= top_k non-zero
    # partials, so bf16 accumulation is safe and halves the wire bytes.
    y = jax.lax.psum(y.astype(jnp.bfloat16), axis)
    return y.astype(x_loc.dtype), jax.lax.psum(dropped, axis)


def _vsn_moe(p, x2, cfg: ModelConfig):
    mesh = current_mesh()
    m = cfg.moe
    if mesh is None:
        # single-device smoke path: same math, one "shard" with all experts
        import numpy as np
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
        axis = "model"
        dp_spec = P()
    else:
        axis = "model"
        dp_spec = resolve("batch")

    n_shards = mesh.shape[axis]
    body = functools.partial(_vsn_body, cfg=cfg, axis=axis,
                             n_shards=n_shards)
    x_spec = P(dp_spec[0] if len(dp_spec) else None, None)
    e_spec = P(axis, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), e_spec, e_spec, e_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(x2, p["router"], p["wg"], p["wu"], p["wd"])


def moe_forward(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y, dropped_count)."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    m = cfg.moe
    if m.dispatch == "sn":
        y, dropped = _sn_moe(p, x2, cfg)
    else:
        y, dropped = _vsn_moe(p, x2, cfg)
    if m.n_shared:
        y = y + swiglu(x2, p["shared_wg"], p["shared_wu"], p["shared_wd"])
    return y.reshape(b, s, d), dropped
