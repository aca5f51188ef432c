"""Composable decoder blocks: GQA/MQA attention (+RoPE, sliding window),
gated MLPs, sort-based MoE, RG-LRU (RecurrentGemma), mLSTM/sLSTM (xLSTM).

Pure-function style: ``init_*`` builds param dicts, ``apply_*`` consumes
them. Everything is written to (a) run a real reduced-config step on CPU,
and (b) lower cleanly under pjit on the production mesh with the specs in
models/sharding.py. Compute dtype is cfg.dtype (bf16 by default); softmax,
recurrence gates and losses run in f32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import ad_checkpoint

from repro.models.config import ModelConfig

Params = Any  # nested dicts of arrays


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Optional mesh context; None mesh → single-device pure JAX."""

    mesh: Any = None
    batch_axes: tuple = ("data",)
    model_axis: str = "model"

    def csp(self, x, *spec):
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / np.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- norms

def init_rmsnorm(cfg) -> Params:
    return {"scale": jnp.ones((cfg.d_model,), _pdtype(cfg))}


def apply_rmsnorm(p, x):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + 1e-6)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- RoPE

def rope(x, positions, theta: float):
    """x [B, S, H, hd], positions int32[B, S] → rotated x (split-half)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs  # [B,S,1,half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention

def init_attn(key, cfg) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    pd = _pdtype(cfg)
    return {
        "wq": _dense_init(k1, (D, H, hd), D, pd),
        "wk": _dense_init(k2, (D, KV, hd), D, pd),
        "wv": _dense_init(k3, (D, KV, hd), D, pd),
        "wo": _dense_init(k4, (H, hd, D), H * hd, pd),
    }


def _online_softmax_attn(q, k, v, qpos, kpos, window: int,
                         chunk_q: int, chunk_kv: int):
    """Chunked causal attention with online softmax (flash-style, pure JAX).

    q, k, v [B,S,H,hd] (kv heads already broadcast to H — a *local slice* of
    a replicated array under tensor parallelism, so GSPMD shards every
    einsum on the flat head axis with no resharding); qpos [B,S];
    kpos [B,Skv] (−1 = empty slot). Never materializes the full score
    matrix: peak intermediate is [B, cq, H, ck].
    """
    B, S, H, hd = q.shape
    Skv = k.shape[1]
    cq = min(chunk_q, S)
    ck = min(chunk_kv, Skv)
    nq, nk = S // cq, Skv // ck
    assert S % cq == 0 and Skv % ck == 0
    scale = 1.0 / np.sqrt(hd)

    qc = jnp.moveaxis(q.reshape(B, nq, cq, H, hd), 1, 0)
    qp = jnp.moveaxis(qpos.reshape(B, nq, cq), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nk, ck, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, ck, H, hd), 1, 0)
    kp = jnp.moveaxis(kpos.reshape(B, nk, ck), 1, 0)

    def q_block(_, q_in):
        qb, qpb = q_in  # [B,cq,H,hd], [B,cq]

        def kv_block(carry, kv_in):
            m, l, acc = carry
            kb, vb, kpb = kv_in
            s = jnp.einsum("bqhd,bkhd->bqhk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = (kpb[:, None, :] <= qpb[:, :, None]) & (kpb[:, None, :] >= 0)
            if window:
                mask &= kpb[:, None, :] > qpb[:, :, None] - window
            s = jnp.where(mask[:, :, None, :], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bqhk,bkhd->bqhd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, cq, H), -1e30, jnp.float32)
        l0 = jnp.zeros((B, cq, H), jnp.float32)
        a0 = jnp.zeros((B, cq, H, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), (kc, vc, kp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, outs = jax.lax.scan(q_block, None, (qc, qp))  # [nq,B,cq,H,hd]
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)
    return out


def _build_cache(k, v, positions, alloc: int):
    """Pack prefill k/v into a (ring) cache of ``alloc`` slots.

    Slot assignment is pos % alloc so subsequent decode steps extend it
    seamlessly (full cache: identity; sliding window: ring buffer)."""
    B, S, KV, hd = k.shape
    take = min(S, alloc)
    kt, vt = k[:, -take:], v[:, -take:]
    pt = positions[0, -take:].astype(jnp.int32)
    slots = pt % alloc
    ck = jnp.zeros((B, alloc, KV, hd), k.dtype).at[:, slots].set(kt)
    cv = jnp.zeros((B, alloc, KV, hd), v.dtype).at[:, slots].set(vt)
    cpos = jnp.full((alloc,), -1, jnp.int32).at[slots].set(pt)
    return {"k": ck, "v": cv, "pos": cpos}


def apply_attn(p, x, cfg: ModelConfig, ctx: ShardCtx, *,
               window: int = 0,
               cache: Optional[Params] = None,
               cur_index=None,
               positions=None,
               want_cache: bool = False,
               s_alloc: int = 0,
               chunk_q: int = 512, chunk_kv: int = 1024):
    """GQA attention. Train/prefill when cache is None; one-token decode
    otherwise (cache: {"k","v","pos"}; pos int32[S_alloc], −1 = empty).
    ``want_cache`` (prefill) additionally returns a cache of ``s_alloc``
    slots (ring-buffered to ``window`` for local attention)."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KV
    dt = _dtype(cfg)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = ctx.csp(q, ctx.batch_axes, None, ctx.model_axis, None)
    k = ctx.csp(k, ctx.batch_axes, None, None, None)

    if cache is None:
        # Broadcast kv heads to the flat H axis via an index-take (NOT a
        # 5D repeat+reshape, which GSPMD cannot re-tile without a full
        # remat): each model shard gathers its head slice from the
        # replicated kv — no collective, and every attention einsum then
        # shards cleanly on H.
        if G > 1:
            head_to_kv = jnp.arange(H, dtype=jnp.int32) // G
            k_rep = jnp.take(k, head_to_kv, axis=2)
            v_rep = jnp.take(v, head_to_kv, axis=2)
        else:
            k_rep, v_rep = k, v
        k_rep = ctx.csp(k_rep, ctx.batch_axes, None, ctx.model_axis, None)
        v_rep = ctx.csp(v_rep, ctx.batch_axes, None, ctx.model_axis, None)
        out = _online_softmax_attn(q, k_rep, v_rep, positions, positions,
                                   window, chunk_q, chunk_kv)
        new_cache = None
        if want_cache:
            alloc = min(s_alloc or S, window) if window else (s_alloc or S)
            new_cache = _build_cache(k, v, positions, alloc)
    else:
        # Decode: S == 1. Write into the (ring) buffer at cur_index.
        S_alloc = cache["k"].shape[1]
        if cache["pos"].ndim == 2:
            # Per-row decode positions (continuous batching): pos is
            # [B, S_alloc] and cur_index is [B] — every slot writes its
            # own ring position and masks by its own timeline, so one
            # batch row can be at token 3 while another is at token 97.
            ci = cur_index.astype(jnp.int32)
            slot = ci % S_alloc
            rows = jnp.arange(B)
            ck_ = cache["k"].at[rows, slot].set(k[:, 0])
            cv_ = cache["v"].at[rows, slot].set(v[:, 0])
            cpos = cache["pos"].at[rows, slot].set(ci)
            kp = cpos[:, None, :]
        else:
            slot = (cur_index % S_alloc).astype(jnp.int32)
            ck_ = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k, slot, axis=1)
            cv_ = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v, slot, axis=1)
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], cur_index[None].astype(jnp.int32), slot, axis=0)
            kp = cpos[None, None, :]
        new_cache = {"k": ck_, "v": cv_, "pos": cpos}
        qg = q.reshape(B, 1, KV, G, hd)
        scale = 1.0 / np.sqrt(hd)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, ck_,
                       preferred_element_type=jnp.float32) * scale
        qp = positions[:, :, None]
        mask = (kp <= qp) & (kp >= 0)
        if window:
            mask = mask & (kp > qp - window)
        s = jnp.where(mask[:, :, None, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqhgk,bkhd->bqhgd", w.astype(dt), cv_,
                         preferred_element_type=jnp.float32)

    out = out.reshape(B, -1, H, hd).astype(dt)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    y = ctx.csp(y, ctx.batch_axes, None, None)
    # Name the post-all-reduce tensor so the remat policy can keep it
    # (§Perf kimi iteration: don't recompute TP collectives in backward).
    y = ad_checkpoint.checkpoint_name(y, "tp_out")
    return y, new_cache


def init_attn_cache(cfg, batch: int, s_alloc: int, window: int) -> Params:
    KV, hd = cfg.n_kv_heads, cfg.head_dim_
    alloc = min(s_alloc, window) if window else s_alloc
    dt = _dtype(cfg)
    return {
        "k": jnp.zeros((batch, alloc, KV, hd), dt),
        "v": jnp.zeros((batch, alloc, KV, hd), dt),
        "pos": jnp.full((alloc,), -1, jnp.int32),
    }


# ---------------------------------------------------------------- MLP

def init_mlp(key, cfg) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    pd = _pdtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(k1, (D, F), D, pd),
            "w_up": _dense_init(k2, (D, F), D, pd),
            "w_down": _dense_init(k3, (F, D), F, pd),
        }
    return {
        "w_up": _dense_init(k1, (D, F), D, pd),
        "w_down": _dense_init(k2, (F, D), F, pd),
    }


def apply_mlp(p, x, cfg, ctx: ShardCtx):
    dt = _dtype(cfg)
    up = x @ p["w_up"].astype(dt)
    up = ctx.csp(up, ctx.batch_axes, None, ctx.model_axis)
    if cfg.mlp_type == "swiglu":
        g = jax.nn.silu(x @ p["w_gate"].astype(dt))
        h = g * up
    elif cfg.mlp_type == "geglu":
        g = jax.nn.gelu(x @ p["w_gate"].astype(dt))
        h = g * up
    else:
        h = jax.nn.gelu(up)
    y = h @ p["w_down"].astype(dt)
    y = ctx.csp(y, ctx.batch_axes, None, None)
    return ad_checkpoint.checkpoint_name(y, "tp_out")


# ---------------------------------------------------------------- MoE

def init_moe(key, cfg) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    pd = _pdtype(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": _dense_init(k1, (D, E), D, pd),
        "w_gate": _dense_init(k2, (E, D, F), D, pd),
        "w_up": _dense_init(k3, (E, D, F), D, pd),
        "w_down": _dense_init(k4, (E, F, D), F, pd),
    }


def _moe_capacity(n_tokens: int, cfg) -> int:
    c = int(np.ceil(n_tokens * cfg.experts_per_token
                    * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _moe_bucketed(xt, gate_w, gate_e, wg, wu, wd, capacity: int, e0: int,
                  dt):
    """Sort-based capacity-bucketed expert dispatch for experts
    [e0, e0+E_loc). xt f32/bf16[T, D]; gate_w f32[T, k]; gate_e int32[T, k].

    Returns the (partial) output [T, D]: sum over this expert range.
    Tokens overflowing an expert's capacity are dropped (standard cf-drop).
    """
    T, k = gate_e.shape
    E_loc = wg.shape[0]
    flat_e = gate_e.reshape(-1)
    order = jnp.argsort(flat_e)                       # [T·k]
    se = flat_e[order]
    run_start = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(T * k, dtype=jnp.int32) - run_start.astype(jnp.int32)
    local_e = se - e0
    valid = (local_e >= 0) & (local_e < E_loc) & (pos < capacity)
    slot = jnp.where(valid, local_e * capacity + pos, E_loc * capacity)
    tok = (order // k).astype(jnp.int32)
    gw = gate_w.reshape(-1)[order]

    # Slot tables (last slot = trash for overflow/foreign experts).
    n_slots = E_loc * capacity + 1
    slot_tok = jnp.zeros((n_slots,), jnp.int32).at[slot].set(tok)
    slot_gw = jnp.zeros((n_slots,), gw.dtype).at[slot].set(
        jnp.where(valid, gw, 0.0))
    slot_live = jnp.zeros((n_slots,), bool).at[slot].set(valid)

    xin = xt[slot_tok[:-1]] * slot_live[:-1, None].astype(xt.dtype)
    xin = xin.reshape(E_loc, capacity, -1)            # [E, C, D]
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, wg.astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", xin, wu.astype(dt))
    y = jnp.einsum("ecf,efd->ecd", g * u, wd.astype(dt))
    y_flat = y.reshape(E_loc * capacity, -1) * slot_gw[:-1, None].astype(y.dtype)

    out = jnp.zeros_like(xt).at[slot_tok[:-1]].add(
        jnp.where(slot_live[:-1, None], y_flat, 0.0).astype(xt.dtype))
    return out


def apply_moe(p, x, cfg, ctx: ShardCtx):
    """Top-k MoE with expert parallelism over the model axis.

    Activations are sharded on the batch axes and replicated across the
    model axis, so each model shard already holds its tokens: it computes
    buckets for its local experts only, and a single psum over the model
    axis combines per-token partial sums (the same all-reduce tensor
    parallelism needs anyway — no all-to-all required; DESIGN.md §5).
    """
    B, S, D = x.shape
    dt = _dtype(cfg)
    xt = x.reshape(B * S, D)
    logits = (xt @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    if ctx.mesh is None or cfg.n_experts % ctx.model_size != 0:
        cap = _moe_capacity(B * S, cfg)
        out = _moe_bucketed(xt, gate_w, gate_e, p["w_gate"], p["w_up"],
                            p["w_down"], cap, 0, dt)
    else:
        from jax.sharding import PartitionSpec as P

        n_model = ctx.model_size
        n_batch = int(np.prod([ctx.mesh.shape[a] for a in ctx.batch_axes]))
        t_local = (B * S) // n_batch
        cap = _moe_capacity(t_local, cfg)
        e_loc = cfg.n_experts // n_model

        def local(xt_l, gw_l, ge_l, wg_l, wu_l, wd_l, eidx):
            e0 = eidx[0] * e_loc
            out = _moe_bucketed(xt_l, gw_l, ge_l, wg_l, wu_l, wd_l,
                                cap, e0, dt)
            return jax.lax.psum(out, ctx.model_axis)

        eidx = jnp.arange(n_model, dtype=jnp.int32)
        ba = ctx.batch_axes
        out = jax.shard_map(
            local, mesh=ctx.mesh,
            in_specs=(P(ba, None), P(ba, None), P(ba, None),
                      P(ctx.model_axis, None, None),
                      P(ctx.model_axis, None, None),
                      P(ctx.model_axis, None, None),
                      P(ctx.model_axis)),
            out_specs=P(ba, None),
            check_vma=False,
        )(xt, gate_w, gate_e, p["w_gate"].astype(dt),
          p["w_up"].astype(dt), p["w_down"].astype(dt), eidx)
        out = ad_checkpoint.checkpoint_name(out, "tp_out")
    return out.reshape(B, S, D), (logits, gate_e)


# ---------------------------------------------------------------- RG-LRU

def init_rglru(key, cfg) -> Params:
    D = cfg.d_model
    w = cfg.rglru_width or D
    cw = cfg.conv_width
    pd = _pdtype(cfg)
    ks = jax.random.split(key, 6)
    return {
        "w_x": _dense_init(ks[0], (D, w), D, pd),
        "w_gate": _dense_init(ks[1], (D, w), D, pd),
        "conv_w": _dense_init(ks[2], (cw, w), cw, pd),
        "w_rec_gate": _dense_init(ks[3], (w, w), w, pd),
        "w_in_gate": _dense_init(ks[4], (w, w), w, pd),
        "lam": jax.random.uniform(ks[5], (w,), jnp.float32, 1.0, 4.0),
        "w_out": _dense_init(ks[0], (w, D), w, pd),
    }


def _rglru_scan(xb, r, i, lam, h0):
    """Linear recurrence h_t = a_t h_{t−1} + sqrt(1−a²)·(i⊙x) via an
    associative scan (O(log S) depth on TPU instead of O(S))."""
    c = 8.0
    log_a = -c * jax.nn.softplus(lam)[None, None, :] * r  # [B,S,w]
    a = jnp.exp(log_a)
    gated = (i * xb).astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * gated

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    a_s, b_s = jax.lax.associative_scan(combine, (a, b), axis=1)
    h = a_s * h0[:, None, :] + b_s
    return h, a, b


def apply_rglru(p, x, cfg, ctx: ShardCtx, *, cache=None, cur_index=None,
                want_cache: bool = False):
    """Griffin recurrent block: conv1d → RG-LRU, GeGLU-style gating."""
    B, S, D = x.shape
    dt = _dtype(cfg)
    w = cfg.rglru_width or D
    cw = cfg.conv_width
    xb = x @ p["w_x"].astype(dt)                      # [B,S,w]
    gate = jax.nn.gelu(x @ p["w_gate"].astype(dt))
    xb = ctx.csp(xb, ctx.batch_axes, None, ctx.model_axis)

    # Causal depthwise conv (width cw).
    if cache is None:
        pad = jnp.zeros((B, cw - 1, w), xb.dtype)
        xc = jnp.concatenate([pad, xb], axis=1)
        conv = sum(xc[:, j:j + S, :] * p["conv_w"][j].astype(dt)
                   for j in range(cw))
        new_conv_state = xc[:, -(cw - 1):, :] if cw > 1 else None
    else:
        hist = jnp.concatenate([cache["conv"].astype(dt), xb], axis=1)
        conv = sum(hist[:, j:j + 1, :] * p["conv_w"][j].astype(dt)
                   for j in range(cw))
        new_conv_state = hist[:, 1:, :]

    r = jax.nn.sigmoid(
        (conv @ p["w_rec_gate"].astype(dt)).astype(jnp.float32))
    i = jax.nn.sigmoid(
        (conv @ p["w_in_gate"].astype(dt)).astype(jnp.float32))

    if cache is None:
        h0 = jnp.zeros((B, w), jnp.float32)
        h, _, _ = _rglru_scan(conv.astype(jnp.float32), r, i, p["lam"], h0)
        new_cache = None
        if want_cache and new_conv_state is not None:
            new_cache = {"h": h[:, -1, :], "conv": new_conv_state.astype(dt)}
    else:
        c = 8.0
        a = jnp.exp(-c * jax.nn.softplus(p["lam"])[None, None, :] * r)
        b = jnp.sqrt(jnp.maximum(1 - a * a, 1e-9)) * (
            i * conv.astype(jnp.float32))
        h = a * cache["h"][:, None, :] + b
        new_cache = {"h": h[:, -1, :], "conv": new_conv_state.astype(dt)}

    y = (h.astype(dt) * gate) @ p["w_out"].astype(dt)
    return ctx.csp(y, ctx.batch_axes, None, None), new_cache


def init_rglru_cache(cfg, batch: int) -> Params:
    w = cfg.rglru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, w), _dtype(cfg)),
    }


# ---------------------------------------------------------------- xLSTM

def _lstm_dims(cfg):
    w = 2 * cfg.d_model           # up-projection width
    H = max(cfg.n_heads, 1)
    return w, H, w // H


def init_mlstm(key, cfg) -> Params:
    D = cfg.d_model
    w, H, hd = _lstm_dims(cfg)
    pd = _pdtype(cfg)
    ks = jax.random.split(key, 8)
    return {
        "w_up": _dense_init(ks[0], (D, w), D, pd),
        "w_q": _dense_init(ks[1], (w, w), w, pd),
        "w_k": _dense_init(ks[2], (w, w), w, pd),
        "w_v": _dense_init(ks[3], (w, w), w, pd),
        "w_i": _dense_init(ks[4], (w, H), w, pd),
        "w_f": _dense_init(ks[5], (w, H), w, pd),
        "w_o": _dense_init(ks[6], (w, w), w, pd),
        "w_down": _dense_init(ks[7], (w, D), w, pd),
    }


def _mlstm_chunkwise(q, k, v, i_g, f_g, C0, n0, chunk: int):
    """Chunkwise-parallel mLSTM (§Perf xlstm hillclimb).

    Within a chunk of L steps the recurrence unrolls to a decay-masked
    attention: with F_t = Π_{s≤t} f_s,

        num_t = F_t·(C0 q_t) + Σ_{s≤t} (F_t/F_s)·i_s·(k_s·q_t)·v_s
        den_t = F_t·(n0·q_t) + Σ_{s≤t} (F_t/F_s)·i_s·(k_s·q_t)
        C_L   = F_L·C0 + Σ_s (F_L/F_s)·i_s·v_s k_sᵀ   (and n_L alike)

    — three matmuls per chunk instead of L sequential rank-1 updates, and
    the [hd,hd] state hits HBM once per chunk instead of once per step.
    Mathematically identical to the sequential scan (decays F_t/F_s ≤ 1,
    computed in log space); tests assert allclose against it.
    """
    B, S, H, hd = q.shape
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L

    def resh(x):
        return jnp.moveaxis(x.reshape(B, nc, L, *x.shape[2:]), 1, 0)

    qc, kc, vc = resh(q), resh(k), resh(v)
    ic, fc = resh(i_g), resh(f_g)

    def chunk_fn(carry, inp):
        C, n = carry                      # [B,H,hd,hd], [B,H,hd]
        qb, kb, vb, ib, fb = inp          # [B,L,H,*]
        logf = jnp.log(jnp.clip(fb.astype(jnp.float32), 1e-9, 1.0))
        cum = jnp.cumsum(logf, axis=1)    # [B,L,H] — log F_t
        Ft = jnp.exp(cum)
        # D[t,s] = exp(cum_t − cum_s)·i_s for s ≤ t.
        diff = cum[:, :, None, :] - cum[:, None, :, :]    # [B,L,L,H]
        tri = jnp.tril(jnp.ones((L, L), bool))
        D = jnp.where(tri[None, :, :, None],
                      jnp.exp(diff) * ib[:, None, :, :], 0.0)
        qf = qb.astype(jnp.float32)
        kf = kb.astype(jnp.float32)
        vf = vb.astype(jnp.float32)
        scores = jnp.einsum("bthd,bshd->btsh", qf, kf) * D  # [B,L,L,H]
        num = (jnp.einsum("btsh,bshd->bthd", scores, vf)
               + Ft[..., None] * jnp.einsum("bhvk,bthk->bthv", C, qf))
        # den: Σ_s scores[t,s] (the k_s·q_t factor is inside scores).
        den = (jnp.sum(scores, axis=2)
               + Ft * jnp.einsum("bhk,bthk->bth", n, qf))
        h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
        # State carry to the next chunk.
        FL = Ft[:, -1]                                     # [B,H]
        decay_s = jnp.exp(cum[:, -1:, :] - cum) * ib       # [B,L,H]
        C = (FL[:, :, None, None] * C
             + jnp.einsum("bsh,bshv,bshk->bhvk", decay_s, vf, kf))
        n = FL[..., None] * n + jnp.einsum("bsh,bshk->bhk", decay_s, kf)
        return (C, n), h

    (C, n), hs = jax.lax.scan(chunk_fn, (C0, n0), (qc, kc, vc, ic, fc))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, hd)
    return h, C, n


def apply_mlstm(p, x, cfg, ctx: ShardCtx, *, cache=None, cur_index=None,
                want_cache: bool = False):
    """mLSTM block (xLSTM): matrix memory C_t = f C_{t−1} + i v kᵀ per head."""
    B, S, D = x.shape
    dt = _dtype(cfg)
    w, H, hd = _lstm_dims(cfg)
    up = x @ p["w_up"].astype(dt)                     # [B,S,w]
    q = (up @ p["w_q"].astype(dt)).reshape(B, S, H, hd)
    k = (up @ p["w_k"].astype(dt)).reshape(B, S, H, hd) / np.sqrt(hd)
    v = (up @ p["w_v"].astype(dt)).reshape(B, S, H, hd)
    i_g = jax.nn.sigmoid((up @ p["w_i"].astype(dt)).astype(jnp.float32))
    f_g = jax.nn.sigmoid((up @ p["w_f"].astype(dt)).astype(jnp.float32))

    C0 = (cache["C"] if cache is not None
          else jnp.zeros((B, H, hd, hd), jnp.float32))
    n0 = (cache["n"] if cache is not None
          else jnp.zeros((B, H, hd), jnp.float32))

    if cache is None and cfg.mlstm_chunk and S >= cfg.mlstm_chunk:
        hmat, C, n = _mlstm_chunkwise(q, k, v, i_g, f_g, C0, n0,
                                      cfg.mlstm_chunk)
        h = hmat.reshape(B, S, w).astype(dt)
        o = jax.nn.sigmoid(up @ p["w_o"].astype(dt))
        y = (o * h) @ p["w_down"].astype(dt)
        new_cache = {"C": C, "n": n} if want_cache else None
        return ctx.csp(y, ctx.batch_axes, None, None), new_cache

    def step(carry, inputs):
        C, n = carry
        qt, kt, vt, it, ft = inputs  # [B,H,hd] ×3, [B,H] ×2
        C = ft[..., None, None] * C + it[..., None, None] * (
            vt[..., :, None] * kt[..., None, :])      # [B,H,hd,hd]
        n = ft[..., None] * n + it[..., None] * kt
        num = jnp.einsum("bhvk,bhk->bhv", C, qt.astype(jnp.float32))
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt.astype(jnp.float32))),
            1.0)
        return (C, n), (num / den[..., None])

    seq = (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
           jnp.moveaxis(v, 1, 0), jnp.moveaxis(i_g, 1, 0),
           jnp.moveaxis(f_g, 1, 0))
    (C, n), hs = jax.lax.scan(step, (C0, n0), seq)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, w).astype(dt)
    o = jax.nn.sigmoid(up @ p["w_o"].astype(dt))
    y = (o * h) @ p["w_down"].astype(dt)
    new_cache = ({"C": C, "n": n}
                 if (cache is not None or want_cache) else None)
    return ctx.csp(y, ctx.batch_axes, None, None), new_cache


def init_mlstm_cache(cfg, batch: int) -> Params:
    _, H, hd = _lstm_dims(cfg)
    return {"C": jnp.zeros((batch, H, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, H, hd), jnp.float32)}


def init_slstm(key, cfg) -> Params:
    D = cfg.d_model
    w, H, hd = _lstm_dims(cfg)
    pd = _pdtype(cfg)
    ks = jax.random.split(key, 7)
    return {
        "w_up": _dense_init(ks[0], (D, w), D, pd),
        "w_z": _dense_init(ks[1], (w, w), w, pd),
        "w_i": _dense_init(ks[2], (w, w), w, pd),
        "w_f": _dense_init(ks[3], (w, w), w, pd),
        "w_o": _dense_init(ks[4], (w, w), w, pd),
        "r_z": _dense_init(ks[5], (H, hd, hd), hd, pd),  # recurrent, per head
        "w_down": _dense_init(ks[6], (w, D), w, pd),
    }


def apply_slstm(p, x, cfg, ctx: ShardCtx, *, cache=None, cur_index=None,
                want_cache: bool = False):
    """sLSTM block (xLSTM): scalar memory with head-wise recurrent mixing."""
    B, S, D = x.shape
    dt = _dtype(cfg)
    w, H, hd = _lstm_dims(cfg)
    up = x @ p["w_up"].astype(dt)
    z_in = up @ p["w_z"].astype(dt)
    i_in = (up @ p["w_i"].astype(dt)).astype(jnp.float32)
    f_in = (up @ p["w_f"].astype(dt)).astype(jnp.float32)
    o_g = jax.nn.sigmoid(up @ p["w_o"].astype(dt))

    c0 = cache["c"] if cache is not None else jnp.zeros((B, w), jnp.float32)
    n0 = cache["n"] if cache is not None else jnp.zeros((B, w), jnp.float32)
    h0 = cache["h"] if cache is not None else jnp.zeros((B, w), jnp.float32)

    def step(carry, inputs):
        c, n, h = carry
        zt, it, ft = inputs
        hr = h.reshape(B, H, hd)
        mix = jnp.einsum("bhk,hkj->bhj", hr, p["r_z"].astype(jnp.float32))
        z = jnp.tanh(zt.astype(jnp.float32) + mix.reshape(B, w))
        i = jax.nn.sigmoid(it)
        f = jax.nn.sigmoid(ft)
        c = f * c + i * z
        n = f * n + i
        h = c / jnp.maximum(n, 1.0)
        return (c, n, h), h

    seq = (jnp.moveaxis(z_in, 1, 0), jnp.moveaxis(i_in, 1, 0),
           jnp.moveaxis(f_in, 1, 0))
    (c, n, h), hs = jax.lax.scan(step, (c0, n0, h0), seq)
    hseq = jnp.moveaxis(hs, 0, 1).astype(dt)
    y = (o_g * hseq) @ p["w_down"].astype(dt)
    new_cache = ({"c": c, "n": n, "h": h}
                 if (cache is not None or want_cache) else None)
    return ctx.csp(y, ctx.batch_axes, None, None), new_cache


def init_slstm_cache(cfg, batch: int) -> Params:
    w, _, _ = _lstm_dims(cfg)
    z = jnp.zeros((batch, w), jnp.float32)
    return {"c": z, "n": z, "h": z}
