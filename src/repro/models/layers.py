"""Shared neural building blocks — pure-function style, pjit/shard_map
friendly (no framework; params are plain dict pytrees; every block has an
`*_axes` twin returning per-dim logical axis names for the sharding rules).

Blocks: RMSNorm, RoPE, GQA attention (training, chunked-flash prefill,
KV-cache decode), SwiGLU FFN, scatter-dispatch MoE (EP-shardable),
embedding. Numerics: params in cfg.param_dtype, activations in cfg.dtype,
softmax/statistics in f32.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical_shard

Array = jax.Array


# --------------------------------------------------------------------------
# Init helpers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def rms_norm(x: Array, w: Array, eps: float = 1e-6) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, jnp.float32) / d_head))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d_head // 2], x32[..., d_head // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention — three execution modes sharing one parameterization
# --------------------------------------------------------------------------

def attn_init(key, cfg) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return {
        "wq": dense_init(kq, (D, H * Dh), cfg.param_dtype),
        "wk": dense_init(kk, (D, KV * Dh), cfg.param_dtype),
        "wv": dense_init(kv, (D, KV * Dh), cfg.param_dtype),
        "wo": dense_init(ko, (H * Dh, D), cfg.param_dtype, scale=1.0 / math.sqrt(H * Dh)),
    }


def attn_axes() -> dict:
    return {
        "wq": ("qkv_in", "qkv_out"),
        "wk": ("qkv_in", "qkv_out"),
        "wv": ("qkv_in", "qkv_out"),
        "wo": ("o_in", "o_out"),
    }


def _qkv(params, x, cfg, positions):
    B, S, D = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (x @ params["wq"].astype(x.dtype)).reshape(B, S, H, Dh)
    k = (x @ params["wk"].astype(x.dtype)).reshape(B, S, KV, Dh)
    v = (x @ params["wv"].astype(x.dtype)).reshape(B, S, KV, Dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = logical_shard(q, "batch", "seq", "heads", None)
    k = logical_shard(k, "batch", "seq", "kv_heads", None)
    v = logical_shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _gqa_logits(q: Array, k: Array) -> Array:
    """q: (B, Sq, H, Dh), k: (B, Sk, KV, Dh) -> (B, KV, G, Sq, Sk) f32."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, Dh)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    )
    return logits / math.sqrt(Dh)


def _gqa_combine(probs: Array, v: Array, dtype) -> Array:
    """probs: (B, KV, G, Sq, Sk), v: (B, Sk, KV, Dh) -> (B, Sq, H, Dh)."""
    B, KV, G, Sq, Sk = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(dtype), v)
    return out.reshape(B, Sq, KV * G, v.shape[-1])


def dense_causal_attention(q, k, v) -> Array:
    """Full-materialization causal attention.

    The (B, KV, G, Sq, Sk) score tensor is sharded on the QUERY-sequence
    axis ('seq_attn' -> 'model'): when the head count does not divide the
    TP axis (phi3: 40 heads on 16) head-dim constraints are dropped and
    XLA would otherwise replicate attention activations per device —
    Sq-sharding restores 16-way parallelism for any head count
    (§Perf hillclimb C). The constraint is a no-op off-mesh.
    """
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    # Pin the WHOLE path to one Sq scheme: q and out Sq-sharded, k/v
    # replicated over 'model'. Constraining only the scores lets GSPMD
    # propagate a conflicting layout into the backward and all-gather the
    # full (B, KV, G, Sq, Sk) probs (43 GB f32/layer at phi3 scale).
    q = logical_shard(q, "batch", "seq_attn", None, None)
    k = logical_shard(k, "batch", None, None, None)
    v = logical_shard(v, "batch", None, None, None)
    logits = _gqa_logits(q, k)
    logits = logical_shard(logits, "batch", None, None, "seq_attn", None)
    qi = jnp.arange(Sq)[:, None] + (Sk - Sq)
    ki = jnp.arange(Sk)[None, :]
    mask = qi >= ki
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = logical_shard(probs, "batch", None, None, "seq_attn", None)
    out = _gqa_combine(probs, v, q.dtype)
    return logical_shard(out, "batch", "seq_attn", None, None)


def chunked_causal_attention(
    q: Array, k: Array, v: Array, *, chunk_q: int, chunk_kv: int,
    skip_masked_chunks: bool = True,
) -> Array:
    """Flash-style double-chunked causal attention in pure JAX.

    Never materializes the (Sq, Sk) score matrix: scans q in chunks of
    `chunk_q`; for each q chunk scans kv chunks with a running
    (max, denominator, accumulator). TPU-native adaptation of the memory
    hierarchy argument — each chunk's score tile lives in VMEM.

    With `skip_masked_chunks` (beyond-paper perf option) fully-masked kv
    chunks are skipped via early bailout inside the kv scan (saves ~2x
    FLOPs for causal attention, matching an upper-triangular schedule).
    """
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    assert Sq % chunk_q == 0 and Sk % chunk_kv == 0, (Sq, Sk, chunk_q, chunk_kv)
    nq, nk = Sq // chunk_q, Sk // chunk_kv
    KV = k.shape[2]
    G = H // KV
    offset = Sk - Sq  # query i attends to keys <= i + offset

    k_chunks = k.reshape(B, nk, chunk_kv, KV, Dh)
    v_chunks = v.reshape(B, nk, chunk_kv, KV, Dh)

    def q_chunk_body(_, qi):
        q_c = jax.lax.dynamic_slice_in_dim(q, qi * chunk_q, chunk_q, axis=1)
        q_pos = qi * chunk_q + jnp.arange(chunk_q) + offset

        def kv_body(carry, kj):
            m, l, acc = carry
            k_c = k_chunks[:, kj]
            v_c = v_chunks[:, kj]
            logits = _gqa_logits(q_c, k_c)          # (B,KV,G,cq,ck) f32
            k_pos = kj * chunk_kv + jnp.arange(chunk_kv)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            scale = jnp.exp(m - m_new)
            l_new = l * scale + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkgqs,bskd->bkgqd", p, v_c.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * scale[..., None] + pv
            return (m_new, l_new, acc_new), None

        def kv_skip(carry, kj):
            # Chunk entirely in the masked (future) region: no-op.
            del kj
            return carry, None

        def kv_step(carry, kj):
            if not skip_masked_chunks:
                return kv_body(carry, kj)
            first_q = qi * chunk_q + offset
            needed = kj * chunk_kv <= first_q + chunk_q - 1
            return jax.lax.cond(needed, kv_body, kv_skip, carry, kj)

        m0 = jnp.full((B, KV, G, chunk_q), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, G, chunk_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, chunk_q, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1)               # (B,cq,KV,G,Dh)
        return None, out.reshape(B, chunk_q, KV * G, Dh).astype(q.dtype)

    _, chunks = jax.lax.scan(q_chunk_body, None, jnp.arange(nq))
    # chunks: (nq, B, chunk_q, H, Dh) -> (B, Sq, H, Dh)
    return jnp.moveaxis(chunks, 0, 1).reshape(B, Sq, H, Dh)


def decode_attention(q: Array, k_cache: Array, v_cache: Array, pos: Array) -> Array:
    """One-token attention against a KV cache.

    q: (B, 1, H, Dh); caches: (B, S_max, KV, Dh); pos: () current length-1
    index (entries at positions > pos are masked). Memory-bound: streams
    the cache once. Softmax stats in f32; safe under sequence sharding
    (GSPMD reduces the stats over the sharded axis).
    """
    B, _, H, Dh = q.shape
    S = k_cache.shape[1]
    logits = _gqa_logits(q, k_cache)                # (B,KV,G,1,S)
    valid = jnp.arange(S) <= pos                    # pos: scalar int32
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / denom
    return _gqa_combine(probs, v_cache, q.dtype)


# --------------------------------------------------------------------------
# SwiGLU FFN
# --------------------------------------------------------------------------

def ffn_init(key, cfg, d_ff: int | None = None) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    return {
        "w_gate": dense_init(k1, (D, F), cfg.param_dtype),
        "w_up": dense_init(k2, (D, F), cfg.param_dtype),
        "w_down": dense_init(k3, (F, D), cfg.param_dtype, scale=1.0 / math.sqrt(F)),
    }


def ffn_axes() -> dict:
    return {
        "w_gate": ("ffn_in", "ffn_out"),
        "w_up": ("ffn_in", "ffn_out"),
        "w_down": ("ffn_down_in", "ffn_down_out"),
    }


def ffn_apply(params, x: Array) -> Array:
    h = jax.nn.silu(x @ params["w_gate"].astype(x.dtype)) * (
        x @ params["w_up"].astype(x.dtype)
    )
    h = logical_shard(h, "batch", "seq", "mlp")
    return h @ params["w_down"].astype(x.dtype)


# --------------------------------------------------------------------------
# Mixture of Experts — scatter dispatch (no T·E·C·D one-hot einsum)
# --------------------------------------------------------------------------
#
# Dispatch = sort tokens by expert + scatter into an (E, C, D) buffer;
# data movement O(T·k·D) instead of the Mesh-TF dispatch einsum's
# O(T·E·C·D) FLOPs (which would dominate the roofline at E=384). The
# expert matmuls are batched einsums over the (sharded) expert axis — EP
# over 'model' with GSPMD-inserted redistribution at the scatter/gather.

def moe_init(key, cfg) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    D, E, Fm = cfg.d_model, cfg.n_experts, cfg.d_ff_moe
    k1, k2, k3 = jax.random.split(ke, 3)
    params = {
        "router": dense_init(kr, (D, E), jnp.float32),
        "experts": {
            "w_gate": dense_init(k1, (E, D, Fm), cfg.param_dtype),
            "w_up": dense_init(k2, (E, D, Fm), cfg.param_dtype),
            "w_down": dense_init(k3, (E, Fm, D), cfg.param_dtype,
                                 scale=1.0 / math.sqrt(Fm)),
        },
    }
    if cfg.shared_expert:
        params["shared"] = ffn_init(ks, cfg, d_ff=cfg.d_ff_moe)
    return params


def moe_axes(cfg) -> dict:
    axes = {
        "router": (None, None),
        "experts": {
            "w_gate": ("experts", "expert_in", "expert_out"),
            "w_up": ("experts", "expert_in", "expert_out"),
            "w_down": ("experts", "expert_out", "expert_in"),
        },
    }
    if cfg.shared_expert:
        axes["shared"] = ffn_axes()
    return axes


def moe_capacity(n_tokens: int, cfg) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    if getattr(cfg, "moe_dispatch", "onehot") == "sort":
        # capacity axis is sharded over ('pod','data') in the sort path
        return max(256, -(-c // 256) * 256)
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane alignment


def moe_apply(params, x: Array, cfg) -> tuple[Array, Array]:
    """x: (B, S, D) -> (y (B,S,D), aux_loss scalar).

    Top-k routing with capacity; overflow tokens are dropped (contribute
    only through the shared expert / residual). Load-balance aux loss per
    Shazeer et al. / Switch.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    C = moe_capacity(T, cfg)
    xt = x.reshape(T, D)

    router_logits = (xt.astype(jnp.float32) @ params["router"])   # (T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)               # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balance loss: E * sum_e (frac_tokens_e * mean_prob_e)
    me = jnp.mean(probs, axis=0)
    one_hot_top1 = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux_loss = E * jnp.sum(me * ce)

    # ---- scatter dispatch ----
    flat_e = expert_ids.reshape(-1)                                # (T*K,)
    flat_g = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    if getattr(cfg, "moe_dispatch", "onehot") == "sort":
        # Sort-based positions: O(T·K) vectors only. The one-hot variant
        # below builds (T·K, E) int32 matrices whose partitioned cumsum
        # makes GSPMD all-gather ~13 GB/layer/device at kimi-k2 scale
        # (§Perf). Stable sort keeps token order within an expert, so
        # capacity drop semantics match the one-hot path exactly.
        TK = flat_e.shape[0]
        order = jnp.argsort(flat_e, stable=True)                   # (TK,)
        sorted_e = flat_e[order]
        counts = jax.ops.segment_sum(
            jnp.ones((TK,), jnp.int32), flat_e, num_segments=E)    # (E,)
        starts = jnp.cumsum(counts) - counts                       # (E,)
        pos_sorted = jnp.arange(TK, dtype=jnp.int32) - starts[sorted_e]
        pos = jnp.zeros((TK,), jnp.int32).at[order].set(pos_sorted)
    else:
        # Position of each assignment within its expert = rank among equal
        # expert ids in stable token order, via one-hot cumsum.
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (TK, E)
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)           # counts before
        pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    safe_pos = jnp.where(keep, pos, 0)

    buf = jnp.zeros((E, C, D), x.dtype)
    src = jnp.where(keep[:, None], xt[flat_tok], 0)
    buf = buf.at[flat_e, safe_pos].add(src, mode="drop")
    if getattr(cfg, "moe_dispatch", "onehot") == "sort":
        # capacity axis sharded over ('pod','data'): the scatter-add
        # partial reduction moves buf-shard-sized pieces, not full bufs
        buf = logical_shard(buf, "experts", "expert_cap", None)
    else:
        buf = logical_shard(buf, "experts", None, None)

    # ---- expert FFN (batched over sharded expert axis) ----
    sort_path = getattr(cfg, "moe_dispatch", "onehot") == "sort"
    cap_axis = "expert_cap" if sort_path else None
    we = params["experts"]
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, we["w_gate"].astype(x.dtype))
    ) * jnp.einsum("ecd,edf->ecf", buf, we["w_up"].astype(x.dtype))
    h = logical_shard(h, "experts", cap_axis, "expert_out")
    out_buf = jnp.einsum("ecf,efd->ecd", h, we["w_down"].astype(x.dtype))
    out_buf = logical_shard(out_buf, "experts", cap_axis, None)

    # ---- combine (gather back) ----
    gathered = out_buf[flat_e, safe_pos]                           # (TK, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.zeros((T, D), x.dtype)
    y = y.at[flat_tok].add(gathered * flat_g[:, None].astype(x.dtype))

    if cfg.shared_expert:
        y = y + ffn_apply(params["shared"], xt)
    return y.reshape(B, S, D), aux_loss


# --------------------------------------------------------------------------
# shard_map expert-parallel MoE (§Perf variant 'shmap')
# --------------------------------------------------------------------------
#
# The GSPMD-global dispatch above lets the partitioner choose the
# communication for the (E, C, D) scatter — at kimi-k2 scale it chooses
# full-buffer all-reduces over 'data' (37 GB/layer/device) plus (T, D)
# all-reduces for the combine (§Perf log). This manual version makes the
# EP structure explicit:
#
#   * tokens are sharded over ('pod','data') and REPLICATED over 'model'
#     -> each model shard already holds every token it could need, so
#     DISPATCH IS COMMUNICATION-FREE: each shard scatters its local
#     tokens into buffers for ITS OWN E/16 experts;
#   * expert weights stay ZeRO-3-sharded over ('pod','data'); the
#     explicit all-gather here is the standard FSDP per-layer gather
#     (backward auto-generates the reduce-scatter);
#   * COMBINE is one psum over 'model' of the (T_local, D) partial sums.
#
# Capacity is enforced per data shard (C_local = ceil-div of the global
# C), the standard EP drop semantics; with capacity_factor 1.25 the
# difference from global capacity is negligible (and exact when no
# tokens drop — asserted in tests).

def _positions_by_expert(flat_e: Array, n_experts: int) -> Array:
    """Stable rank of each assignment within its expert id — O(TK log TK)
    sort, no (TK, E) matrices."""
    TK = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(
        jnp.ones((TK,), jnp.int32), flat_e, num_segments=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(TK, dtype=jnp.int32) - starts[sorted_e]
    return jnp.zeros((TK,), jnp.int32).at[order].set(pos_sorted)


def moe_apply_shmap(params, x: Array, cfg, mesh) -> tuple[Array, Array]:
    """shard_map twin of moe_apply. x: (B, S, D) global."""
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    ep = mesh.shape.get("model", 1)
    E_l = E // ep if E % ep == 0 else E
    T = B * S
    T_l = T // n_batch
    C_l = moe_capacity(T_l, cfg)

    def body(x_l, router_w, wg_l, wu_l, wd_l):
        B_l = x_l.shape[0]
        xt = x_l.reshape(B_l * S, D)
        tl = xt.shape[0]
        probs = jax.nn.softmax(xt.astype(jnp.float32) @ router_w, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        # combine in activation dtype: keeping f32 gates makes AD produce
        # f32 (T*K, D) tensors in the backward (2x the HBM traffic of the
        # whole dispatch path — §Perf log)
        gate_vals = gate_vals.astype(x_l.dtype)

        # load-balance aux loss with GLOBAL token statistics
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32),
                      axis=0)
        if batch_axes:
            me = jax.lax.pmean(me, batch_axes)
            ce = jax.lax.pmean(ce, batch_axes)
        aux = E * jnp.sum(me * ce)

        flat_e = expert_ids.reshape(-1)
        flat_g = gate_vals.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(tl), K)
        pos = _positions_by_expert(flat_e, E)

        e0 = (jax.lax.axis_index("model") * E_l
              if "model" in mesh.axis_names else 0)
        local = jnp.logical_and(flat_e >= e0, flat_e < e0 + E_l)
        keep = jnp.logical_and(local, pos < C_l)
        el = jnp.clip(flat_e - e0, 0, E_l - 1)
        safe_pos = jnp.where(keep, pos, 0)

        # Slot-centric dispatch: scatter only the (tiny, int) slot->token
        # and slot->gate maps, then GATHER token rows per expert slot.
        # Slot count E_l*C_l is ~T*K/ep — scattering (T*K, D) token
        # copies (the naive form) moves ep-times more data and, under AD,
        # materializes (T*K, D) cotangents (§Perf log).
        # Invalid assignments scatter OUT OF BOUNDS (pos = C_l) and are
        # dropped — .set() with in-bounds collisions would be
        # nondeterministic.
        drop_pos = jnp.where(keep, safe_pos, C_l)
        slot_tok = jnp.zeros((E_l, C_l), jnp.int32).at[el, drop_pos].set(
            flat_tok, mode="drop")
        slot_gate = jnp.zeros((E_l, C_l), x_l.dtype).at[el, drop_pos].set(
            flat_g, mode="drop")
        slot_valid = jnp.zeros((E_l, C_l), x_l.dtype).at[el, drop_pos].set(
            jnp.ones_like(flat_g), mode="drop")

        buf = xt[slot_tok] * slot_valid[..., None]           # (E_l, C_l, D)

        # FSDP gather of this shard's expert weights (ZeRO-3).
        # w_gate/w_up shard D on axis 1; w_down (E, F, D) shards D on
        # axis 2 (its expert_in dim).
        if batch_axes:
            wg = jax.lax.all_gather(wg_l, batch_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu_l, batch_axes, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd_l, batch_axes, axis=2, tiled=True)
        else:
            wg, wu, wd = wg_l, wu_l, wd_l

        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", buf, wg.astype(x_l.dtype))
        ) * jnp.einsum("ecd,edf->ecf", buf, wu.astype(x_l.dtype))
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd.astype(x_l.dtype))

        # Slot-driven combine: each valid slot adds gate * out_row to its
        # token — E_l*C_l rows moved, never (T*K, D).
        weighted = out_buf * (slot_gate * slot_valid)[..., None]
        y = jnp.zeros((tl, D), x_l.dtype)
        y = y.at[slot_tok.reshape(-1)].add(
            weighted.reshape(-1, D), mode="drop")
        if "model" in mesh.axis_names:
            y = jax.lax.psum(y, "model")        # the EP combine
        return y.reshape(B_l, S, D), aux

    batch_spec = batch_axes if batch_axes else None
    w_spec = P("model", batch_spec, None)
    we = params["experts"]
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_spec, None, None), P(), w_spec, w_spec,
                  P("model", None, batch_spec)),
        out_specs=(P(batch_spec, None, None), P()),
        check_vma=False,
    )(x, params["router"], we["w_gate"], we["w_up"], we["w_down"])

    if cfg.shared_expert:
        B, S, D = x.shape
        y = y + ffn_apply(params["shared"], x.reshape(B * S, D)).reshape(
            B, S, D)
    return y, aux


# --------------------------------------------------------------------------
# Embedding
# --------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype) -> Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def embed_lookup(table: Array, ids: Array) -> Array:
    return jnp.take(table, ids, axis=0)
