"""Composable decoder: parameter spec / init / forward / loss / decode.

All per-layer parameters are stacked along a leading L axis so the layer
stack is a single ``jax.lax.scan`` (O(1) trace & HLO size regardless of
depth — essential for the 512-device dry-run compiles).  Every block type
(dense / moe / hybrid / ssm) shares this contract:

    block(cfg, lp, x, mode, cache) -> (x, new_cache, aux)

where lp is one layer's parameter slice and cache is that layer's decode
state (None in train/prefill).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig

# named scopes of the model's parts: every op of a compiled step carries
# the innermost one in its metadata, forward, backward and recomputation
# alike, so a device trace can be split by part
SCOPE_EMBED = "model.embed"
SCOPE_ATTENTION = "model.attention"
SCOPE_MLP = "model.mlp"
SCOPE_HEAD_LOSS = "model.head_loss"


def _ring(cfg: ModelConfig, tp) -> int:
    """Static model-axis size when ring-overlapped collectives are on
    (0 selects the monolithic psum conjugates)."""
    return tp.size if (tp is not None and cfg.overlap_collectives) else 0


# ==================================================== tensor parallelism
# The model-axis shard-plan subsystem lives in ``models/shard_plan``
# (family-generic: expert-parallel MoE, sharded recurrent mixers,
# sequence parallelism).  Re-exported here under the historical names.
from repro.models.shard_plan import (TPPlan, TPRuntime,  # noqa: F401
                                     tp_plan)


# ============================================================ param spec
def param_spec(cfg: ModelConfig) -> dict:
    """Shapes of every parameter (single source of truth; init + counting
    + sharding rules all derive from this)."""
    D, V, Lyr = cfg.d_model, cfg.vocab, cfg.n_layers
    F, Q, KV, hd, H = cfg.d_ff, cfg.q_dim, cfg.kv_dim, cfg.hd, cfg.n_heads
    blk: dict[str, tuple] = {"ln1": (Lyr, D), "ln2": (Lyr, D)}
    if cfg.family != "ssm":
        blk.update(wq=(Lyr, D, Q), wk=(Lyr, D, KV), wv=(Lyr, D, KV),
                   wo=(Lyr, Q, D))
        if cfg.qkv_bias:
            blk.update(bq=(Lyr, Q), bk=(Lyr, KV), bv=(Lyr, KV))
        if cfg.qk_norm:
            blk.update(q_norm=(Lyr, hd), k_norm=(Lyr, hd))
    if cfg.family == "moe":
        E = cfg.n_experts
        blk.update(router=(Lyr, D, E), w_gate=(Lyr, E, D, F),
                   w_up=(Lyr, E, D, F), w_down=(Lyr, E, F, D))
    elif cfg.family == "ssm":
        blk.update(xq=(Lyr, D, Q), xk=(Lyr, D, Q), xv=(Lyr, D, Q),
                   xo=(Lyr, Q, D), w_i=(Lyr, D, H), w_f=(Lyr, D, H),
                   b_i=(Lyr, H), b_f=(Lyr, H),
                   p_up=(Lyr, D, 2 * D), p_gate=(Lyr, D, 2 * D),
                   p_down=(Lyr, 2 * D, D))
    elif cfg.family == "hybrid":
        Di, N = D, cfg.ssm_state
        blk.update(m_in=(Lyr, D, 2 * Di), m_dt=(Lyr, D, Di),
                   m_bc=(Lyr, D, 2 * N), m_A=(Lyr, Di, N),
                   m_D=(Lyr, Di), m_out=(Lyr, Di, D), m_ln=(Lyr, Di),
                   w_gate=(Lyr, D, F), w_up=(Lyr, D, F), w_down=(Lyr, F, D))
    else:                                   # dense / audio / vlm
        blk.update(w_gate=(Lyr, D, F), w_up=(Lyr, D, F), w_down=(Lyr, F, D))
    spec = {"embed": (V, D), "ln_f": (D,), "blocks": blk}
    if not cfg.tie_embeddings:
        spec["lm_head"] = (D, V)
    if cfg.frontend == "vlm":
        spec["proj_in"] = (cfg.d_frontend, D)
    return spec


def param_count(cfg: ModelConfig) -> int:
    import numpy as np
    spec = param_spec(cfg)
    total = 0
    for k, v in spec.items():
        if k == "blocks":
            total += sum(int(np.prod(s)) for s in v.values())
        else:
            total += int(np.prod(v))
    return total


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top_k of n_experts expert FFNs)."""
    total = param_count(cfg)
    if cfg.family == "moe":
        expert = 3 * cfg.d_model * cfg.d_ff
        total -= cfg.n_layers * (cfg.n_experts - cfg.top_k) * expert
    return total


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    spec = param_spec(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def one(key, name, shape):
        if name.startswith(("ln", "q_norm", "k_norm", "m_ln")):
            return jnp.ones(shape, dtype)
        if name.startswith("b") or name in ("m_D",):
            return jnp.zeros(shape, dtype)
        if name == "b_f":
            return jnp.full(shape, 2.0, dtype)      # open forget gates
        if name == "m_A":
            return jnp.log(jnp.broadcast_to(
                jnp.arange(1, shape[-1] + 1, dtype=jnp.float32),
                shape)).astype(dtype)               # S4D-real init
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return (jax.random.normal(key, shape, jnp.float32) *
                (fan_in ** -0.5)).astype(dtype)

    flat: dict[str, Any] = {}
    idx = 0
    for name, shape in spec.items():
        if name == "blocks":
            flat["blocks"] = {}
            for bn, bs in shape.items():
                flat["blocks"][bn] = one(jax.random.fold_in(key, idx), bn, bs)
                idx += 1
        else:
            flat[name] = one(jax.random.fold_in(key, idx), name, shape)
            idx += 1
    return flat


# ================================================================= blocks
def _attn_ctx(cfg: ModelConfig, lp, x, positions, window, tp, seq):
    """Context-parallel (ring) attention region: the sequence, not the
    heads, shards over the model axis — the escape hatch for configs
    whose head counts can't divide (odd heads, GQA kv < tp).  Weights
    are replicated (grads partial — see shard_plan._leaf_spec); each
    position projects q/k/v for ITS S/n chunk and K/V chunks rotate
    through the ppermute ring with online-softmax accumulation.  Under a
    seq plan the residual stream already IS the chunk, so entry/exit are
    free; otherwise ctx_enter/ctx_exit slice and reassemble."""
    B = x.shape[0]
    n = tp.size
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if not seq:
        h = L.ctx_enter(h, tp.axis, n)
    C = h.shape[1]
    cpos = jax.lax.dynamic_slice_in_dim(positions, tp.index * C, C, 1)
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, C, cfg.n_heads, cfg.hd)
    k = k.reshape(B, C, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(B, C, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = L.rope(q, cpos, cfg.rope_theta)
    k = L.rope(k, cpos, cfg.rope_theta)
    out = L.ring_attention(q, k, v, tp.axis, n, window=window)
    y = out.reshape(B, C, cfg.n_heads * cfg.hd) @ lp["wo"]
    if not seq:
        y = L.ctx_exit(y, tp.axis, n)
    return x + y, None


def _attn(cfg: ModelConfig, lp, x, positions, mode, cache, window, tp=None):
    B = x.shape[0]
    tp_attn = tp is not None and tp.plan.attn
    seq = tp is not None and tp.plan.seq
    if (tp is not None and tp.plan.ctx > 1 and mode == "train"
            and window != 0
            and (x.shape[1] * (tp.size if seq else 1)) % tp.size == 0):
        return _attn_ctx(cfg, lp, x, positions, window, tp, seq)
    n_heads = cfg.n_heads // (tp.size if tp_attn else 1)
    n_kv = cfg.n_kv_heads // (tp.size if tp_attn else 1)
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if seq:
        # sequence-parallel entry: assemble the full sequence (bwd:
        # psum_scatter of the shards' partial cotangents)
        h = L.tp_seq_gather(h, tp.axis, 1)
    elif tp_attn:
        h = L.tp_enter(h, tp.axis, _ring(cfg, tp))
    S = h.shape[1]
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, n_heads, cfg.hd)
    k = k.reshape(B, S, n_kv, cfg.hd)
    v = v.reshape(B, S, n_kv, cfg.hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    if cfg.attn_batch_shard and mode != "decode":
        from jax.sharding import PartitionSpec as _P
        bs = _P("model")
        q, k, v = (jax.lax.with_sharding_constraint(t, bs)
                   for t in (q, k, v))
    if mode == "decode":
        pos = positions[0, 0]
        size = cache["k"].shape[1]
        slot = pos % size if window is not None else pos
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                                      k.astype(cache["k"].dtype), slot, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                                      v.astype(cache["v"].dtype), slot, 1)
        out = L.decode_attention(q, k_cache, v_cache, pos, window=window)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        from repro.kernels import flash_attention as fa
        from repro.kernels.common import log_fallback
        use_flash = (cfg.flash_attention and mode == "train"
                     and not cfg.attn_batch_shard and window != 0)
        if use_flash and not fa.supports(S, cfg.hd, q.dtype.itemsize):
            log_fallback("flash_attention", f"S={S} d={cfg.hd} "
                         f"{q.dtype} is outside the kernel's shape gate")
            use_flash = False
        if use_flash:
            # blocked online-softmax kernel, custom-VJP backward: no S x S
            # score materialization in either pass.  Head counts here are
            # already TP-local; a seq plan entered above, so S is full.
            out = fa.flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True, window=window).swapaxes(1, 2)
        else:
            out = L.causal_attention(
                q, k, v, window=window, chunk=cfg.attn_chunk,
                scores_f32=cfg.attn_scores_f32 and not cfg.bf16_residency)
        if cfg.attn_batch_shard:
            from jax.sharding import PartitionSpec as _P
            out = jax.lax.with_sharding_constraint(out, _P("model"))
        new_cache = ({"k": k, "v": v} if mode == "prefill" else None)
    y = out.reshape(B, S, n_heads * cfg.hd) @ lp["wo"]
    if seq and tp_attn:
        y = L.tp_seq_scatter(y, tp.axis, 1)     # partials -> seq shards
    elif seq:
        # replicated-attention fallback under a seq plan: every position
        # computed the full (identical) output; keep this position's
        # sequence slice — the entry gather's psum_scatter assembles the
        # per-slice cotangent contributions on the way back
        s_loc = S // tp.size
        y = jax.lax.dynamic_slice_in_dim(y, tp.index * s_loc, s_loc, 1)
    elif tp_attn:
        y = L.tp_exit(y, tp.axis, _ring(cfg, tp))
    return x + y, new_cache


def _gated_mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _ffn(cfg, lp, x, tp=None):
    tp_ffn = tp is not None and tp.plan.ffn
    seq = tp is not None and tp.plan.seq       # seq plans imply tp_ffn
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if seq:
        h = L.tp_seq_gather(h, tp.axis, 1)
    elif tp_ffn:
        h = L.tp_enter(h, tp.axis, _ring(cfg, tp))
    y = _gated_mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    if seq:
        y = L.tp_seq_scatter(y, tp.axis, 1)
    elif tp_ffn:
        y = L.tp_exit(y, tp.axis, _ring(cfg, tp))
    return x + y


def _mamba(cfg, lp, x, mode, state, tp=None):
    """Selective-SSM branch (hybrid).  Returns (delta, new_state).

    Under a sharded-mixer plan the CHANNEL dim is split over the model
    axis: m_dt/m_A/m_D/m_ln/m_out hold local channels and the chunked
    scan runs fully local (state is per-channel).  m_in and m_bc stay
    replicated (their z/u and B/C halves straddle the split) with
    partial-grad psum; the per-channel slices of z/u are taken locally.
    The m_ln RMS norm is the one cross-shard statistic (psum'd mean of
    squares over the full channel width)."""
    B, S, D = x.shape
    tp_mix = tp is not None and tp.plan.mixer
    x_in = L.tp_push(x, tp.axis) if tp_mix else x
    zu = x_in @ lp["m_in"]
    z, u = jnp.split(zu, 2, axis=-1)
    dt = jax.nn.softplus(x_in @ lp["m_dt"])
    bc = x_in @ lp["m_bc"]
    Bm, Cm = jnp.split(bc, 2, axis=-1)
    if tp_mix:
        d_loc = dt.shape[-1]                   # m_dt is column-sharded
        z = jax.lax.dynamic_slice_in_dim(z, tp.index * d_loc, d_loc, -1)
        u = jax.lax.dynamic_slice_in_dim(u, tp.index * d_loc, d_loc, -1)
    u = jax.nn.silu(u)
    if mode == "decode":
        h_new, y = ssm_lib.ssm_decode_step(
            state, u[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0],
            lp["m_A"], lp["m_D"])
        y = y[:, None]
    else:
        y, h_new = ssm_lib.ssm_scan(u, dt, Bm, Cm, lp["m_A"], lp["m_D"],
                                    chunk=cfg.scan_chunk,
                                    scan_f32=cfg.ssm_scan_f32)
        h_new = h_new if mode == "prefill" else None
    if tp_mix:
        y = L.rms_norm_sharded(y, lp["m_ln"], cfg.norm_eps, tp.axis, D)
    else:
        y = L.rms_norm(y, lp["m_ln"], cfg.norm_eps)
    y = y * jax.nn.silu(z)
    out = y @ lp["m_out"]
    return (L.tp_pull(out, tp.axis) if tp_mix else out), h_new


def _mlstm(cfg, lp, x, mode, state, tp=None):
    B, S, D = x.shape
    tp_mix = tp is not None and tp.plan.mixer
    n_heads = cfg.n_heads // (tp.size if tp_mix else 1)
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if tp_mix:
        # head-sharded mixer: xq/xk/xv and the i/f gates are
        # column-parallel on heads, xo row-parallel; the recurrent state
        # is per-head, so the whole chunked recurrence runs local
        h = L.tp_push(h, tp.axis)
    q = (h @ lp["xq"]).reshape(B, S, n_heads, cfg.hd)
    k = (h @ lp["xk"]).reshape(B, S, n_heads, cfg.hd)
    v = (h @ lp["xv"]).reshape(B, S, n_heads, cfg.hd)
    i_pre = h @ lp["w_i"] + lp["b_i"]
    f_pre = h @ lp["w_f"] + lp["b_f"]
    if cfg.attn_batch_shard and mode != "decode":
        from jax.sharding import PartitionSpec as _P
        bs = _P("model")
        q, k, v, i_pre, f_pre = (jax.lax.with_sharding_constraint(t, bs)
                                 for t in (q, k, v, i_pre, f_pre))
    if mode == "decode":
        new_state, out = ssm_lib.mlstm_decode_step(
            state, q[:, 0], k[:, 0], v[:, 0], i_pre[:, 0], f_pre[:, 0])
        out = out[:, None]
    else:
        out = ssm_lib.mlstm_parallel(q, k, v, i_pre, f_pre,
                                     chunk=cfg.attn_chunk,
                                     scores_f32=cfg.attn_scores_f32)
        new_state = None
        if mode == "prefill":
            # build the recurrent state by replaying the last step math:
            # run a cheap recurrent pass is O(T); instead fold the whole
            # prefix with the recurrence once (scan) — acceptable at
            # prefill time, states are tiny.
            def step(st, inp):
                qq, kk, vv, ii, ff = inp
                st, _ = ssm_lib.mlstm_decode_step(st, qq, kk, vv, ii, ff)
                return st, ()
            st0 = init_mlstm_state(cfg, B, x.dtype)
            elems = (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                     i_pre.swapaxes(0, 1), f_pre.swapaxes(0, 1))
            new_state, _ = jax.lax.scan(step, st0, elems)
    y = out.reshape(B, S, n_heads * cfg.hd) @ lp["xo"]
    if tp_mix:
        y = L.tp_pull(y, tp.axis)
    return x + y, new_state


def init_mlstm_state(cfg, B, dtype=jnp.float32):
    H, hd = cfg.n_heads, cfg.hd
    return {"C": jnp.zeros((B, H, hd, hd), jnp.float32),
            "n": jnp.zeros((B, H, hd), jnp.float32),
            "m": jnp.full((B, H), -1e30, jnp.float32)}


def _block(cfg: ModelConfig, lp, x, positions, mode, cache, window, tp=None):
    aux = {}
    if cfg.family == "ssm":
        x, mix_state = _mlstm(cfg, lp, x, mode,
                              cache["mix"] if cache else None, tp)
        tp_ffn = tp is not None and tp.plan.ffn
        with jax.named_scope(SCOPE_MLP):
            h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if tp_ffn:                  # gated in-block projection pair
                h = L.tp_push(h, tp.axis)
            y = _gated_mlp(h, lp["p_gate"], lp["p_up"], lp["p_down"])
            if tp_ffn:
                y = L.tp_pull(y, tp.axis)
            x = x + y
        new_cache = {"mix": mix_state} if mode != "train" else None
        return x, new_cache, aux
    if cfg.family == "hybrid":
        with jax.named_scope(SCOPE_ATTENTION):
            attn_out, kv = _attn(cfg, lp, x, positions, mode,
                                 cache.get("kv") if cache else None,
                                 window, tp)
        m_out, m_state = _mamba(cfg, lp, x, mode,
                                cache.get("ssm") if cache else None, tp)
        x = 0.5 * (attn_out + (x + m_out))       # parallel heads, averaged
        with jax.named_scope(SCOPE_MLP):
            x = _ffn(cfg, lp, x, tp)
        new_cache = ({"kv": kv, "ssm": m_state} if mode != "train" else None)
        return x, new_cache, aux
    # dense / moe / audio / vlm
    with jax.named_scope(SCOPE_ATTENTION):
        x, kv = _attn(cfg, lp, x, positions, mode,
                      cache.get("kv") if cache else None, window, tp)
    with jax.named_scope(SCOPE_MLP):
        if cfg.family == "moe":
            h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            y, aux = moe_lib.moe_ffn(h, lp["router"], lp["w_gate"],
                                     lp["w_up"], lp["w_down"],
                                     top_k=cfg.top_k,
                                     capacity_factor=cfg.capacity_factor,
                                     group=cfg.moe_group_size, tp=tp)
            x = x + y
        else:
            x = _ffn(cfg, lp, x, tp)
    new_cache = {"kv": kv} if mode != "train" else None
    return x, new_cache, aux


# ================================================================ forward
@jax.custom_vjp
def _dense_grad_lookup(table, ids):
    """table[ids] with a dense one-hot-matmul backward.  Value- and
    gradient-identical to the plain gather (the one-hot dot touches each
    cotangent row exactly once), but the transpose is a single MXU matmul
    instead of a scatter-add — which XLA CPU lowers to a serial while
    loop re-reading the full table every trip (it dominated the train
    step's HBM-traffic proxy)."""
    return table[ids]


def _dense_grad_lookup_fwd(table, ids):
    return table[ids], (table, ids)


def _dense_grad_lookup_bwd(res, ct):
    import numpy as np
    table, ids = res
    V = table.shape[0]
    oh = (jax.lax.broadcasted_iota(jnp.int32, (*ids.shape, V), ids.ndim)
          == ids[..., None]).astype(ct.dtype)
    dtable = jax.lax.dot_general(
        oh.reshape(-1, V), ct.reshape(-1, ct.shape[-1]),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(table.dtype)
    return dtable, np.zeros(ids.shape, jax.dtypes.float0)


_dense_grad_lookup.defvjp(_dense_grad_lookup_fwd, _dense_grad_lookup_bwd)


def _embed_rows(params, cfg: ModelConfig, ids):
    if cfg.dense_embed_grad:
        return _dense_grad_lookup(params["embed"], ids)
    return params["embed"][ids]


def embed_inputs(params, cfg: ModelConfig, tokens,
                 frontend_embeds=None, tp=None):
    """Token embedding; VLM prepends projected patch embeddings.

    Under a vocab-parallel plan each shard holds vocab rows
    [index*V/tp, (index+1)*V/tp): out-of-range tokens look up zero and
    the psum (``tp_pull``) assembles the full embedding — the backward
    stays local (each shard accumulates only its own rows' grads)."""
    with jax.named_scope(SCOPE_EMBED):
        if tp is not None and tp.plan.vocab:
            v_loc = cfg.vocab // tp.size
            idx = tokens - tp.index * v_loc
            ok = (idx >= 0) & (idx < v_loc)
            x = jnp.where(ok[..., None],
                          _embed_rows(params, cfg,
                                      jnp.clip(idx, 0, v_loc - 1)),
                          0)
            if tp.plan.seq:
                # sequence-parallel residual stream: reduce-scatter the
                # vocab partials straight into (B, S/tp, D) shards
                x = L.tp_seq_scatter(x, tp.axis, 1)
            else:
                x = L.tp_exit(x, tp.axis, _ring(cfg, tp))
        else:
            x = _embed_rows(params, cfg, tokens)
        if cfg.frontend == "vlm":
            assert frontend_embeds is not None
            img = frontend_embeds.astype(x.dtype) @ params["proj_in"]
            x = jnp.concatenate([img, x], axis=1)
    return x


def _remat_policy(name: str):
    """Selective-remat policies for the layer-scan checkpoint.  ``full``
    is the historical blanket remat (save only the carry); the others
    keep matmul outputs resident so the backward re-runs only the cheap
    elementwise/softmax glue — HBM re-read traffic drops by the width of
    every recomputed GEMM input."""
    cp = jax.checkpoint_policies
    table = {
        "full": None,
        "dots": cp.dots_with_no_batch_dims_saveable,
        "dots_batch": cp.dots_saveable,
        "offload_dots": cp.offload_dot_with_no_batch_dims(
            "device", "pinned_host"),
    }
    if name not in table:
        raise ValueError(
            f"remat_policy {name!r}: want one of {sorted(table)} | none")
    return table[name]


def forward(params, cfg: ModelConfig, tokens, frontend_embeds=None,
            mode: str = "train", window: Optional[int] = None,
            remat: bool = True, tp: Optional[TPRuntime] = None,
            inputs_embeds=None):
    """Full-sequence forward.  Returns (logits, caches, aux).

    caches is the per-layer stacked decode state when mode == 'prefill'.
    With ``remat`` each layer is rematerialized in the backward pass
    (activation memory = one carry per layer instead of all residuals).
    With ``tp`` (inside a manual shard_map over tp.axis) params are the
    local shards of the TPPlan and, when the plan shards the vocab, the
    returned logits are vocab-sharded (B, S, V/tp) — ``loss_fn`` computes
    the cross-entropy without ever materializing full logits.  Under a
    sequence-parallel plan the residual stream between TP regions is
    (B, S/tp, D); the logits come back full-sequence (the unembed
    gathers), so the loss path is unchanged.

    ``inputs_embeds`` (B, S, D) bypasses the token-embedding lookup — the
    continuous-input hook the DLG gradient-inversion attack optimizes
    over (``repro.privacy``); ``tokens`` still supplies positions and CE
    targets.  Replicated path only (``tp`` must be None).
    """
    seq = tp is not None and tp.plan.seq
    if seq:
        s_full = tokens.shape[1]
        if s_full % tp.size != 0:
            raise ValueError(
                f"sequence-parallel plan needs seq_len divisible by the "
                f"model axis: {s_full} % {tp.size} != 0")
    if inputs_embeds is not None:
        if tp is not None:
            raise ValueError("inputs_embeds is a replicated-path hook "
                             "(attack/simulator side); tp must be None")
        x = inputs_embeds
    else:
        x = embed_inputs(params, cfg, tokens, frontend_embeds, tp)
    B = x.shape[0]
    S = x.shape[1] * (tp.size if seq else 1)    # full sequence length
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(carry, lp):
        h = carry
        h, cache, aux = _block(cfg, lp, h, positions, mode, None, window, tp)
        return h, (cache, aux.get("load_balance", jnp.zeros((), jnp.float32)))

    if remat and mode == "train" and cfg.remat_policy != "none":
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=_remat_policy(cfg.remat_policy))
    x, (caches, lb) = jax.lax.scan(body, x, params["blocks"])
    # seq_ce (ssm/hybrid, whose residual stream stays replicated): run
    # the final-norm region on this position's sequence chunk — entered
    # with a slice whose backward ASSEMBLES the chunk cotangents
    # (ctx_enter), exited into the unembed through the seq conjugate
    # (all-gather fwd, psum_scatter bwd) so the vocab-partial dL/dx is
    # summed exactly once.  ln_f grads become partial (shard_plan).
    seq_ce = (tp is not None and tp.plan.seq_ce and not seq
              and mode == "train" and x.shape[1] % tp.size == 0)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        if seq_ce:
            x = L.ctx_enter(x, tp.axis, tp.size)
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        if tp is not None and tp.plan.vocab:
            # column-parallel unembed; a seq plan assembles the sequence
            x = (L.tp_seq_gather(x, tp.axis, 1) if (seq or seq_ce)
                 else L.tp_enter(x, tp.axis, _ring(cfg, tp)))
        logits = x @ head
    return logits, caches, {"load_balance": lb.mean()}


def _select_logit(pred, tgt):
    """pred[..., tgt] as a one-hot masked sum — value- and
    gradient-identical to take_along_axis (exactly one nonzero term per
    row), but both directions are dense fused elementwise ops: the gather
    transpose otherwise lowers to a serial scatter-add while loop on XLA
    CPU that re-reads the whole (B, S, V) buffer every trip (it was ~60%
    of the train step's HBM-traffic proxy)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, pred.shape, pred.ndim - 1)
    return jnp.sum(jnp.where(iota == tgt[..., None], pred, 0), axis=-1)


def loss_fn(params, cfg: ModelConfig, batch, window=None,
            tp: Optional[TPRuntime] = None):
    """Causal LM loss.  batch: dict(tokens (B,S) [, frontend_embeds,
    loss_mask (B,S)]).  Next-token CE in f32 with logits sharded-friendly
    logsumexp.

    ``tp=None`` is the replicated path every simulator engine runs.  With
    a TPRuntime (inside the distributed runtime's manual shard_map) the
    forward computes on this position's parameter shards and, under a
    vocab-parallel plan, the CE runs on vocab-sharded logits: pmax/psum
    logsumexp plus a masked target-logit gather — the transposes stay
    local, so gradients are exact (not tp-times-counted)."""
    tokens = batch["tokens"]
    logits, _, aux = forward(params, cfg, tokens,
                             batch.get("frontend_embeds"), "train", window,
                             tp=tp, inputs_embeds=batch.get("inputs_embeds"))
    with jax.named_scope(SCOPE_HEAD_LOSS):
        nll = _ce(cfg, logits, tokens, batch.get("loss_mask"), tp)
    if cfg.family == "moe":
        nll = nll + 0.01 * aux["load_balance"]
    return nll


def _ce(cfg: ModelConfig, logits, tokens, loss_mask, tp):
    """Masked next-token CE from (possibly vocab-sharded) logits — the
    shared tail of ``loss_fn`` and ``pipeline_loss_fn``."""
    # align: for VLM, logits cover [img; text]; predict text tokens only
    n_pre = cfg.n_frontend_tokens if cfg.frontend == "vlm" else 0
    logits = logits[:, n_pre:, :]
    targ = tokens[:, 1:]
    fp32_logits = cfg.loss_fp32_logits and not cfg.bf16_residency
    if tp is not None and tp.plan.vocab:
        # sharded-vocab CE: max over shards via pmax (stop-grad, like the
        # max-shift below), sum-of-exp and target logit assembled with
        # tp_pull so each shard's backward touches only its own columns
        v_loc = cfg.vocab // tp.size
        pred = logits[:, :-1]
        if fp32_logits:
            pred = pred.astype(jnp.float32)
        m = jax.lax.pmax(jax.lax.stop_gradient(pred.max(-1)), tp.axis)
        e = jnp.exp(pred - m[..., None])
        lse = m.astype(jnp.float32) + jnp.log(
            L.tp_exit(jnp.sum(e, axis=-1, dtype=jnp.float32), tp.axis,
                      _ring(cfg, tp)))
        idx = targ - tp.index * v_loc
        ok = (idx >= 0) & (idx < v_loc)
        ll_loc = _select_logit(pred, jnp.clip(idx, 0, v_loc - 1))
        ll = L.tp_exit(jnp.where(ok, ll_loc, 0).astype(jnp.float32),
                       tp.axis, _ring(cfg, tp))
    elif fp32_logits:
        pred = logits[:, :-1].astype(jnp.float32)
        lse = jax.nn.logsumexp(pred, axis=-1)
        ll = _select_logit(pred, targ)
    else:
        # avoid materializing an f32 copy of the (B,S,V) logits: max-shift
        # and exp in the compute dtype, accumulate the sum in f32
        pred = logits[:, :-1]
        m = jax.lax.stop_gradient(pred.max(-1))
        e = jnp.exp(pred - m[..., None])
        lse = m.astype(jnp.float32) + jnp.log(
            jnp.sum(e, axis=-1, dtype=jnp.float32))
        ll = _select_logit(pred, targ).astype(jnp.float32)
    nll = lse - ll
    if loss_mask is not None:
        m = loss_mask[:, 1:]
        nll = (nll * m).sum() / jnp.maximum(m.sum(), 1)
    else:
        nll = nll.mean()
    return nll


def pipeline_loss_fn(params, cfg: ModelConfig, batch, window=None,
                     tp: Optional[TPRuntime] = None, pipe=None):
    """Causal LM loss with the layer stack split into ``pipe.plan.size``
    contiguous stages and the batch into ``microbatches`` slices.

    Runs inside the manual shard_map train body with the pipe axis in
    scope: ``params["blocks"]`` leaves hold this stage's L/p layer rows
    (everything else replicated over pipe).  One differentiable
    ``lax.scan`` over the m + p - 1 wavefront ticks: each tick ppermutes
    the activation carry one stage forward while computing this stage's
    next resident microbatch — the boundary send overlaps the following
    microbatch's compute, and AD of the scan replays the wavefront in
    reverse, realizing the interleaved 1F1B order that
    ``shard_plan.pipeline_schedule`` enumerates.  Stage 0 injects the
    embedding of microbatch clip(t, 0, m-1); the last stage folds the CE
    of the microbatch that entered p - 1 ticks earlier; both are
    where/mask-selected so every pipe coordinate traces one identical
    program.  The returned loss is psum'd over pipe (identical on every
    coordinate) = the mean of the m per-microbatch mean-CEs, which
    equals ``loss_fn``'s full-batch mean when microbatches weigh equally
    (no loss_mask, B % m == 0).
    """
    if pipe is None or not pipe.plan.active:
        return loss_fn(params, cfg, batch, window, tp)
    p, m = pipe.plan.size, pipe.plan.microbatches
    tokens = batch["tokens"]
    B, S = tokens.shape
    if B % m != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {m}")
    mb = B // m
    seq = tp is not None and tp.plan.seq
    tok_mb = tokens.reshape(m, mb, S)
    mask_mb = (batch["loss_mask"].reshape(m, mb, S)
               if batch.get("loss_mask") is not None else None)
    fe_mb = (batch["frontend_embeds"].reshape(
        m, mb, *batch["frontend_embeds"].shape[1:])
        if batch.get("frontend_embeds") is not None else None)
    n_pre = cfg.n_frontend_tokens if cfg.frontend == "vlm" else 0
    S_h = (S + n_pre) // (tp.size if seq else 1)   # carry seq length
    positions = jnp.broadcast_to(jnp.arange(S + n_pre), (mb, S + n_pre))
    perm = [(i, (i + 1) % p) for i in range(p)]
    stage = pipe.index
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    def block_body(carry, lp):
        h = carry
        h, _, aux = _block(cfg, lp, h, positions, "train", None, window, tp)
        return h, aux.get("load_balance", jnp.zeros((), jnp.float32))

    if cfg.remat_policy != "none":
        block_body = jax.checkpoint(block_body, prevent_cse=False,
                                    policy=_remat_policy(cfg.remat_policy))

    def tick(carry, t):
        x_prev, loss_sum, lb_sum = carry
        # boundary send: the activation computed last tick moves one
        # stage forward while this tick's compute proceeds below
        recv = jax.lax.ppermute(x_prev, pipe.axis, perm)
        j_in = jnp.clip(t, 0, m - 1)
        inj = embed_inputs(
            params, cfg, jax.lax.dynamic_index_in_dim(tok_mb, j_in, 0,
                                                      keepdims=False),
            (jax.lax.dynamic_index_in_dim(fe_mb, j_in, 0, keepdims=False)
             if fe_mb is not None else None), tp)
        x_in = jnp.where(stage == 0, inj, recv)
        x_out, lb = jax.lax.scan(block_body, x_in, params["blocks"])
        # stage s holds real data for microbatch t - s at ticks
        # s <= t < s + m
        valid_here = (t >= stage) & (t < stage + m)
        lb_sum = lb_sum + jnp.where(valid_here, lb.mean(), 0.0)
        # the microbatch leaving the LAST stage this tick entered the
        # pipe p - 1 ticks ago
        j_out = jnp.clip(t - (p - 1), 0, m - 1)
        h = x_out
        seq_ce = (tp is not None and tp.plan.seq_ce and not seq
                  and h.shape[1] % tp.size == 0)
        if seq_ce:
            h = L.ctx_enter(h, tp.axis, tp.size)
        h = L.rms_norm(h, params["ln_f"], cfg.norm_eps)
        if tp is not None and tp.plan.vocab:
            h = (L.tp_seq_gather(h, tp.axis, 1) if (seq or seq_ce)
                 else L.tp_enter(h, tp.axis, _ring(cfg, tp)))
        logits = h @ head
        nll = _ce(cfg, logits,
                  jax.lax.dynamic_index_in_dim(tok_mb, j_out, 0,
                                               keepdims=False),
                  (jax.lax.dynamic_index_in_dim(mask_mb, j_out, 0,
                                                keepdims=False)
                   if mask_mb is not None else None), tp)
        valid_out = (stage == p - 1) & (t >= p - 1) & (t < p - 1 + m)
        loss_sum = loss_sum + jnp.where(valid_out, nll, 0.0)
        return (x_out, loss_sum, lb_sum), None

    x0 = jnp.zeros((mb, S_h, cfg.d_model), jnp.dtype(cfg.dtype))
    (xf, loss_sum, lb_sum), _ = jax.lax.scan(
        tick, (x0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(m + p - 1))
    del xf
    # only the last stage accumulated loss; every stage accumulated its
    # own layers' load-balance aux — both assemble with the psum-forward
    # / identity-backward conjugate so every stage gets the SAME 1/m
    # cotangent and prices its own contribution exactly once (a plain
    # psum transposes to psum under the manual region's check_vma=False,
    # which would scale every gradient by the stage count)
    loss = L.tp_pull(loss_sum, pipe.axis) / m
    if cfg.family == "moe":
        loss = loss + 0.01 * L.tp_pull(lb_sum, pipe.axis) / (p * m)
    return loss


# ================================================================= decode
def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               window: Optional[int] = None, dtype=jnp.bfloat16):
    """Per-layer stacked decode caches for serve_step."""
    Lyr = cfg.n_layers
    if cfg.family == "ssm":
        st = init_mlstm_state(cfg, batch)
        return {"mix": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (Lyr, *x.shape)), st)}
    size = min(window, cache_len) if window else cache_len
    kv = {"k": jnp.zeros((Lyr, batch, size, cfg.n_kv_heads, cfg.hd), dtype),
          "v": jnp.zeros((Lyr, batch, size, cfg.n_kv_heads, cfg.hd), dtype)}
    if cfg.family == "hybrid":
        ssm = jnp.zeros((Lyr, batch, cfg.d_model, cfg.ssm_state), jnp.float32)
        return {"kv": kv, "ssm": ssm}
    return {"kv": kv}


def decode_step(params, cfg: ModelConfig, cache, token, pos,
                window: Optional[int] = None):
    """serve_step: one new token per sequence against the cache.

    token: (B, 1) int32; pos: scalar int32 absolute position.
    Returns (logits (B, 1, V), new_cache).
    """
    x = params["embed"][token]
    B = x.shape[0]
    positions = jnp.broadcast_to(pos, (B, 1))

    def body(carry, scanned):
        h = carry
        lp, layer_cache = scanned
        h, new_cache, _ = _block(cfg, lp, h, positions, "decode",
                                 layer_cache, window)
        return h, new_cache

    x, new_caches = jax.lax.scan(body, x, (params["blocks"], cache))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, new_caches


# ========================================================== paged decode
# The serving engine's cache is a global pool of fixed-size blocks
# (repro/serve/cache.py); each request owns a block table.  The decode
# step below is the batched per-request-position twin of ``decode_step``:
# every row carries its OWN absolute position (continuous batching mixes
# requests at different depths), K/V write through the block table, and
# attention gathers through it (the Pallas kernel in
# ``kernels/paged_attention`` or its jnp reference).

def paged_families() -> tuple:
    """Families the paged decode path serves (pure-KV caches; the
    recurrent ssm/hybrid states are per-request dense, not pageable)."""
    return ("dense", "moe", "audio", "vlm")


def init_paged_pools(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> dict:
    """Per-layer stacked K/V block pools: (L, N, KV, bs, hd)."""
    if cfg.family not in paged_families():
        raise ValueError(
            f"paged KV cache supports families {paged_families()}, not "
            f"{cfg.family!r} (recurrent state is per-request, not paged)")
    shape = (cfg.n_layers, num_blocks, cfg.n_kv_heads, block_size, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attn_paged(cfg: ModelConfig, lp, x, positions, k_pool, v_pool,
                block_tables, ctx_lens, window, use_kernel, tp=None):
    """One layer's attention against the paged pools.  x: (B, 1, D);
    positions/ctx_lens: (B, 1)/(B,) — the new token's absolute position.
    Returns (x_out, k_pool, v_pool) with the new K/V scattered in.

    With ``tp`` (inside a manual shard_map serve body) the wq/wk/wv/wo
    shards and the pools' kv-head shard are this position's — the Pallas
    kernel sees local head counts, exactly the train path's contract."""
    from repro.kernels import paged_attention as pa
    tp_attn = tp is not None and tp.plan.attn
    n_heads = cfg.n_heads // (tp.size if tp_attn else 1)
    n_kv = cfg.n_kv_heads // (tp.size if tp_attn else 1)
    B = x.shape[0]
    bs = k_pool.shape[2]
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, 1, n_heads, cfg.hd)
    k = k.reshape(B, 1, n_kv, cfg.hd)
    v = v.reshape(B, 1, n_kv, cfg.hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    # scatter the new K/V through the block table: logical position
    # ctx_lens[b] lives at (block_tables[b, ctx//bs], ctx%bs)
    pages = block_tables[jnp.arange(B), ctx_lens // bs]
    offs = ctx_lens % bs
    k_pool = k_pool.at[pages, :, offs].set(
        k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[pages, :, offs].set(
        v[:, 0].astype(v_pool.dtype))
    fn = pa.paged_attention if use_kernel else pa.paged_attention_ref
    if use_kernel and not pa.supports(n_heads, n_kv, cfg.hd):
        from repro.kernels.common import log_fallback
        log_fallback("paged_attention", f"H={n_heads} KV={n_kv} "
                     f"hd={cfg.hd} is outside the kernel's shape gate")
        fn = pa.paged_attention_ref
    out = fn(q[:, 0], k_pool, v_pool, block_tables, ctx_lens + 1,
             window=window)
    y = out.reshape(B, 1, n_heads * cfg.hd) @ lp["wo"]
    if tp_attn:
        y = jax.lax.psum(y, tp.axis)        # row-parallel wo partials
    return x + y, k_pool, v_pool


def paged_decode_step(params, cfg: ModelConfig, pools, block_tables,
                      context_lens, tokens,
                      window: Optional[int] = None,
                      use_kernel: bool = True,
                      tp: Optional[TPRuntime] = None):
    """One decode step for a batch of requests at DIFFERENT positions.

    tokens: (B, 1) int32 — each row's newest token
    context_lens: (B,) int32 — tokens already cached per row (the new
        token's absolute position); inactive rows pass 0 with a
        scratch-block table and produce garbage logits that the engine
        masks out
    pools: ``init_paged_pools`` tree; block_tables: (B, P) int32

    With ``tp`` (inside a manual shard_map serve body) params and the
    pools' kv-head dim are this position's shards; logits come back FULL
    (an all_gather over the model axis after the column-parallel unembed)
    so the engine's row-wise sampler is unchanged.

    Returns (logits (B, 1, V), new_pools).
    """
    x = embed_inputs(params, cfg, tokens, None, tp)
    B = x.shape[0]
    positions = jnp.broadcast_to(context_lens[:, None], (B, 1))

    def body(carry, scanned):
        h = carry
        lp, layer_pools = scanned
        h, kp, vp = _attn_paged(cfg, lp, h, positions,
                                layer_pools["k"], layer_pools["v"],
                                block_tables, context_lens, window,
                                use_kernel, tp)
        if cfg.family == "moe":
            hh = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            y, _ = moe_lib.moe_ffn(hh, lp["router"], lp["w_gate"],
                                   lp["w_up"], lp["w_down"],
                                   top_k=cfg.top_k,
                                   capacity_factor=cfg.capacity_factor,
                                   group=cfg.moe_group_size, tp=tp)
            h = h + y
        else:
            h = _ffn(cfg, lp, h, tp)
        return h, {"k": kp, "v": vp}

    x, new_pools = jax.lax.scan(body, x, (params["blocks"], pools))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    if tp is not None and tp.plan.vocab:
        logits = jax.lax.all_gather(logits, tp.axis, axis=2, tiled=True)
    return logits, new_pools
