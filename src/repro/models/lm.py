"""Model classes for the assigned architecture zoo.

  DecoderLM  — dense / MoE / gemma3-style local:global patterns (uniform scan
               over layers: compile size O(1) in depth).
  Mamba2LM   — attention-free SSD stack.
  HybridLM   — recurrentgemma (R,R,A period scan: RG-LRU + local attention).
  EncDecLM   — whisper backbone (bidirectional encoder + cross-attn decoder;
               conv/mel frontend STUBBED: input_specs provides frame embeds).
  VLM        — internvl backbone (patch-embedding stub -> projector -> LM).

Common interface:
  init_params(key)          -> pytree (stacked per-layer leaves)
  loss(params, batch)       -> (scalar, metrics)   [train_4k]
  prefill(params, batch)    -> (last_logits, cache) [prefill_32k]
  decode_step(params, cache, tokens) -> (logits, cache) [decode_32k/long_500k]
  init_cache(batch, cache_len, dtype) -> pytree
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (apply_rope, attention_decode, attention_train,
                     attn_params, cross_attention, dense_init, mlp_params,
                     rmsnorm, rope_freqs, swiglu)
from .moe import moe_ffn, moe_params
from .rglru import rglru_decode, rglru_params, rglru_train
from .ssm import ssd_layer_decode, ssd_layer_train, ssd_params

Array = Any


def _embed_params(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 3)
    p = {"embed": dense_init(ks[0], (cfg.vocab_padded, cfg.d_model), dtype,
                             scale=0.02),
         "final_norm": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_padded),
                                  dtype)
    return p


def _logits(x, params, cfg):
    """Full-vocab logits in the COMPUTE dtype with the pad mask fused as an
    additive min-value (not an f32 where): the (B,T,Vp) tensor dominates HBM
    bytes for big-vocab training cells, so it stays bf16 end-to-end in
    deployment (§Perf iteration A2); f32/f64 in tests."""
    if cfg.tie_embeddings:
        lg = x @ params["embed"].T
    else:
        lg = x @ params["unembed"]
    V = cfg.vocab_size
    col = jnp.arange(cfg.vocab_padded)
    neg = jnp.asarray(jnp.finfo(lg.dtype).min / 8, lg.dtype)
    return jnp.where(col[None, None, :] < V, lg, neg)


class ActivationSharding:
    """Batch-dim sharding constraint applied at block boundaries.

    Without it GSPMD may trade batch sharding away (measured on
    qwen prefill_32k: the partitioner replicated the global batch over `data`
    and sharded attention over kv-heads => 16x redundant T^2 compute+bytes;
    §Perf iteration A3). Factories (train/serve) attach an instance to the
    model; mesh=None (tests/CPU) is a no-op.
    """

    def __init__(self, mesh=None):
        self.mesh = mesh
        if mesh is not None:
            self.daxes = tuple(a for a in ("pod", "data")
                               if a in mesh.axis_names)

    def __call__(self, x):
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(*([self.daxes] + [None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


def xent_loss(logits, labels):
    """logits (B,T,Vp) any float dtype, labels (B,T). Max/sum statistics are
    accumulated in f32; the big tensors are never upcast."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    e = jnp.exp(logits - m)
    s = jnp.sum(e.astype(jnp.float32), axis=-1)
    lse = jnp.log(s) + m[..., 0].astype(jnp.float32)
    tgt = jnp.take_along_axis(logits, labels[..., None],
                              axis=-1)[..., 0].astype(jnp.float32)
    return jnp.mean(lse - tgt)


# ===========================================================================
# DecoderLM: dense / moe / gemma3 local-global
# ===========================================================================

class DecoderLM:
    def __init__(self, cfg: ModelConfig, dtype=jnp.bfloat16, remat=False,
                 moe_group=4096, moe_cf=1.25, unroll=1):
        self.cfg = cfg
        self.dtype = dtype
        self.remat = remat
        self.moe_group = moe_group
        self.moe_cf = moe_cf  # None => no-drop (used by inference paths)
        # inference capacity: None = no-drop exactness (tests); the serve
        # factory sets a finite factor (2.0) for deployment shapes — no-drop
        # dispatch buffers at 32k prefill are E/topk-times over-provisioned
        # (grok: 8/2 = 4x, measured 52 GiB/device)
        self.moe_inference_cf = None
        # unroll=True: unroll layer scans (XLA cost analysis counts a rolled
        # scan body only ONCE)
        self.unroll = unroll
        self.act_shard = ActivationSharding(None)
        # q_chunk>0: memory-efficient attention over query blocks (set by the
        # serve/train factories for long-context deployment shapes)
        self.q_chunk = 0
        # per-layer is_global flags (gemma3 pattern; all-global otherwise)
        if cfg.global_every:
            flags = [(i + 1) % cfg.global_every == 0
                     for i in range(cfg.n_layers)]
        else:
            flags = [True] * cfg.n_layers
        self.layer_global = jnp.asarray(flags)

    # ---- params ----
    def _block_params(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        p = {"attn": attn_params(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.hd, self.dtype, cfg.qkv_bias),
             "ln1": jnp.zeros((cfg.d_model,), self.dtype),
             "ln2": jnp.zeros((cfg.d_model,), self.dtype)}
        if cfg.family == "moe":
            p["moe"] = moe_params(k2, cfg.d_model, cfg.moe_d_ff,
                                  cfg.n_experts, cfg.n_shared_experts,
                                  self.dtype)
        else:
            p["mlp"] = mlp_params(k2, cfg.d_model, cfg.d_ff, self.dtype)
        return p

    def init_params(self, key):
        cfg = self.cfg
        ke, kb = jax.random.split(key)
        params = _embed_params(ke, cfg, self.dtype)
        params["blocks"] = jax.vmap(self._block_params)(
            jax.random.split(kb, cfg.n_layers))
        return params

    # ---- blocks ----
    def _attn_kwargs(self):
        cfg = self.cfg
        return dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=cfg.hd,
                    rope_theta=cfg.rope_theta, window=cfg.window,
                    softcap=cfg.attn_softcap, q_chunk=self.q_chunk)

    def _block_train(self, p, x, is_global, aux):
        cfg = self.cfg
        x = self.act_shard(x)
        bias = ({k: p["attn"][k] for k in ("bq", "bk", "bv")}
                if cfg.qkv_bias else None)
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = x + attention_train(h, p["attn"], is_global=is_global, bias=bias,
                                **self._attn_kwargs())
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, a = moe_ffn(h, p["moe"], topk=cfg.topk,
                           n_experts=cfg.n_experts,
                           capacity_factor=self.moe_cf,
                           group_size=self.moe_group)
            aux = aux + a
        else:
            y = swiglu(h, p["mlp"])
        return x + y, aux

    def forward(self, params, tokens, h0=None):
        """Full-sequence compute (train / prefill). Returns (x, aux, kv):
        kv = (k, v) stacked (L, B, T, KV, hd) for cache building."""
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype) if h0 is None else h0

        def body(carry, xs):
            x, aux = carry
            p, is_global = xs
            x, aux = block_fn(p, x, is_global, aux)
            return (x, aux), None

        block_fn = self._block_train
        if self.remat:
            # remat="dots": save matmul outputs (incl. FSDP-gathered weight
            # products) so the backward pass re-gathers nothing — trades
            # activation memory for ~1/3 of the gather collective traffic
            # (§Perf iteration B1). remat=True: full recompute (min memory).
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if self.remat == "dots" else None)
            block_fn = jax.checkpoint(block_fn, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.asarray(0.0, jnp.float32)),
                                   (params["blocks"], self.layer_global),
                                   unroll=self.unroll)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return x, aux

    def loss(self, params, batch):
        x, aux = self.forward(params, batch["tokens"])
        logits = _logits(x, params, self.cfg)
        ce = xent_loss(logits[:, :-1], batch["labels"][:, 1:])
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    # ---- serving ----
    def init_cache(self, batch, cache_len, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.dtype
        L = cfg.n_layers
        shape = (L, batch, cache_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "pos": jnp.zeros((), jnp.int32)}

    def prefill(self, params, batch, cache_len=None):
        """Prompt pass: returns (last-position logits, filled cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        cache_len = cache_len or T
        x = params["embed"][tokens].astype(self.dtype)

        def body(carry, xs):
            x, aux = carry
            p, is_global = xs
            x = self.act_shard(x)
            bias = ({k: p["attn"][k] for k in ("bq", "bk", "bv")}
                    if cfg.qkv_bias else None)
            h = rmsnorm(x, p["ln1"], cfg.norm_eps)
            # recompute k/v for cache (train attention already rope-encodes)
            k = h @ p["attn"]["wk"]
            v = h @ p["attn"]["wv"]
            if bias is not None:
                k = k + bias["bk"]
                v = v + bias["bv"]
            k = k.reshape(B, T, cfg.n_kv_heads, cfg.hd)
            v = v.reshape(B, T, cfg.n_kv_heads, cfg.hd)
            cos, sin = rope_freqs(cfg.hd, cfg.rope_theta, jnp.arange(T))
            k = apply_rope(k, cos, sin)
            x = x + attention_train(h, p["attn"], is_global=is_global,
                                    bias=bias, **self._attn_kwargs())
            h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, a = moe_ffn(h2, p["moe"], topk=cfg.topk,
                               n_experts=cfg.n_experts,
                               capacity_factor=self.moe_inference_cf,
                               group_size=self.moe_group)
                aux = aux + a
            else:
                y = swiglu(h2, p["mlp"])
            return (x + y, aux), (k, v)

        (x, aux), (ks, vs) = jax.lax.scan(
            body, (x, jnp.asarray(0.0, jnp.float32)),
            (params["blocks"], self.layer_global), unroll=self.unroll)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _logits(x[:, -1:], params, cfg)
        pad = cache_len - T
        if pad > 0:
            ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache = {"k": ks, "v": vs, "pos": jnp.asarray(T, jnp.int32)}
        return logits, cache

    def decode_step(self, params, cache, tokens):
        """tokens (B, 1) -> (logits (B,1,Vp), new cache)."""
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)
        pos = cache["pos"]

        def body(x, xs):
            p, is_global, ck, cv = xs
            bias = ({k: p["attn"][k] for k in ("bq", "bk", "bv")}
                    if cfg.qkv_bias else None)
            h = rmsnorm(x, p["ln1"], cfg.norm_eps)
            lc = {"k": ck, "v": cv, "pos": pos}
            a, lc = attention_decode(h, p["attn"], lc, is_global=is_global,
                                     bias=bias, **self._attn_kwargs())
            x = x + a
            h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, _ = moe_ffn(h2, p["moe"], topk=cfg.topk,
                               n_experts=cfg.n_experts,
                               capacity_factor=self.moe_inference_cf,
                               group_size=x.shape[0])
            else:
                y = swiglu(h2, p["mlp"])
            return x + y, (lc["k"], lc["v"])

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], self.layer_global,
                      cache["k"], cache["v"]), unroll=self.unroll)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _logits(x, params, cfg), {"k": ks, "v": vs, "pos": pos + 1}


# ===========================================================================
# Mamba2LM
# ===========================================================================

class Mamba2LM:
    def __init__(self, cfg: ModelConfig, dtype=jnp.bfloat16, remat=False,
                 ssd_chunk=256, unroll=1):
        self.cfg = cfg
        self.dtype = dtype
        self.remat = remat
        self.ssd_chunk = ssd_chunk
        self.unroll = unroll
        self.act_shard = ActivationSharding(None)
        self.q_chunk = 0  # inert (attention-free)

    def init_params(self, key):
        cfg = self.cfg
        ke, kb = jax.random.split(key)
        params = _embed_params(ke, cfg, self.dtype)

        def one(k):
            return {"ssd": ssd_params(k, cfg, self.dtype),
                    "ln": jnp.zeros((cfg.d_model,), self.dtype)}

        params["blocks"] = jax.vmap(one)(jax.random.split(kb, cfg.n_layers))
        return params

    def forward(self, params, tokens):
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)

        def block(p, x):
            x = self.act_shard(x)
            h = rmsnorm(x, p["ln"], cfg.norm_eps)
            y, _ = ssd_layer_train(h, p["ssd"], cfg, chunk=self.ssd_chunk)
            return x + y

        if self.remat:
            block = jax.checkpoint(block)

        def body(x, p):
            return block(p, x), None

        x, _ = jax.lax.scan(lambda c, p: (block(p, c), None), x,
                            params["blocks"], unroll=self.unroll)
        return rmsnorm(x, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch):
        x = self.forward(params, batch["tokens"])
        logits = _logits(x, params, self.cfg)
        ce = xent_loss(logits[:, :-1], batch["labels"][:, 1:])
        return ce, {"ce": ce, "aux": jnp.asarray(0.0)}

    def init_cache(self, batch, cache_len, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.dtype
        L = cfg.n_layers
        din, N = cfg.d_inner, cfg.ssm_state
        H, P = cfg.ssm_heads, cfg.ssm_head_dim
        K = cfg.ssm_conv
        return {"h": jnp.zeros((L, batch, H, P, N), jnp.float32),
                "conv": jnp.zeros((L, batch, K - 1, din + 2 * N), dtype),
                "pos": jnp.zeros((), jnp.int32)}

    def prefill(self, params, batch, cache_len=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = params["embed"][tokens].astype(self.dtype)

        def body(x, p):
            x = self.act_shard(x)
            h = rmsnorm(x, p["ln"], cfg.norm_eps)
            y, st = ssd_layer_train(h, p["ssd"], cfg, chunk=self.ssd_chunk)
            return x + y, (st["h"], st["conv"])

        x, (hs, convs) = jax.lax.scan(body, x, params["blocks"],
                                      unroll=self.unroll)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = _logits(x[:, -1:], params, cfg)
        cache = {"h": hs, "conv": convs,
                 "pos": jnp.asarray(tokens.shape[1], jnp.int32)}
        return logits, cache

    def decode_step(self, params, cache, tokens):
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)

        def body(x, xs):
            p, h, conv = xs
            hh = rmsnorm(x, p["ln"], cfg.norm_eps)
            y, st = ssd_layer_decode(hh, p["ssd"], cfg,
                                     {"h": h, "conv": conv})
            return x + y, (st["h"], st["conv"])

        x, (hs, convs) = jax.lax.scan(body, x, (params["blocks"], cache["h"],
                                                cache["conv"]),
                                      unroll=self.unroll)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _logits(x, params, cfg), {"h": hs, "conv": convs,
                                         "pos": cache["pos"] + 1}


# ===========================================================================
# HybridLM (recurrentgemma): period pattern (R, R, A)
# ===========================================================================

class HybridLM:
    def __init__(self, cfg: ModelConfig, dtype=jnp.bfloat16, remat=False,
                 unroll=1):
        self.cfg = cfg
        self.dtype = dtype
        self.remat = remat
        self.unroll = unroll
        self.act_shard = ActivationSharding(None)
        self.q_chunk = 0
        pat = cfg.block_pattern or ("R", "R", "A")
        self.pattern = pat
        self.period = len(pat)
        self.n_periods = cfg.n_layers // self.period
        self.rem = tuple(pat[:cfg.n_layers % self.period])
        self.W = cfg.rnn_width or cfg.d_model

    def _slot_params(self, key, kind):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        p = {"ln1": jnp.zeros((cfg.d_model,), self.dtype),
             "ln2": jnp.zeros((cfg.d_model,), self.dtype),
             "mlp": mlp_params(k2, cfg.d_model, cfg.d_ff, self.dtype)}
        if kind == "A":
            p["attn"] = attn_params(k1, cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.hd, self.dtype)
        else:
            p["rglru"] = rglru_params(k1, cfg.d_model, self.W, cfg.ssm_conv,
                                      self.dtype)
        return p

    def init_params(self, key):
        cfg = self.cfg
        ke, kb, kr = jax.random.split(key, 3)
        params = _embed_params(ke, cfg, self.dtype)
        slot_stacks = []
        for s, kind in enumerate(self.pattern):
            keys = jax.random.split(jax.random.fold_in(kb, s),
                                    self.n_periods)
            slot_stacks.append(jax.vmap(
                partial(self._slot_params, kind=kind))(keys))
        params["periods"] = tuple(slot_stacks)
        params["rem"] = tuple(
            self._slot_params(jax.random.fold_in(kr, i), kind)
            for i, kind in enumerate(self.rem))
        return params

    def _apply_slot(self, p, x, kind, mode, state=None):
        """mode: train|prefill|decode. Returns (x, new_state)."""
        cfg = self.cfg
        x = self.act_shard(x)
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if kind == "A":
            if mode == "decode":
                # ring-buffer window cache: eviction IS the sliding window
                a, state = attention_decode(
                    h, p["attn"], state, n_heads=cfg.n_heads,
                    n_kv=cfg.n_kv_heads, hd=cfg.hd,
                    rope_theta=cfg.rope_theta, window=0, is_global=True)
            else:
                a = attention_train(h, p["attn"], n_heads=cfg.n_heads,
                                    n_kv=cfg.n_kv_heads, hd=cfg.hd,
                                    rope_theta=cfg.rope_theta,
                                    window=cfg.window, is_global=False,
                                    q_chunk=self.q_chunk)
                if mode == "prefill":
                    B, T, _ = h.shape
                    k = (h @ p["attn"]["wk"]).reshape(B, T, cfg.n_kv_heads,
                                                      cfg.hd)
                    v = (h @ p["attn"]["wv"]).reshape(B, T, cfg.n_kv_heads,
                                                      cfg.hd)
                    cos, sin = rope_freqs(cfg.hd, cfg.rope_theta,
                                          jnp.arange(T))
                    k = apply_rope(k, cos, sin)
                    state = {"k": k, "v": v}
        else:
            if mode == "decode":
                a, state = rglru_decode(h, p["rglru"], state)
            else:
                a, state = rglru_train(h, p["rglru"],
                                       state if mode == "prefill" else None)
        x = x + a
        h2 = rmsnorm(x, p["ln2"], cfg.norm_eps)
        return x + swiglu(h2, p["mlp"]), state

    def forward(self, params, tokens):
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)

        def period_fn(x, slot_params):
            for s, kind in enumerate(self.pattern):
                x, _ = self._apply_slot(
                    jax.tree.map(lambda a: a, slot_params[s]), x, kind,
                    "train")
            return x

        if self.remat:
            period_fn = jax.checkpoint(period_fn)

        def body(x, slot_params):
            return period_fn(x, slot_params), None

        x, _ = jax.lax.scan(body, x, params["periods"],
                            unroll=self.unroll)
        for i, kind in enumerate(self.rem):
            x, _ = self._apply_slot(params["rem"][i], x, kind, "train")
        return rmsnorm(x, params["final_norm"], cfg.norm_eps)

    def loss(self, params, batch):
        x = self.forward(params, batch["tokens"])
        logits = _logits(x, params, self.cfg)
        ce = xent_loss(logits[:, :-1], batch["labels"][:, 1:])
        return ce, {"ce": ce, "aux": jnp.asarray(0.0)}

    # serving: caches per slot kind. Attention slots keep a WINDOW-sized
    # cache (ring buffer semantics via position clamp) — RG-LRU state is O(1):
    # this is what makes long_500k run for this family.
    def init_cache(self, batch, cache_len, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.dtype
        wlen = min(cache_len, cfg.window) if cfg.window else cache_len
        K = cfg.ssm_conv
        caches = []
        for s, kind in enumerate(self.pattern):
            if kind == "A":
                caches.append({
                    "k": jnp.zeros((self.n_periods, batch, wlen,
                                    cfg.n_kv_heads, cfg.hd), dtype),
                    "v": jnp.zeros((self.n_periods, batch, wlen,
                                    cfg.n_kv_heads, cfg.hd), dtype)})
            else:
                caches.append({
                    "h": jnp.zeros((self.n_periods, batch, self.W),
                                   jnp.float32),
                    "conv": jnp.zeros((self.n_periods, batch, K - 1, self.W),
                                      dtype)})
        rem = []
        for kind in self.rem:
            if kind == "A":
                rem.append({"k": jnp.zeros((batch, wlen, cfg.n_kv_heads,
                                            cfg.hd), dtype),
                            "v": jnp.zeros((batch, wlen, cfg.n_kv_heads,
                                            cfg.hd), dtype)})
            else:
                rem.append({"h": jnp.zeros((batch, self.W), jnp.float32),
                            "conv": jnp.zeros((batch, K - 1, self.W), dtype)})
        return {"slots": tuple(caches), "rem": tuple(rem),
                "pos": jnp.zeros((), jnp.int32)}

    def decode_step(self, params, cache, tokens):
        cfg = self.cfg
        x = params["embed"][tokens].astype(self.dtype)
        pos = cache["pos"]
        wlen = cache["slots"][self.pattern.index("A")]["k"].shape[2] \
            if "A" in self.pattern else 0

        def body(x, xs):
            slot_params = xs[0]
            slot_caches = xs[1]
            new_caches = []
            for s, kind in enumerate(self.pattern):
                st = dict(slot_caches[s])
                if kind == "A":
                    st["pos"] = pos                      # absolute (rope)
                    st["write_idx"] = pos % wlen         # ring slot
                x, st = self._apply_slot(slot_params[s], x, kind, "decode",
                                         state=st)
                if kind == "A":
                    st = {"k": st["k"], "v": st["v"]}
                new_caches.append(st)
            return x, tuple(new_caches)

        x, new_slots = jax.lax.scan(body, x,
                                    (params["periods"], cache["slots"]),
                                    unroll=self.unroll)
        rem_new = []
        for i, kind in enumerate(self.rem):
            st = dict(cache["rem"][i])
            if kind == "A":
                st["pos"] = pos
                st["write_idx"] = pos % wlen
            x, st = self._apply_slot(params["rem"][i], x, kind, "decode",
                                     state=st)
            if kind == "A":
                st = {"k": st["k"], "v": st["v"]}
            rem_new.append(st)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return _logits(x, params, cfg), {"slots": new_slots,
                                         "rem": tuple(rem_new),
                                         "pos": pos + 1}

    def prefill(self, params, batch, cache_len=None):
        # prefill = forward + state capture; window caches keep the LAST
        # `wlen` keys placed at their ring slots (slot = position % wlen).
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        cache_len = cache_len or T
        x = params["embed"][tokens].astype(self.dtype)
        wlen = min(cache_len, cfg.window) if cfg.window else cache_len

        def to_ring(k):
            """(B, T, KV, hd) -> (B, wlen, KV, hd) at ring slots."""
            if T >= wlen:
                kept = k[:, -wlen:]
                return jnp.roll(kept, T % wlen, axis=1)
            pad = [(0, 0), (0, wlen - T)] + [(0, 0)] * (k.ndim - 2)
            return jnp.pad(k, pad)

        def run_slot(x, p, kind):
            return self._apply_slot(p, x, kind, "prefill")

        x_cur = x
        collected = [[] for _ in self.pattern]
        for c in range(self.n_periods):
            for s, kind in enumerate(self.pattern):
                p = jax.tree.map(lambda a: a[c], params["periods"][s])
                x_cur, st = run_slot(x_cur, p, kind)
                if kind == "A":
                    st = {"k": to_ring(st["k"]), "v": to_ring(st["v"])}
                collected[s].append(st)
        rem_states = []
        for i, kind in enumerate(self.rem):
            x_cur, st = run_slot(x_cur, params["rem"][i], kind)
            if kind == "A":
                st = {"k": to_ring(st["k"]), "v": to_ring(st["v"])}
            rem_states.append(st)
        slots = tuple(jax.tree.map(lambda *xs: jnp.stack(xs), *col)
                      for col in collected)
        x_cur = rmsnorm(x_cur, params["final_norm"], cfg.norm_eps)
        logits = _logits(x_cur[:, -1:], params, cfg)
        return logits, {"slots": slots, "rem": tuple(rem_states),
                        "pos": jnp.asarray(T, jnp.int32)}
