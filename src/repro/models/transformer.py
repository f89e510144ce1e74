"""Composable LM supporting every assigned architecture family.

One parameterized decoder stack covers dense / MoE / SSM / hybrid / VLM
(prefix) models; an optional encoder stack + cross-attention covers the
enc-dec (whisper) family.  Layers follow ``cfg.layer_pattern`` (a repeating
cycle of mixer kinds); full pattern groups are stacked and driven by
``lax.scan`` so the HLO stays one-group-sized regardless of depth, with the
remainder layers unrolled.

Modes:
  * ``forward(...)``          -- train/prefill: (B, S) tokens -> hidden
  * ``lm_loss(...)``          -- fused vocab-parallel softmax-xent
  * ``init_cache/decode_step``-- single-token serving with KV/state caches

TP details (all surfaced in the roofline):
  * query heads padded to a multiple of TP, KV heads replicated to cover
    shards (Megatron GQA rule); vocab padded to a multiple of 128;
  * embedding lookup and the loss run in ``shard_map`` (masked local lookup
    + psum) so the 200k-row tables never get gathered;
  * attention uses the blockwise online-softmax path (flash in XLA); the
    Pallas kernels replace it on real TPUs.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.parallel.sharding import (get_mesh, get_rules, logical, resolve,
                                     shard)

ATTN_KINDS = ("attn", "swa", "chunked", "enc")


# ============================================================== init


def _init_attn(key, cfg: ModelConfig, tp: int, dtype, cross: bool = False,
               kv_pad: bool = True):
    d, hd = cfg.d_model, cfg.head_dim
    hq = cfg.padded_heads(tp)
    kv = cfg.padded_kv_heads(tp) if kv_pad else max(cfg.n_kv_heads, 1)
    if hq % kv:
        kv = cfg.padded_kv_heads(tp)   # dedup needs integer GQA groups
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, hq * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (hq * hd, d)) /
               math.sqrt(hq * hd)).astype(dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _init_layer(key, cfg: ModelConfig, kind: str, layer_idx: int, tp: int,
                dtype, cross: bool = False, kv_pad: bool = True) -> Dict:
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": L.init_norm(ks[0], cfg.d_model, cfg.norm)}
    if kind in ATTN_KINDS:
        p["attn"] = _init_attn(ks[1], cfg, tp, dtype, kv_pad=kv_pad)
    elif kind == "ssd":
        p["ssd"] = SSM.init_ssd_block(ks[1], cfg, dtype)
    elif kind == "rglru":
        p["rglru"] = RG.init_rglru_block(ks[1], cfg, dtype)
    if cross:
        p["normx"] = L.init_norm(ks[2], cfg.d_model, cfg.norm)
        p["xattn"] = _init_attn(ks[3], cfg, tp, dtype, cross=True)
    if cfg.d_ff > 0:
        p["norm2"] = L.init_norm(ks[4], cfg.d_model, cfg.norm)
        is_moe = cfg.n_experts and (layer_idx % cfg.moe_every
                                    == cfg.moe_every - 1)
        if is_moe:
            p["moe"] = MOE.init_moe(ks[5], cfg, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[5], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def init_params(cfg: ModelConfig, key, tp: int = 1,
                dtype=jnp.bfloat16, kv_pad: bool = True) -> Dict:
    """Build the full parameter pytree.

    Stacking: layers are grouped by full cycles of ``cfg.layer_pattern``;
    each group slot holds arrays with a leading ``n_groups`` dim for scan.
    MoE interleaving must be compatible with the pattern cycle (asserted).
    """
    pat = cfg.layer_pattern
    plen = len(pat)
    cycle = plen
    if cfg.n_experts and cfg.moe_every > 1:
        # group length must be a multiple of moe_every for uniform stacking
        cycle = plen * cfg.moe_every // math.gcd(plen, cfg.moe_every)
    n_groups = cfg.num_layers // cycle
    rest = cfg.num_layers - n_groups * cycle

    keys = jax.random.split(key, cfg.num_layers + 8)
    cross = cfg.is_encdec

    def layer_p(i):
        return _init_layer(keys[i], cfg, cfg.pattern_at(i), i, tp, dtype,
                           cross=cross, kv_pad=kv_pad)

    groups = []
    if n_groups:
        slot_params = []
        for s in range(cycle):
            per_group = [layer_p(g * cycle + s) for g in range(n_groups)]
            slot_params.append(jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_group))
        groups = slot_params
    rest_params = [layer_p(n_groups * cycle + i) for i in range(rest)]

    vp = cfg.padded_vocab()
    emb = (jax.random.normal(keys[-1], (vp, cfg.d_model)) * 0.02).astype(dtype)
    params: Dict[str, Any] = {
        "embed": emb,
        "groups": groups,
        "rest": rest_params,
        "final_norm": L.init_norm(keys[-2], cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[-3], (cfg.d_model, vp))
                             * 0.02).astype(dtype)
    if cfg.is_encdec:
        ek = jax.random.split(keys[-4], cfg.enc_layers + 1)
        enc_layers = [
            _init_layer(ek[i], cfg, "enc", i, tp, dtype) for i in
            range(cfg.enc_layers)]
        params["enc"] = {
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers),
            "final_norm": L.init_norm(ek[-1], cfg.d_model, cfg.norm),
        }
    return params


# ============================================================== embedding


def embed_tokens(params: Dict, cfg: ModelConfig, ids: jnp.ndarray) -> jnp.ndarray:
    """Vocab-parallel embedding lookup (masked local take + psum)."""
    mesh = get_mesh()
    rules = get_rules()
    emb = params["embed"]
    if mesh is None or rules is None or rules.get("vocab") is None:
        return jnp.take(emb, ids, axis=0).astype(emb.dtype)

    axis = rules["vocab"]
    batch = rules.get("batch")

    def body(emb_l, ids_l):
        vs = emb_l.shape[0]
        off = lax.axis_index(axis) * vs
        loc = ids_l - off
        ok = (loc >= 0) & (loc < vs)
        out = jnp.take(emb_l, jnp.clip(loc, 0, vs - 1), axis=0)
        out = jnp.where(ok[..., None], out, jnp.zeros_like(out))
        return lax.psum(out, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(batch, None)),
        out_specs=P(batch, None, None))(emb, ids)


def lm_loss(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
            labels: jnp.ndarray) -> jnp.ndarray:
    """Fused vocab-parallel softmax cross-entropy; returns mean token loss.

    Never materializes replicated (B, S, V) logits: each model shard keeps
    its vocab slice, reduces max/sum/label-pick over the model axis.
    """
    w = (params["lm_head"] if "lm_head" in params
         else params["embed"].T)
    mesh = get_mesh()
    rules = get_rules()
    if mesh is None or rules is None or rules.get("vocab") is None:
        logits = (x @ w).astype(jnp.float32)
        logits = logits[..., :cfg.vocab_size]
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - lab)

    axis = rules["vocab"]
    batch = rules.get("batch")
    vp = w.shape[-1]

    def body(x_l, w_l, labels_l):
        vs = w_l.shape[-1]
        off = lax.axis_index(axis) * vs
        logits = (x_l @ w_l).astype(jnp.float32)          # (b,s,vs)
        # mask vocab padding (global ids >= cfg.vocab_size)
        gids = off + jnp.arange(vs)
        logits = jnp.where(gids < cfg.vocab_size, logits, -1e30)
        # stability max carries no gradient (d/d_mx of lse - lab == 0);
        # stop_gradient goes *inside* pmax so its JVP sees a symbolic zero
        mx = lax.pmax(lax.stop_gradient(jnp.max(logits, -1)), axis)  # (b,s)
        se = lax.psum(jnp.sum(jnp.exp(logits - mx[..., None]), -1), axis)
        loc = labels_l - off
        ok = (loc >= 0) & (loc < vs)
        lab = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, vs - 1)[..., None], axis=-1)[..., 0]
        lab = lax.psum(jnp.where(ok, lab, 0.0), axis)
        loss = (mx + jnp.log(se)) - lab                    # (b,s)
        return loss

    per_tok = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch, None, None), P(None, axis), P(batch, None)),
        out_specs=P(batch, None))(x, w, labels)
    return jnp.mean(per_tok)


# ============================================================== layer apply


def _attn_apply(p: Dict, cfg: ModelConfig, x: jnp.ndarray, kind: str,
                positions: jnp.ndarray, prefix_len: int = 0,
                kv_override: Optional[Tuple] = None) -> jnp.ndarray:
    """Full-sequence attention (train/prefill).  x: (B, S, d)."""
    b, s, d = x.shape
    hd = cfg.head_dim
    hq = p["wq"].shape[-1] // hd
    kvh = p["wk"].shape[-1] // hd
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = shard(q, logical("batch", None, "heads"))
    q = q.reshape(b, s, hq, hd)
    if kv_override is None:
        k = x @ p["wk"]
        v = x @ p["wv"]
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = shard(k, logical("batch", None, "kv_heads")).reshape(b, -1, kvh, hd)
        v = shard(v, logical("batch", None, "kv_heads")).reshape(b, -1, kvh, hd)
        kv_pos = positions
    else:
        k, v, kv_pos = kv_override
    if kind != "enc" and kv_override is None:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, kv_pos, cfg.rope_theta)

    causal = kind != "enc" and kv_override is None
    window = cfg.window if kind == "swa" else 0
    chunk = cfg.window if kind == "chunked" else 0
    out = L.flash_attention_xla(q, k, v, causal=causal, window=window,
                                chunk=chunk, prefix_len=prefix_len)
    out = out.reshape(b, s, hq * hd)
    y = out @ p["wo"]
    return shard(y, logical("batch", "seq_sp", None))


def _layer_apply(p: Dict, cfg: ModelConfig, kind: str, x: jnp.ndarray,
                 positions: jnp.ndarray, prefix_len: int,
                 enc_kv: Optional[Tuple], moe_ctx: Dict) -> jnp.ndarray:
    h = L.norm(x, p["norm1"], cfg.norm)
    # SP boundary: gather the sequence-sharded residual ONCE here, so the
    # q/k/v (and gate/up) projections don't each trigger their own
    # all-to-all reshard (measured 3x collective reduction on dense archs)
    h = shard(h, logical("batch", None, None))
    if kind in ATTN_KINDS:
        x = x + _attn_apply(p["attn"], cfg, h, kind, positions, prefix_len)
    elif kind == "ssd":
        y, _ = SSM.ssd_block_apply(p["ssd"], cfg, h)
        x = x + y
    elif kind == "rglru":
        y, _ = RG.rglru_block_apply(p["rglru"], cfg, h)
        x = x + y
    if "xattn" in p and enc_kv is not None:
        hx = L.norm(x, p["normx"], cfg.norm)
        x = x + _attn_apply(p["xattn"], cfg, hx, "attn", positions,
                            kv_override=enc_kv)
    if "mlp" in p:
        h2 = L.norm(x, p["norm2"], cfg.norm)
        h2 = shard(h2, logical("batch", None, None))  # single SP gather
        x = x + L.mlp_apply(p["mlp"], h2, cfg.act)
    elif "moe" in p:
        h2 = L.norm(x, p["norm2"], cfg.norm)
        h2 = shard(h2, logical("batch", None, None))
        x = x + _moe_dispatch(p["moe"], cfg, h2, moe_ctx)
    return x


def _moe_dispatch(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                  moe_ctx: Dict) -> jnp.ndarray:
    """Run the MoE layer inside shard_map over the full mesh (per-shard
    dispatch locality); falls back to plain local compute without a mesh."""
    mesh = get_mesh()
    rules = get_rules()
    impl = moe_ctx.get("moe_impl", "tp")
    if mesh is None or rules is None or rules.get("ff") is None:
        return MOE.moe_apply_local(p, cfg, x, tp=1, moe_impl="tp")

    axis = rules["ff"]
    batch = rules.get("batch")
    tp = mesh.shape[axis] if axis else 1
    if impl == "tp":
        wspec = {"router": P(None, None), "w_up": P(None, None, axis),
                 "w_down": P(None, axis, None)}
        if "w_gate" in p:
            wspec["w_gate"] = P(None, None, axis)
    else:
        wspec = {"router": P(None, None), "w_up": P(axis, None, None),
                 "w_down": P(axis, None, None)}
        if "w_gate" in p:
            wspec["w_gate"] = P(axis, None, None)
    if "shared" in p:
        wspec["shared"] = {k: (P(None, axis) if k in ("w_up", "w_gate")
                               else P(axis, None))
                           for k in p["shared"]}

    def body(p_l, x_l):
        return MOE.moe_apply_local(
            p_l, cfg, x_l, axis_name=axis, moe_impl=impl,
            a2a_impl=moe_ctx.get("a2a_impl", "binary"),
            ar_impl=moe_ctx.get("ar_impl", "psum"), tp=tp)

    # check_vma off: replication of the output over the model axis comes
    # from the explicit ring all-reduce / all-to-all pair, which the static
    # replication checker cannot see through (ppermute chains).
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, P(batch, None, None)),
        out_specs=P(batch, None, None), check_vma=False)(p, x)


# ============================================================== forward


def forward(params: Dict, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            *, moe_ctx: Optional[Dict] = None,
            remat: bool = True) -> jnp.ndarray:
    """Token ids (+ stub modality embeddings) -> final hidden states."""
    moe_ctx = moe_ctx or {}
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens)
    prefix_len = 0
    if cfg.prefix_len and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        prefix_len = cfg.prefix_len
    x = shard(x, logical("batch", "seq_sp", None))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    enc_kv = None
    if cfg.is_encdec and "frames" in batch:
        enc_out = encode(params, cfg, batch["frames"])
        enc_kv = ("enc_out", enc_out)  # resolved per layer below

    pat = cfg.layer_pattern
    cycle = len(params["groups"]) if params["groups"] else 0

    def group_body(x, slot_params):
        for sidx, p in enumerate(slot_params):
            kind = pat[sidx % len(pat)]
            ekv = _enc_kv_for(p, cfg, enc_kv)
            x = _layer_apply(p, cfg, kind, x, positions, prefix_len, ekv,
                             moe_ctx)
        return x

    if params["groups"]:
        stacked = tuple(params["groups"])

        def scan_body(x, gp):
            fn = group_body
            if remat:
                fn = jax.checkpoint(group_body,
                                    policy=jax.checkpoint_policies.nothing_saveable)
            return fn(x, gp), None

        x, _ = lax.scan(scan_body, x, stacked)
    n_scanned = cfg.num_layers - len(params["rest"])
    for i, p in enumerate(params["rest"]):
        kind = cfg.pattern_at(n_scanned + i)
        ekv = _enc_kv_for(p, cfg, enc_kv)
        x = _layer_apply(p, cfg, kind, x, positions, prefix_len, ekv, moe_ctx)

    return L.norm(x, params["final_norm"], cfg.norm)


def _enc_kv_for(p: Dict, cfg: ModelConfig, enc_kv):
    """Project encoder output into this layer's cross-attn K/V."""
    if enc_kv is None or "xattn" not in p:
        return None
    _, enc_out = enc_kv
    hd = cfg.head_dim
    kvh = p["xattn"]["wk"].shape[-1] // hd
    b, se, _ = enc_out.shape
    k = (enc_out @ p["xattn"]["wk"]).reshape(b, se, kvh, hd)
    v = (enc_out @ p["xattn"]["wv"]).reshape(b, se, kvh, hd)
    pos = jnp.broadcast_to(jnp.arange(se)[None], (b, se))
    return (k, v, pos)


def encode(params: Dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    """Bidirectional encoder over stub frame embeddings (whisper)."""
    b, s, d = frames.shape
    # sinusoidal positions
    pos = jnp.arange(s)[:, None]
    dim = jnp.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    x = frames + pe[None].astype(frames.dtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    enc = params["enc"]

    def body(x, p):
        x = _layer_apply(p, cfg, "enc", x, positions, 0, None, {})
        return x, None

    x, _ = lax.scan(body, x, enc["layers"])
    return L.norm(x, enc["final_norm"], cfg.norm)


# ============================================================== serving


def _cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    if kind in ("swa", "chunked") and cfg.window:
        return min(cfg.window, max_len)
    return max_len


def _init_layer_cache(cfg: ModelConfig, kind: str, p: Dict, batch: int,
                      max_len: int, dtype=jnp.bfloat16) -> Dict:
    if kind in ATTN_KINDS:
        hd = cfg.head_dim
        kvh = p["attn"]["wk"].shape[-1] // hd
        wc = _cache_len(cfg, kind, max_len)
        c = {"k": jnp.zeros((batch, wc, kvh, hd), dtype),
             "v": jnp.zeros((batch, wc, kvh, hd), dtype),
             "pos": jnp.full((batch, wc), -1, jnp.int32)}
    elif kind == "ssd":
        c = SSM.init_ssd_cache(cfg, batch, dtype)
    elif kind == "rglru":
        c = RG.init_rglru_cache(cfg, batch, dtype)
    else:
        c = {}
    if "xattn" in p:
        hd = cfg.head_dim
        kvh = p["xattn"]["wk"].shape[-1] // hd
        c["xk"] = jnp.zeros((batch, cfg.enc_seq, kvh, hd), dtype)
        c["xv"] = jnp.zeros((batch, cfg.enc_seq, kvh, hd), dtype)
    return c


def init_cache(params: Dict, cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """Zeroed KV/state caches matching the params layout (scan-stacked)."""
    pat = cfg.layer_pattern
    groups = []
    if params["groups"]:
        n_groups = jax.tree.leaves(params["groups"][0])[0].shape[0]
        for sidx, slot in enumerate(params["groups"]):
            kind = pat[sidx % len(pat)]
            one = _init_layer_cache(cfg, kind,
                                    jax.tree.map(lambda x: x[0], slot),
                                    batch, max_len, dtype)
            groups.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), one))
    n_scanned = cfg.num_layers - len(params["rest"])
    rest = []
    for i, p in enumerate(params["rest"]):
        kind = cfg.pattern_at(n_scanned + i)
        rest.append(_init_layer_cache(cfg, kind, p, batch, max_len, dtype))
    return {"groups": groups, "rest": rest}


def cache_specs(params: Dict, cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree of the cache (for allocation-free lowering)."""
    return jax.eval_shape(
        lambda: init_cache(params, cfg, batch, max_len, dtype))


def _attn_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray, kind: str,
                 position: jnp.ndarray, cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One-token attention against the ring-buffer cache.

    x: (B, 1, d); position: (B,) absolute positions.
    """
    b = x.shape[0]
    hd = cfg.head_dim
    hq = p["wq"].shape[-1] // hd
    kvh = p["wk"].shape[-1] // hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q, logical("batch", None, "heads")).reshape(b, 1, hq, hd)
    k = shard(k, logical("batch", None, "kv_heads")).reshape(b, 1, kvh, hd)
    v = shard(v, logical("batch", None, "kv_heads")).reshape(b, 1, kvh, hd)
    pos_b = position[:, None]
    q = L.apply_rope(q, pos_b, cfg.rope_theta)
    k = L.apply_rope(k, pos_b, cfg.rope_theta)

    wc = cache["k"].shape[1]
    slot = position % wc
    bi = jnp.arange(b)
    kc = cache["k"].at[bi, slot].set(k[:, 0])
    vc = cache["v"].at[bi, slot].set(v[:, 0])
    pc = cache["pos"].at[bi, slot].set(position)

    window = cfg.window if kind == "swa" else 0
    chunk = cfg.window if kind == "chunked" else 0
    out = L.decode_attention_cache_xla(q, kc, vc, pc, position,
                                       window=window, chunk=chunk)
    y = out.reshape(b, 1, hq * hd) @ p["wo"]
    y = shard(y, logical("batch", None, None))
    return y, {"k": kc, "v": vc, "pos": pc, **{kk: cache[kk] for kk in
                                               ("xk", "xv") if kk in cache}}


def _layer_decode(p: Dict, cfg: ModelConfig, kind: str, x: jnp.ndarray,
                  position: jnp.ndarray, cache: Dict,
                  moe_ctx: Dict) -> Tuple[jnp.ndarray, Dict]:
    h = L.norm(x, p["norm1"], cfg.norm)
    new_cache = dict(cache)
    if kind in ATTN_KINDS:
        y, new_cache = _attn_decode(p["attn"], cfg, h, kind, position, cache)
        x = x + y
    elif kind == "ssd":
        y, c = SSM.ssd_block_apply(p["ssd"], cfg, h, cache, decode=True)
        new_cache.update(c)
        x = x + y
    elif kind == "rglru":
        y, c = RG.rglru_block_apply(p["rglru"], cfg, h, cache, decode=True)
        new_cache.update(c)
        x = x + y
    if "xattn" in p and "xk" in cache:
        hx = L.norm(x, p["normx"], cfg.norm)
        xa = p["xattn"]
        b = x.shape[0]
        hd = cfg.head_dim
        hq = xa["wq"].shape[-1] // hd
        q = (hx @ xa["wq"]).reshape(b, 1, hq, hd)
        out = L.decode_attention_xla(
            q, cache["xk"], cache["xv"],
            jnp.full((b,), cache["xk"].shape[1], jnp.int32))
        x = x + out.reshape(b, 1, hq * hd) @ xa["wo"]
    if "mlp" in p:
        h2 = L.norm(x, p["norm2"], cfg.norm)
        x = x + L.mlp_apply(p["mlp"], h2, cfg.act)
    elif "moe" in p:
        h2 = L.norm(x, p["norm2"], cfg.norm)
        x = x + _moe_dispatch(p["moe"], cfg, h2, moe_ctx)
    return x, new_cache


def decode_step(params: Dict, cfg: ModelConfig, cache: Dict,
                tokens: jnp.ndarray, position: jnp.ndarray,
                *, moe_ctx: Optional[Dict] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """One serving step: (B,1) tokens at (B,) positions -> (B,) next tokens
    plus the updated cache."""
    moe_ctx = moe_ctx or {}
    x = embed_tokens(params, cfg, tokens)
    x = shard(x, logical("batch", None, None))
    pat = cfg.layer_pattern

    new_groups = []
    if params["groups"]:
        def scan_body(x, inp):
            params_g, cache_g = inp
            new_c = []
            for sidx, (p, c) in enumerate(zip(params_g, cache_g)):
                kind = pat[sidx % len(pat)]
                x, nc = _layer_decode(p, cfg, kind, x, position, c, moe_ctx)
                new_c.append(nc)
            return x, tuple(new_c)

        x, stacked_caches = lax.scan(
            scan_body, x, (tuple(params["groups"]), tuple(cache["groups"])))
        new_groups = list(stacked_caches)

    n_scanned = cfg.num_layers - len(params["rest"])
    new_rest = []
    for i, p in enumerate(params["rest"]):
        kind = cfg.pattern_at(n_scanned + i)
        x, nc = _layer_decode(p, cfg, kind, x, position, cache["rest"][i],
                              moe_ctx)
        new_rest.append(nc)

    x = L.norm(x, params["final_norm"], cfg.norm)
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (x[:, 0] @ w).astype(jnp.float32)
    vmask = jnp.arange(logits.shape[-1]) < cfg.vocab_size
    logits = jnp.where(vmask[None], logits, -jnp.inf)
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tok, {"groups": new_groups, "rest": new_rest}


def encode_to_cache(params: Dict, cfg: ModelConfig, cache: Dict,
                    frames: jnp.ndarray) -> Dict:
    """Run the encoder and fill every decoder layer's cross-attention K/V
    (whisper serving: call once per utterance before decode_step)."""
    enc_out = encode(params, cfg, frames)
    b, se, _ = enc_out.shape
    hd = cfg.head_dim

    def proj(p):
        kvh = p["xattn"]["wk"].shape[-1] // hd
        xk = (enc_out @ p["xattn"]["wk"]).reshape(b, se, kvh, hd)
        xv = (enc_out @ p["xattn"]["wv"]).reshape(b, se, kvh, hd)
        return xk, xv

    new_groups = []
    for slot_p, slot_c in zip(params["groups"], cache["groups"]):
        n_groups = jax.tree.leaves(slot_p)[0].shape[0]
        xks, xvs = [], []
        for g in range(n_groups):
            p_g = jax.tree.map(lambda x: x[g], slot_p)
            xk, xv = proj(p_g)
            xks.append(xk)
            xvs.append(xv)
        c = dict(slot_c)
        c["xk"] = jnp.stack(xks)
        c["xv"] = jnp.stack(xvs)
        new_groups.append(c)
    new_rest = []
    for p_r, c_r in zip(params["rest"], cache["rest"]):
        xk, xv = proj(p_r)
        c = dict(c_r)
        c["xk"], c["xv"] = xk, xv
        new_rest.append(c)
    return {"groups": new_groups, "rest": new_rest}
