"""Model assembly: configs -> init / forward / loss / prefill / decode.

Layer plans (configs.base.ArchConfig.layer_plan) are grouped into runs of
identical block kinds; each run's params are stacked on a leading axis and
executed with ``lax.scan`` (+ optional remat) so compile time and HBM stay
bounded at 61-layer scale. Hybrid (zamba2) shared-attention blocks keep a
single param set reused at every occurrence, each occurrence with its own
KV cache.

Sharding is expressed through logical axis hints (distributed.sharding):
activations (batch, seq, -) for train/prefill, KV caches (batch, kvseq, -)
for decode, vocab-parallel embedding/head.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core.objective import ExecutionPolicy
from ..distributed.sharding import current_mesh_context, shard
from .attention import (
    GQACache,
    MLACache,
    cross_attention,
    gqa_attend_step,
    gqa_train,
    init_cross_attention,
    init_gqa,
    init_gqa_cache,
    init_mla,
    init_mla_cache,
    mla_attend_step,
    mla_train,
)
from .layers import (
    init_embedding,
    init_linear,
    init_mlp,
    layernorm,
    linear,
    mlp,
    rmsnorm,
    trunc_normal,
)
from .moe import init_moe, moe_dense, moe_ep_local
from .ot_loss import init_ot_loss, ot_prototype_loss
from .ssm import (
    Mamba2Cache,
    init_mamba2,
    init_mamba2_cache,
    mamba2_decode,
    mamba2_train,
)

__all__ = [
    "init_params",
    "forward",
    "train_loss",
    "prefill",
    "decode_step",
    "init_caches",
    "group_plan",
    "param_count",
]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _norm(p, x, cfg: ArchConfig):
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


def _init_norm(cfg: ArchConfig, d=None):
    d = cfg.d_model if d is None else d
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)}
    return {"w": jnp.ones((d,), cfg.dtype)}


def group_plan(plan: List[str]) -> List[Tuple[str, int]]:
    groups: List[Tuple[str, int]] = []
    for kind in plan:
        if groups and groups[-1][0] == kind and kind != "shared_attn":
            groups[-1] = (kind, groups[-1][1] + 1)
        else:
            groups.append((kind, 1))
    return groups


def effective_window(cfg: ArchConfig, s_max: int) -> Optional[int]:
    if cfg.window is not None:
        return cfg.window
    if cfg.long_context_window is not None and s_max > 65536:
        return cfg.long_context_window
    return None


# ---------------------------------------------------------------------------
# per-block init / train / decode
# ---------------------------------------------------------------------------


def _init_block(key, kind: str, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    if kind in ("attn", "attn_moe", "shared_attn", "enc_attn"):
        p["norm1"] = _init_norm(cfg)
        p["attn"] = init_gqa(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=cfg.dtype,
        )
    elif kind in ("mla", "mla_moe"):
        p["norm1"] = _init_norm(cfg)
        p["attn"] = init_mla(
            ks[0], cfg.d_model, cfg.n_heads, kv_lora=cfg.kv_lora,
            q_lora=cfg.q_lora, qk_nope=cfg.qk_nope, qk_rope=cfg.qk_rope,
            v_head=cfg.v_head, dtype=cfg.dtype,
        )
    elif kind == "mamba":
        p["norm1"] = _init_norm(cfg)
        p["mixer"] = init_mamba2(
            ks[0], cfg.d_model, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
            conv_kernel=cfg.conv_kernel, dtype=cfg.dtype,
        )
        return p
    elif kind == "dec_attn":
        p["norm1"] = _init_norm(cfg)
        p["attn"] = init_gqa(
            ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=cfg.dtype,
        )
        p["norm_x"] = _init_norm(cfg)
        p["xattn"] = init_cross_attention(
            ks[2], cfg.d_model, cfg.n_heads, cfg.head_dim, dtype=cfg.dtype
        )
    else:
        raise ValueError(kind)

    # FFN half
    if kind.endswith("_moe"):
        p["norm2"] = _init_norm(cfg)
        p["moe"] = init_moe(
            ks[1], cfg.d_model, cfg.moe_d_ff, cfg.n_experts, dtype=cfg.dtype
        )
        if cfg.n_shared_experts:
            p["shared_mlp"] = init_mlp(
                ks[3], cfg.d_model, cfg.n_shared_experts * cfg.moe_d_ff,
                gated=cfg.mlp_gated, dtype=cfg.dtype,
            )
    elif cfg.d_ff:
        p["norm2"] = _init_norm(cfg)
        p["mlp"] = init_mlp(
            ks[1], cfg.d_model, cfg.d_ff, gated=cfg.mlp_gated, dtype=cfg.dtype
        )
    return p


def _ot_policy(cfg: ArchConfig) -> ExecutionPolicy:
    """The run-wide OT execution policy, derived from config. A pure
    (static, hashable) function of cfg — equal to the record the launch
    layer constructs once per run and logs."""
    return ExecutionPolicy.from_config(cfg)


def _moe_apply(p, x2: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """x2 (B, S, d) normed input -> (out, aux). EP under a mesh, dense otherwise."""
    B, S, d = x2.shape
    policy = _ot_policy(cfg)
    ctx = current_mesh_context()
    if ctx is None or ctx.tp_axis is None:
        out, aux = moe_dense(
            p["moe"], x2.reshape(-1, d), top_k=cfg.top_k, router=cfg.router,
            policy=policy,
        )
        return out.reshape(B, S, d), aux

    from jax.sharding import PartitionSpec as P

    dp = ctx.dp_axes if ctx.dp_axes else None
    tp = ctx.tp_axis
    fsdp_axes = ctx.dp_axes if (cfg.zero3 and ctx.dp_axes) else None
    fsdp = (fsdp_axes if fsdp_axes and len(fsdp_axes) > 1
            else (fsdp_axes[0] if fsdp_axes else None))

    def body(p_loc, x_loc):
        Bl, Sl, _ = x_loc.shape
        out, aux = moe_ep_local(
            p_loc, x_loc.reshape(-1, d), top_k=cfg.top_k,
            n_experts=cfg.n_experts, axis=tp, router=cfg.router,
            capacity_factor=cfg.capacity_factor,
            fsdp_axis=fsdp, policy=policy,
        )
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out.reshape(Bl, Sl, d), aux

    wspec_d1 = P(tp, fsdp, None) if fsdp else P(tp, None, None)
    wspec_d2 = P(tp, None, fsdp) if fsdp else P(tp, None, None)
    in_specs = (
        {
            "router": P(None, None),
            "up": wspec_d1,
            "gate": wspec_d1,
            "down": wspec_d2,
        },
        P(dp, tp, None),
    )
    out_specs = (P(dp, tp, None), P())
    fn = jax.shard_map(
        body, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(p["moe"], x2)


def _block_train(kind: str, p, x: jax.Array, cfg: ArchConfig,
                 enc: Optional[jax.Array] = None,
                 window: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Returns (x_out, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h = mamba2_train(
            p["mixer"], _norm(p["norm1"], x, cfg), d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
            chunk=cfg.ssm_chunk,
        )
        return x + h, aux
    if kind in ("mla", "mla_moe"):
        h = mla_train(
            p["attn"], _norm(p["norm1"], x, cfg), n_heads=cfg.n_heads,
            kv_lora=cfg.kv_lora, qk_nope=cfg.qk_nope, qk_rope=cfg.qk_rope,
            v_head=cfg.v_head, rope_theta=cfg.rope_theta,
        )
    elif kind == "enc_attn":
        # bidirectional: full window, no causal mask -> use cross-attn math
        h = cross_attention(
            p["attn"], _norm(p["norm1"], x, cfg), _norm(p["norm1"], x, cfg),
            n_heads=cfg.n_heads, head_dim=cfg.head_dim,
        )
    else:
        h = gqa_train(
            p["attn"], _norm(p["norm1"], x, cfg), n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, window=window if window else cfg.window,
        )
    x = x + h
    x = shard(x, "batch", "seq", None)
    if kind == "dec_attn":
        x = x + cross_attention(
            p["xattn"], _norm(p["norm_x"], x, cfg), enc,
            n_heads=cfg.n_heads, head_dim=cfg.head_dim,
        )
    if kind.endswith("_moe"):
        x2 = _norm(p["norm2"], x, cfg)
        out, aux = _moe_apply(p, x2, cfg)
        if "shared_mlp" in p:
            out = out + mlp(p["shared_mlp"], x2, gated=cfg.mlp_gated)
        x = x + out
    elif "mlp" in p:
        x = x + mlp(p["mlp"], _norm(p["norm2"], x, cfg), gated=cfg.mlp_gated)
    return shard(x, "batch", "seq", None), aux


def _block_decode(kind: str, p, x, cache, cfg: ArchConfig,
                  enc_kv=None, window: Optional[int] = None
                  ) -> Tuple[jax.Array, Any]:
    """Decode one token through one block, append-then-write style: the
    attention cache is READ-ONLY; this returns (x, update) where update is
    the small per-layer payload the caller scatters into the stacked cache
    once per step ((k,v) slot, (c_kv, rope) slot, or the full SSM state)."""
    if kind == "mamba":
        h, new_cache = mamba2_decode(
            p["mixer"], _norm(p["norm1"], x, cfg), cache,
            d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
            expand=cfg.ssm_expand,
        )
        return x + h, new_cache
    if kind in ("mla", "mla_moe"):
        h, c_new, r_new = mla_attend_step(
            p["attn"], _norm(p["norm1"], x, cfg), cache.c_kv, cache.k_rope,
            cache.length, n_heads=cfg.n_heads,
            kv_lora=cfg.kv_lora, qk_nope=cfg.qk_nope, qk_rope=cfg.qk_rope,
            v_head=cfg.v_head, rope_theta=cfg.rope_theta,
        )
        update = (c_new, r_new)
    else:
        h, k_new, v_new = gqa_attend_step(
            p["attn"], _norm(p["norm1"], x, cfg), cache.k, cache.v,
            cache.length, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
            window=window,
        )
        update = (k_new, v_new)
    x = x + h
    if kind == "dec_attn":
        # cross-attention over cached encoder K/V
        k, v = enc_kv
        B = x.shape[0]
        xq = _norm(p["norm_x"], x, cfg)
        q = linear(p["xattn"]["wq"], xq).reshape(
            B, 1, cfg.n_heads, cfg.head_dim
        ) * (cfg.head_dim ** -0.5)
        s = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bshd->bqhd", pr, v.astype(jnp.float32))
        o = o.reshape(B, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
        x = x + linear(p["xattn"]["wo"], o)
    if kind.endswith("_moe"):
        x2 = _norm(p["norm2"], x, cfg)
        out, _ = _moe_apply_decode(p, x2, cfg)
        if "shared_mlp" in p:
            out = out + mlp(p["shared_mlp"], x2, gated=cfg.mlp_gated)
        x = x + out
    elif "mlp" in p:
        x = x + mlp(p["mlp"], _norm(p["norm2"], x, cfg), gated=cfg.mlp_gated)
    return x, update


def _moe_apply_decode(p, x2, cfg):
    """Decode-time MoE: tiny token count (B tokens) — dense combine over
    experts is affordable and avoids all_to_all latency on the decode path
    (batch x E x d_ff flops with B <= 128)."""
    B, S, d = x2.shape
    out, aux = moe_dense(
        p["moe"], x2.reshape(-1, d), top_k=cfg.top_k, router=cfg.router,
        policy=_ot_policy(cfg),
    )
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig) -> Dict:
    ks = iter(jax.random.split(key, 64))
    params: Dict[str, Any] = {}
    if cfg.input_kind in ("tokens", "encdec"):
        params["embed"] = init_embedding(next(ks), cfg.padded_vocab,
                                         cfg.d_model, dtype=cfg.dtype)
    if cfg.pos == "learned":
        params["pos"] = trunc_normal(next(ks), (65536, cfg.d_model),
                                     std=0.01, dtype=cfg.dtype)

    groups = group_plan(cfg.layer_plan())
    stacks = []
    shared_attn_done = False
    for kind, count in groups:
        if kind == "shared_attn":
            if not shared_attn_done:
                params["shared_attn"] = _init_block(next(ks), "attn", cfg)
                shared_attn_done = True
            stacks.append(None)
            continue
        keys = jax.random.split(next(ks), count)
        stacks.append(jax.vmap(lambda k: _init_block(k, kind, cfg))(keys))
    params["groups"] = stacks

    if cfg.family == "encdec":
        enc_keys = jax.random.split(next(ks), cfg.n_enc_layers)
        params["encoder"] = jax.vmap(
            lambda k: _init_block(k, "enc_attn", cfg)
        )(enc_keys)
        params["enc_norm"] = _init_norm(cfg)

    params["final_norm"] = _init_norm(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(next(ks), cfg.d_model,
                                        cfg.padded_vocab, dtype=cfg.dtype)
    if cfg.mtp:
        params["mtp_block"] = _init_block(next(ks), "mla" if
                                          cfg.attention == "mla" else "attn",
                                          cfg)
        params["mtp_norm"] = _init_norm(cfg)
    if cfg.ot_loss_weight > 0:
        params["ot"] = init_ot_loss(
            next(ks), cfg.d_model, ot_dim=cfg.ot_dim, n_protos=cfg.ot_protos,
            n_features=cfg.ot_features, eps=cfg.ot_eps,
        )
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ArchConfig, batch: Dict) -> jax.Array:
    if cfg.input_kind == "embeds":
        x = batch["embeds"].astype(cfg.cdtype)
    else:
        tok = batch["tokens"]
        x = params["embed"]["table"].astype(cfg.cdtype)[tok]
    if cfg.pos == "learned":
        S = x.shape[1]
        x = x + params["pos"][:S][None].astype(cfg.cdtype)
    return shard(x, "batch", "seq", None)


def _run_decoder_groups(params, cfg: ArchConfig, x: jax.Array,
                        enc: Optional[jax.Array] = None):
    """Scan each stacked group; python-apply shared blocks."""
    aux_total = jnp.zeros((), jnp.float32)
    plan_groups = group_plan(cfg.layer_plan())
    for (kind, count), stack in zip(plan_groups, params["groups"]):
        if kind == "shared_attn":
            x, aux = _block_train("attn", params["shared_attn"], x, cfg)
            aux_total += aux
            continue

        def body(carry, p_l, _kind=kind):
            y, aux = _block_train(_kind, p_l, carry, cfg, enc=enc)
            return y, aux

        body_fn = body
        if cfg.remat:
            body_fn = jax.checkpoint(
                body_fn, policy=jax.checkpoint_policies.nothing_saveable
            )
        x, auxs = jax.lax.scan(body_fn, x, stack)
        aux_total += jnp.sum(auxs)
    return x, aux_total


def forward(params, cfg: ArchConfig, batch: Dict) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,S,d) after final norm, aux losses)."""
    enc = None
    if cfg.family == "encdec":
        enc = _encode(params, cfg, batch)
    x = _embed_inputs(params, cfg, batch)
    x, aux = _run_decoder_groups(params, cfg, x, enc=enc)
    return _norm(params["final_norm"], x, cfg), aux


def _encode(params, cfg: ArchConfig, batch: Dict) -> jax.Array:
    x = batch["enc_embeds"].astype(cfg.cdtype)
    if cfg.pos == "learned":
        x = x + params["pos"][: x.shape[1]][None].astype(cfg.cdtype)
    x = shard(x, "batch", "seq", None)

    def body(carry, p_l):
        y, _ = _block_train("enc_attn", p_l, carry, cfg)
        return y, None

    body_fn = body
    if cfg.remat:
        body_fn = jax.checkpoint(
            body_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    x, _ = jax.lax.scan(body_fn, x, params["encoder"])
    return _norm(params["enc_norm"], x, cfg)


def _logits(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(h.dtype)
        logits = h @ w.T
    else:
        logits = linear(params["lm_head"], h)
    # 'model' can shard either the seq or the vocab dim of the logits, not
    # both: keep the upstream seq sharding when S > 1 (train/prefill),
    # vocab-parallel when decoding a single position.
    ctx = current_mesh_context()
    if ctx is not None and ctx.mode == "decode":
        return shard(logits, "batch", None, "vocab")
    return shard(logits, "batch", "seq", None)


def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def _head_weight(params, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


_XENT_CHUNKS = 16


def _xent_chunked(h: jax.Array, w: jax.Array, labels: jax.Array,
                  n_chunks: int = _XENT_CHUNKS) -> jax.Array:
    """Streaming cross-entropy over vocab chunks (never materializes the
    (B, S, V) logits — §Perf train-memory hillclimb). The chunk body is
    rematerialized in the backward pass, so peak memory is O(V / n_chunks)."""
    B, S, d = h.shape
    V = w.shape[1]
    chunk = -(-V // n_chunks)
    pad = n_chunks * chunk - V
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))

    def body(carry, i):
        m, s, gold = carry
        wc = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk, 1)
        logits = (h @ wc.astype(h.dtype)).astype(jnp.float32)   # (B,S,chunk)
        # padded vocab tail must not contribute
        col = i * chunk + jnp.arange(chunk)
        logits = jnp.where((col < V)[None, None, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1)
        local = jnp.clip(labels - i * chunk, 0, chunk - 1)
        gold_c = jnp.take_along_axis(logits, local[..., None], -1)[..., 0]
        in_chunk = (labels >= i * chunk) & (labels < (i + 1) * chunk)
        gold = jnp.where(in_chunk, gold_c, gold)
        return (m_new, s, gold), None

    body = jax.checkpoint(body)
    m0 = jnp.full((B, S), -1e30, jnp.float32)
    s0 = jnp.zeros((B, S), jnp.float32)
    g0 = jnp.full((B, S), -1e30, jnp.float32)
    (m, s, gold), _ = jax.lax.scan(body, (m0, s0, g0),
                                   jnp.arange(n_chunks))
    lse = m + jnp.log(jnp.maximum(s, 1e-30))
    return jnp.mean(lse - gold)


def _lm_ce(params, cfg: ArchConfig, h: jax.Array, labels: jax.Array
           ) -> jax.Array:
    if cfg.padded_vocab >= 32768:
        return _xent_chunked(h, _head_weight(params, cfg), labels)
    return _xent(_logits(params, cfg, h), labels)


def train_loss(params, cfg: ArchConfig, batch: Dict,
               policy: Optional[ExecutionPolicy] = None
               ) -> Tuple[jax.Array, Dict]:
    """Full training objective. ``policy`` is the run-wide OT execution
    policy (constructed once by the launch layer); ``None`` derives the
    identical record from cfg."""
    if policy is None:
        policy = _ot_policy(cfg)
    h, aux = forward(params, cfg, batch)
    loss_ce = _lm_ce(params, cfg, h, batch["labels"])
    metrics = {"ce": loss_ce, "aux": aux}
    loss = loss_ce + 0.01 * aux
    if cfg.mtp:
        # multi-token prediction: one extra block on h predicts t+2
        hm, _ = _block_train(
            "mla" if cfg.attention == "mla" else "attn",
            params["mtp_block"], h, cfg,
        )
        hm = _norm(params["mtp_norm"], hm, cfg)
        loss_mtp = _lm_ce(params, cfg, hm[:, :-1], batch["labels"][:, 1:])
        metrics["mtp"] = loss_mtp
        loss = loss + 0.3 * loss_mtp
    if cfg.ot_loss_weight > 0:
        loss_ot = ot_prototype_loss(
            params["ot"], h, eps=cfg.ot_eps, n_tokens=cfg.ot_tokens,
            n_iter=cfg.ot_iters, policy=policy,
        )
        metrics["ot"] = loss_ot
        loss = loss + cfg.ot_loss_weight * loss_ot
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: caches / prefill / decode
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, s_max: int) -> List[Any]:
    """Per-group stacked caches (leading axis = layers in group)."""
    win = effective_window(cfg, s_max)
    caches: List[Any] = []
    plan_groups = group_plan(cfg.layer_plan())

    def stack(c, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), c)

    for kind, count in plan_groups:
        if kind in ("attn", "attn_moe", "dec_attn", "shared_attn"):
            c = init_gqa_cache(batch, s_max, cfg.n_kv_heads, cfg.head_dim,
                               window=win, dtype=cfg.cdtype)
        elif kind in ("mla", "mla_moe"):
            c = init_mla_cache(batch, s_max, kv_lora=cfg.kv_lora,
                               qk_rope=cfg.qk_rope, dtype=cfg.cdtype)
        elif kind == "mamba":
            c = init_mamba2_cache(
                batch, cfg.d_model, d_state=cfg.ssm_state,
                head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                conv_kernel=cfg.conv_kernel, dtype=cfg.cdtype,
            )
        else:
            raise ValueError(kind)
        caches.append(c if kind == "shared_attn" else stack(c, count))
    return caches


def cache_logical_axes(cfg: ArchConfig) -> List[Any]:
    """Logical axis names per cache leaf (mirrors init_caches structure).

    GQA/MLA caches shard the KV sequence over 'model' (flash-decoding
    contract); Mamba states shard SSD heads over 'model'.
    """
    plan_groups = group_plan(cfg.layer_plan())
    specs: List[Any] = []
    for kind, count in plan_groups:
        lead = () if kind == "shared_attn" else (None,)
        if kind in ("attn", "attn_moe", "dec_attn", "shared_attn"):
            c = GQACache(
                k=lead + ("batch", "kvseq", None, None),
                v=lead + ("batch", "kvseq", None, None),
                length="skip",
            )
        elif kind in ("mla", "mla_moe"):
            c = MLACache(
                c_kv=lead + ("batch", "kvseq", None),
                k_rope=lead + ("batch", "kvseq", None),
                length="skip",
            )
        elif kind == "mamba":
            c = Mamba2Cache(
                conv=lead + ("batch", None, None),
                state=lead + ("batch", "heads", None, None),
                length="skip",
            )
        else:
            raise ValueError(kind)
        specs.append(c)
    return specs


def shard_caches(cfg: ArchConfig, caches):
    """Apply the decode sharding contract to a cache pytree."""
    specs = cache_logical_axes(cfg)
    leaves, treedef = jax.tree.flatten(caches)
    spec_leaves = jax.tree.flatten(
        specs,
        is_leaf=lambda x: isinstance(x, str)
        or (isinstance(x, tuple) and not isinstance(
            x, (GQACache, MLACache, Mamba2Cache))),
    )[0]
    assert len(leaves) == len(spec_leaves), (len(leaves), len(spec_leaves))
    out = [
        leaf if ax == "skip" else shard(leaf, *ax)
        for leaf, ax in zip(leaves, spec_leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def decode_step(params, cfg: ArchConfig, token_batch: Dict,
                caches: List[Any], *, window: Optional[int] = None
                ) -> Tuple[jax.Array, List[Any]]:
    """One-token decode. token_batch: tokens (B,1) (+ enc_kv for encdec).

    ``window`` must be effective_window(cfg, s_max) of the serving session
    (rolling-buffer caches for SWA / hybrid long-context).
    """
    if cfg.input_kind == "embeds":
        x = token_batch["embeds"].astype(cfg.cdtype)
    else:
        x = params["embed"]["table"].astype(cfg.cdtype)[token_batch["tokens"]]
    if cfg.pos == "learned":
        # position = cache length of the first group
        pos = jax.tree.leaves(caches[0])[-1]
        pos = pos.reshape(-1)[0].astype(jnp.int32)
        x = x + jax.lax.dynamic_index_in_dim(
            params["pos"], pos, 0, keepdims=True
        )[None, 0].astype(cfg.cdtype)
    x = shard(x, "batch", None, None)

    enc_kv = token_batch.get("enc_kv")
    plan_groups = group_plan(cfg.layer_plan())
    new_caches = []

    def write_gqa(cache: GQACache, k_new, v_new, *, stacked: bool):
        """One scatter for the whole group — the only cache write."""
        seq_ax = 2 if stacked else 1
        s_cache = cache.k.shape[seq_ax]
        pos = cache.length.reshape(-1)[0]
        slot = jnp.mod(pos, s_cache) if window else jnp.minimum(
            pos, s_cache - 1)
        k_new = jnp.expand_dims(k_new, seq_ax)
        v_new = jnp.expand_dims(v_new, seq_ax)
        return GQACache(
            jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, slot, seq_ax),
            jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, slot, seq_ax),
            cache.length + 1,
        )

    def write_mla(cache: MLACache, c_new, r_new):
        pos = cache.length.reshape(-1)[0]
        return MLACache(
            jax.lax.dynamic_update_slice_in_dim(
                cache.c_kv, jnp.expand_dims(c_new, 2), pos, 2),
            jax.lax.dynamic_update_slice_in_dim(
                cache.k_rope, jnp.expand_dims(r_new, 2), pos, 2),
            cache.length + 1,
        )

    for (kind, count), cache, stack in zip(plan_groups, caches,
                                           params["groups"]):
        if kind == "shared_attn":
            x, (k_new, v_new) = _block_decode(
                "attn", params["shared_attn"], x, cache, cfg, window=window)
            new_caches.append(write_gqa(cache, k_new, v_new, stacked=False))
            continue

        if kind == "dec_attn":
            xs = (stack, cache, enc_kv["k"], enc_kv["v"])

            def body(carry, pc, _kind=kind):
                p_l, c_l, ek, ev = pc
                y, upd = _block_decode(_kind, p_l, carry, c_l, cfg,
                                       enc_kv=(ek, ev), window=window)
                return y, upd
        else:
            xs = (stack, cache)

            def body(carry, pc, _kind=kind):
                p_l, c_l = pc
                y, upd = _block_decode(_kind, p_l, carry, c_l, cfg,
                                       window=window)
                return y, upd

        x, upd = jax.lax.scan(body, x, xs)
        if kind == "mamba":
            new_caches.append(upd)          # full (small) SSM state stack
        elif kind in ("mla", "mla_moe"):
            new_caches.append(write_mla(cache, *upd))
        else:
            new_caches.append(write_gqa(cache, *upd, stacked=True))
    h = _norm(params["final_norm"], x, cfg)
    return _logits(params, cfg, h), new_caches


def prefill(params, cfg: ArchConfig, batch: Dict):
    """Prefill step for serving: full forward, returns last-position logits.

    Cache construction during prefill shares the forward compute (the
    dry-run prefii shape measures exactly this program). For simplicity and
    because the 32k cells only need the compiled artifact, the returned
    caches are rebuilt from a second pass of the cheap projections inside
    each block would duplicate code — instead we run the standard forward
    and return logits for the final position (the production system would
    fuse cache emission into the same scan; see launch/serve.py).
    """
    h, _ = forward(params, cfg, batch)
    return _logits(params, cfg, h[:, -1:, :])
