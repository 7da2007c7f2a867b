"""Distributed decode: tensor-parallel projections + flash-decoding attention.

Why shard_map and not pjit auto-sharding: decode against a 32k-524k KV
cache is dominated by reading the cache (B x Hkv x S x Dh x 2 per layer).
The only layout that scales it to 256-512 chips shards BOTH the batch
(over "pod","data") and the cache *sequence* (over "model").  The combine
across sequence shards is the flash-decoding split-K pattern — each shard
computes a partial online-softmax (m, l, acc) over its S/16 slice and the
shards merge with one tiny all_gather — which GSPMD cannot discover from a
scanned softmax, so we write the collectives ourselves.

Layout summary (single step, one token per sequence):
  activations x        [B_loc, d]      replicated over "model"
  wq/wk/wv             cols sharded over "model"  (TP)
  q/k after projection all_gather over "model" (tiny: B x H x Dh)
  KV cache             [nb, bl, B_loc, Hkv, S_loc, Dh], S over "model"
  attention            local partial flash -> all_gather(m, l, acc) -> merge
  wo / mlp down        rows sharded -> partial matmul -> psum (TP)
  MoE experts          E sharded over "model", replicated over "data"
                       (decode replicas don't ZeRO-shard weights; see
                       ``decode_param_specs``)
  lm_head              cols sharded -> logits stay vocab-sharded

Cross-pod ("pod" axis): pure DP — no collective in this step touches it,
so all gathers/psums stay on intra-pod ICI.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import layers
from ..models.transformer import LMConfig


def decode_param_specs(cfg: LMConfig):
    """Training specs with ZeRO ("data") sharding stripped: serving replicas
    hold full (model-sharded) weights."""
    from ..models.transformer import lm_specs

    def strip(s: P):
        return P(*[None if e == "data" else e for e in s])

    return jax.tree.map(strip, lm_specs(cfg),
                        is_leaf=lambda x: isinstance(x, P))


def lm_specs_fshard(cfg: LMConfig):
    """Serving layout for llama4-class archs (weights/16 > HBM): expert d_ff
    additionally shards over "data" so per-device weights fit.  (Training
    uses the replicated-expert ZeRO-1 layout in ``moe.moe_specs``.)"""
    from ..models.transformer import lm_specs

    specs = lm_specs(cfg)

    def fshard_moe(block):
        if "moe" in block:
            e = block["moe"]["experts"]
            e["gate"] = P(None, "model", None, "data")
            e["up"] = P(None, "model", None, "data")
            e["down"] = P(None, "model", "data", None)
        return block

    for name, block in specs["blocks"].items():
        specs["blocks"][name] = fshard_moe(block)
    return specs


def cache_spec(ba):
    return P(None, None, ba, None, "model", None)


def _psum_lookup(table_loc, ids, lo, axis):
    """Row lookup from a dim0-sharded table: mask + psum."""
    v_loc = table_loc.shape[0]
    local = ids - lo
    ok = (local >= 0) & (local < v_loc)
    rows = table_loc[jnp.clip(local, 0, v_loc - 1)]
    rows = jnp.where(ok[..., None], rows, 0)
    return jax.lax.psum(rows, axis)


def _flash_decode_attn(q, k_loc, v_loc, pos, s_lo, axis,
                       k_scale=None, v_scale=None):
    """q [B,H,Dh]; k/v_loc [B,Hkv,S_loc,Dh] (this shard's S slice).

    int8 KV mode (k/v_scale [B,Hkv,S_loc] given): scores/values are
    rescaled per cache position instead of dequantizing the cache — the
    dominant decode cost is *reading* the cache, so int8 halves the
    memory-bound term (EXPERIMENTS.md §Perf, LM decode iteration).

    Returns merged attention output [B, H, Dh] (replicated over ``axis``).
    """
    B, H, Dh = q.shape
    Hkv, S_loc = k_loc.shape[1], k_loc.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Dh)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg,
                   k_loc.astype(qg.dtype)) * (Dh ** -0.5)
    if k_scale is not None:
        s = s * k_scale[:, :, None, :]
    kpos = s_lo + jnp.arange(S_loc)
    valid = kpos <= pos
    s = jnp.where(valid[None, None, None, :], s.astype(jnp.float32), -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # [B,Hkv,g]
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,Hkv,g]
    if v_scale is not None:
        pv = (p * v_scale[:, :, None, :]).astype(jnp.float32)
        acc = jnp.einsum("bhgs,bhsd->bhgd", pv,
                         v_loc.astype(jnp.float32))
    else:
        acc = jnp.einsum("bhgs,bhsd->bhgd", p.astype(v_loc.dtype), v_loc
                         ).astype(jnp.float32)

    # flash-decoding merge across sequence shards
    m_all = jax.lax.all_gather(m, axis)                       # [W,B,Hkv,g]
    l_all = jax.lax.all_gather(l, axis)
    acc_all = jax.lax.all_gather(acc, axis)                   # [W,B,Hkv,g,Dh]
    m_star = jnp.max(m_all, axis=0)
    w = jnp.exp(m_all - m_star[None])                         # [W,B,Hkv,g]
    l_star = jnp.sum(l_all * w, axis=0)
    out = jnp.sum(acc_all * w[..., None], axis=0) / jnp.maximum(
        l_star[..., None], 1e-30)
    return out.reshape(B, H, Dh)


def build_decode_step(mesh: Mesh, cfg: LMConfig, batch: int, s_max: int,
                      kv_quant: bool = False):
    """Returns (jit'd step, param_shardings, cache_shardings).

    step(params, token [B], (k_cache, v_cache), pos) ->
        (vocab-sharded logits [B, V], new cache)

    Three layouts by shape/size:
      * standard: batch over ("pod","data"), cache seq over "model",
        TP weights (model-sharded, ZeRO stripped).
      * tiny batch (long_500k, B=1): batch replicated, cache seq over
        EVERY axis (524288/512 = 1024 rows/chip), merge over the mesh.
      * f-sharded (llama4-class, weights/16 > HBM): expert d_ff stays
        sharded over "data" as in training, batch over "pod" only, cache
        seq over ("data","model"); MoE partial products psum over both.
    """
    tp = mesh.shape["model"]
    fshard = cfg.param_count() * 2 / tp > 8e9
    if fshard:
        ba = ("pod",) if ("pod" in mesh.axis_names
                          and batch % mesh.shape["pod"] == 0) else ()
        seq_ax = ("data", "model")
        p_specs = lm_specs_fshard(cfg)
    else:
        ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        n_b = 1
        for a in ba:
            n_b *= mesh.shape[a]
        if batch % n_b != 0:
            ba = ()                                 # replicate batch
            seq_ax = tuple(mesh.axis_names)         # seq over all axes
        else:
            seq_ax = ("model",)
        p_specs = decode_param_specs(cfg)
    n_seq = 1
    for a in seq_ax:
        n_seq *= mesh.shape[a]
    assert s_max % n_seq == 0, (s_max, n_seq)
    c_spec = P(None, None, (ba or None), None, seq_ax, None)
    H, Hkv, Dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model

    def step(params, token, caches, pos):
        widx = jax.lax.axis_index("model")
        seq_idx = jax.lax.axis_index(seq_ax)
        s_loc = s_max // n_seq
        s_lo = seq_idx * s_loc
        v_loc = cfg.vocab // tp
        v_lo = widx * v_loc

        x = _psum_lookup(params["embed"], token, v_lo, "model")  # [B,d] repl.

        def attn_block(p, x, kc, vc, ks, vs):
            """x [B,d]; kc/vc [B,Hkv,S_loc,Dh] local (int8 when kv_quant,
            with ks/vs [B,Hkv,S_loc] scales). Returns (x', caches...)."""
            z = layers.rms_norm(x, p["ln1"]["scale"]).astype(x.dtype)
            # TP projections: local cols, gather heads
            q = jax.lax.all_gather(z @ p["attn"]["wq"], "model",
                                   axis=1, tiled=True).reshape(-1, H, Dh)
            k = jax.lax.all_gather(z @ p["attn"]["wk"], "model",
                                   axis=1, tiled=True).reshape(-1, Hkv, Dh)
            v = jax.lax.all_gather(z @ p["attn"]["wv"], "model",
                                   axis=1, tiled=True).reshape(-1, Hkv, Dh)
            if cfg.qk_norm:
                q = layers.rms_norm(q, p["attn"]["q_norm"]["scale"]).astype(q.dtype)
                k = layers.rms_norm(k, p["attn"]["k_norm"]["scale"]).astype(k.dtype)
            posv = jnp.full((1,), pos)
            # [B, H, Dh] -> [B, H, 1, Dh] so RoPE sees a length-1 sequence
            q = layers.apply_rope(q[:, :, None, :], posv, cfg.rope_base)[:, :, 0]
            k = layers.apply_rope(k[:, :, None, :], posv, cfg.rope_base)[:, :, 0]

            # masked cache write: only the owner of `pos` writes
            rel = pos - s_lo
            own = (rel >= 0) & (rel < s_loc)
            rel_c = jnp.clip(rel, 0, s_loc - 1)
            if kv_quant:
                def quant(a):
                    sc = jnp.maximum(jnp.max(jnp.abs(a), -1) / 127.0, 1e-8)
                    qv = jnp.clip(jnp.round(a / sc[..., None]),
                                  -127, 127).astype(jnp.int8)
                    return qv, sc.astype(jnp.float32)
                k_w, ks_w = quant(k.astype(jnp.float32))
                v_w, vs_w = quant(v.astype(jnp.float32))
                ks_ins = jax.lax.dynamic_update_slice_in_dim(
                    ks, ks_w[:, :, None], rel_c, axis=2)
                ks = jnp.where(own, ks_ins, ks)
                vs_ins = jax.lax.dynamic_update_slice_in_dim(
                    vs, vs_w[:, :, None], rel_c, axis=2)
                vs = jnp.where(own, vs_ins, vs)
            else:
                k_w, v_w = k, v
            k_ins = jax.lax.dynamic_update_slice_in_dim(
                kc, k_w[:, :, None, :], rel_c, axis=2)
            kc = jnp.where(own, k_ins, kc)
            v_ins = jax.lax.dynamic_update_slice_in_dim(
                vc, v_w[:, :, None, :], rel_c, axis=2)
            vc = jnp.where(own, v_ins, vc)

            o = _flash_decode_attn(
                q, kc, vc, pos, s_lo, seq_ax,
                k_scale=ks if kv_quant else None,
                v_scale=vs if kv_quant else None)
            o = o.astype(x.dtype).reshape(x.shape[0], H * Dh)
            # TP out-projection: slice my head rows, partial matmul, psum
            rows = H * Dh // tp
            o_loc = jax.lax.dynamic_slice_in_dim(o, widx * rows, rows, axis=1)
            attn_out = jax.lax.psum(o_loc @ p["attn"]["wo"], "model")
            return x + attn_out, kc, vc, ks, vs

        def mlp_block(p, x):
            z = layers.rms_norm(x, p["ln2"]["scale"]).astype(x.dtype)
            if "moe" in p:
                return x + _moe_decode(p["moe"], z)
            h = jax.nn.silu(z @ p["ffn"]["gate"]) * (z @ p["ffn"]["up"])
            return x + jax.lax.psum(h @ p["ffn"]["down"], "model")

        def _moe_decode(mp, z):
            B = z.shape[0]
            E, k_top = cfg.n_experts, cfg.top_k
            e_loc = E // tp
            e_lo = widx * e_loc
            logits = z.astype(jnp.float32) @ mp["router"]
            gate, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k_top)
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
            we = mp["experts"]

            # decode batch is tiny: evaluate each *local* expert on all
            # tokens, weight by routing indicator, psum across shards.
            def one_expert(e):
                h = jax.nn.silu(z @ we["gate"][e]) * (z @ we["up"][e])
                out_e = h @ we["down"][e]
                w = jnp.sum(
                    jnp.where(idx == (e_lo + e), gate, 0.0), axis=-1
                ).astype(z.dtype)
                return out_e * w[:, None]

            out = jnp.sum(
                jax.vmap(one_expert)(jnp.arange(e_loc)), axis=0
            )
            # fshard: expert d_ff is data-sharded, so the down-projection
            # partials reduce over BOTH axes (EP over model + f over data)
            out = jax.lax.psum(out, ("data", "model") if fshard else "model")
            if cfg.n_shared > 0:
                sh = jax.nn.silu(z @ mp["shared"]["gate"]) * (
                    z @ mp["shared"]["up"])
                out = out + jax.lax.psum(sh @ mp["shared"]["down"], "model")
            return out

        if kv_quant:
            kc_all, vc_all, ks_all, vs_all = caches
        else:
            kc_all, vc_all = caches
            dummy = jnp.zeros(kc_all.shape[:-1], jnp.float32)
            ks_all = vs_all = dummy
        bl = cfg.block_layers

        def block(x, inp):
            bp, kcb, vcb, ksb, vsb = inp
            new_k, new_v, new_ks, new_vs = [], [], [], []
            for i in range(bl):
                lp = bp[f"l{i}"]
                x, kci, vci, ksi, vsi = attn_block(
                    lp, x, kcb[i], vcb[i], ksb[i], vsb[i])
                x = mlp_block(lp, x)
                new_k.append(kci)
                new_v.append(vci)
                new_ks.append(ksi)
                new_vs.append(vsi)
            return x, (jnp.stack(new_k), jnp.stack(new_v),
                       jnp.stack(new_ks), jnp.stack(new_vs))

        x, (kc_all, vc_all, ks_all, vs_all) = jax.lax.scan(
            block, x, (params["blocks"], kc_all, vc_all, ks_all, vs_all)
        )
        x = layers.rms_norm(x, params["final_norm"]["scale"]).astype(x.dtype)
        logits = x @ params["lm_head"]            # [B_loc, V/tp]
        if kv_quant:
            return logits, (kc_all, vc_all, ks_all, vs_all)
        return logits, (kc_all, vc_all)

    tok_spec = P(ba or None)
    out_spec = P(ba or None, "model")
    s_spec = P(None, None, (ba or None), None, seq_ax)   # scale arrays
    cache_specs_t = ((c_spec, c_spec, s_spec, s_spec) if kv_quant
                     else (c_spec, c_spec))
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(p_specs, tok_spec, cache_specs_t, P()),
        out_specs=(out_spec, cache_specs_t),
        check_vma=False,
    )
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                            is_leaf=lambda x: isinstance(x, P))
    cache_sh = tuple(NamedSharding(mesh, s) for s in cache_specs_t)
    step_jit = jax.jit(
        sharded,
        in_shardings=(param_sh, NamedSharding(mesh, tok_spec),
                      cache_sh, NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, out_spec), cache_sh),
        donate_argnums=(2,),
    )
    return step_jit, param_sh, cache_sh
