"""Cell -> (step_fn, abstract args, shardings) builders for every family.

``build_cell(arch_id, shape, mesh)`` returns a ``CellBundle`` the dry-run
lowers and the train/serve drivers execute.  Everything is built
abstractly (``jax.eval_shape``) — no parameter allocation happens here, so
the 400B-parameter cells cost nothing until real training runs on real
hardware.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import configs
from ..core.types import BanditHyper
from ..distributed import decode_shard, distclub_shard, sharding
from ..models import gnn, transformer
from ..models.recsys import dcn_v2, mind, seqrec
from ..train import optimizer
from . import mesh as mesh_lib

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class CellBundle:
    arch_id: str
    shape: str
    kind: str
    step_fn: Callable            # positional args
    abstract_args: tuple         # ShapeDtypeStructs / pytrees thereof
    in_shardings: tuple
    out_shardings: Any           # None -> let GSPMD choose
    donate_argnums: tuple = ()
    note: str = ""
    prejit: bool = False         # step_fn is already jit'd with shardings


def _shard_tree(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _abstract(fn, *args):
    return jax.eval_shape(fn, *args)


# --- LM family -------------------------------------------------------------------


def build_lm_cell(spec, shape: str, mesh: Mesh,
                  kv_quant: bool = False) -> CellBundle:
    cfg = spec.cell_cfg(shape)
    cell = spec.shapes[shape]
    inputs = spec.input_specs(shape)
    ba = mesh_lib.batch_axes(mesh)
    p_specs = transformer.lm_specs(cfg)
    params_abs = _abstract(partial(transformer.init_lm, cfg=cfg),
                           SDS((2,), jnp.uint32))

    if cell.kind == "train":
        # ZeRO: moments + grad accumulator fully sharded (params stay in
        # their TP/EP layout; "data" is added on a replicated dim).
        data_size = mesh.shape["data"]
        z_specs = sharding.zero_specs(p_specs, params_abs, data_size)
        use_adafactor = cfg.param_count() > 100e9

        if use_adafactor:
            opt_init = partial(optimizer.adafactor_init,
                               momentum_dtype=jnp.bfloat16)
            opt_update = optimizer.adafactor_update
        else:
            opt_init = partial(optimizer.adamw_init,
                               moment_dtype=jnp.float32)
            opt_update = partial(optimizer.adamw_update, lr=3e-4)
        opt_abs = _abstract(opt_init, params_abs)
        mb = cfg.microbatches
        B = inputs["tokens"].shape[0]
        assert B % mb == 0

        def step(params, opt, tokens, labels):
            # keep the *batch* dim data-sharded after the microbatch split
            # (otherwise GSPMD shards the microbatch axis and every
            # microbatch runs fully replicated)
            mb_sh = NamedSharding(mesh, P(None, ba, None))
            tb = jax.lax.with_sharding_constraint(
                tokens.reshape(mb, B // mb, -1), mb_sh)
            lb = jax.lax.with_sharding_constraint(
                labels.reshape(mb, B // mb, -1), mb_sh)

            def mb_body(g_acc, xs):
                t, l = xs
                loss, grads = jax.value_and_grad(transformer.lm_loss)(
                    params, cfg, t, l)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), g_acc, grads)
                g_acc = jax.lax.with_sharding_constraint(
                    g_acc, _shard_tree(mesh, z_specs))
                return g_acc, loss

            acc_dt = jnp.bfloat16 if use_adafactor else jnp.float32
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
            g0 = jax.lax.with_sharding_constraint(
                g0, _shard_tree(mesh, z_specs))
            g_acc, losses = jax.lax.scan(mb_body, g0, (tb, lb))
            g_avg = jax.tree.map(lambda g: g / mb, g_acc)
            params, opt = opt_update(g_avg, opt, params)
            return params, opt, jnp.mean(losses)

        p_sh = _shard_tree(mesh, p_specs)
        z_sh = _shard_tree(mesh, z_specs)

        # per-leaf moment shardings follow the ZeRO param layout where the
        # moment has the same rank, else replicate (adafactor factors)
        def opt_shardings(opt_tree):
            flat_p, _ = jax.tree.flatten(params_abs)
            flat_zs, _ = jax.tree.flatten(z_sh)
            by_shape = {}
            for p, s in zip(flat_p, flat_zs):
                by_shape.setdefault(p.shape, s)

            def pick(leaf):
                return by_shape.get(leaf.shape, NamedSharding(mesh, P()))

            return jax.tree.map(pick, opt_tree)

        opt_sh = opt_shardings(opt_abs)
        tok_sh = NamedSharding(mesh, P(ba, None))
        return CellBundle(
            spec.arch_id, shape, "train", step,
            (params_abs, opt_abs, inputs["tokens"], inputs["labels"]),
            (p_sh, opt_sh, tok_sh, tok_sh),
            None, donate_argnums=(0, 1), note=cell.note,
        )

    if cell.kind == "serve":            # prefill
        def step(params, tokens):
            return transformer.lm_prefill(params, cfg, tokens)

        # llama4-class: weights/16 exceed HBM -> keep the training (data-
        # sharded) layout for prefill; gathers amortize over 32k tokens.
        fshard = cfg.param_count() * 2 / mesh.shape["model"] > 8e9
        p_sh = _shard_tree(
            mesh, decode_shard.lm_specs_fshard(cfg) if fshard
            else decode_shard.decode_param_specs(cfg))
        tok_sh = NamedSharding(mesh, P(ba, None))
        cache_sh = NamedSharding(mesh, decode_shard.cache_spec(ba))
        out_sh = (NamedSharding(mesh, P(ba, "model")), (cache_sh, cache_sh))
        return CellBundle(
            spec.arch_id, shape, "serve", step,
            (params_abs, inputs["tokens"]), (p_sh, tok_sh), out_sh,
            note=cell.note,
        )

    # decode: shard_map flash-decoding + TP (already jit'd with shardings)
    batch = inputs["token"].shape[0]
    s_max = inputs["k_cache"].shape[4]
    step_jit, p_sh, cache_sh = decode_shard.build_decode_step(
        mesh, cfg, batch, s_max, kv_quant=kv_quant)
    if kv_quant:
        kq = jax.ShapeDtypeStruct(inputs["k_cache"].shape, jnp.int8)
        sc = jax.ShapeDtypeStruct(inputs["k_cache"].shape[:-1], jnp.float32)
        caches = (kq, kq, sc, sc)
    else:
        caches = (inputs["k_cache"], inputs["v_cache"])
    return CellBundle(
        spec.arch_id, shape, "decode", step_jit,
        (params_abs, inputs["token"], caches, inputs["pos"]),
        (), None, note=cell.note, prejit=True,
    )


# --- GNN family ------------------------------------------------------------------


def build_gnn_cell(spec, shape: str, mesh: Mesh) -> CellBundle:
    """GNN train step: explicit shard_map (GSPMD replicates scatters).

    Layout contract: nodes row-sharded over every mesh axis; edges
    partitioned by destination block (dst in the local node shard) — the
    production graph-partitioning layout, making segment reductions local.
    """

    cfg = spec.cell_cfg(shape)
    cell = spec.shapes[shape]
    inputs = spec.input_specs(shape)
    axes = tuple(mesh.axis_names)
    params_abs = _abstract(partial(gnn.init_gat, cfg=cfg), SDS((2,), jnp.uint32))
    opt_abs = _abstract(optimizer.adamw_init, params_abs)

    def local_step(params, opt, feats, src, dst, labels, mask):
        def loss_fn(p):
            return gnn.gat_loss_local(p, cfg, feats, src, dst, labels, mask,
                                      axes)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, axes)
        params, opt = optimizer.adamw_update(grads, opt, params, lr=5e-3)
        return params, opt, loss

    n_spec = P(axes)
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(axes, None), n_spec, n_spec, n_spec, n_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    p_sh = _shard_tree(mesh, jax.tree.map(lambda _: P(), params_abs))
    opt_sh = optimizer.AdamWState(
        step=NamedSharding(mesh, P()), m=p_sh, v=p_sh)
    node_sh = NamedSharding(mesh, P(axes))
    node2_sh = NamedSharding(mesh, P(axes, None))
    return CellBundle(
        spec.arch_id, shape, "train", sharded,
        (params_abs, opt_abs, inputs["feats"], inputs["src"], inputs["dst"],
         inputs["labels"], inputs["mask"]),
        (p_sh, opt_sh, node2_sh, node_sh, node_sh, node_sh, node_sh),
        None, donate_argnums=(0, 1), note=cell.note,
    )


# --- recsys family -----------------------------------------------------------


def build_recsys_cell(spec, shape: str, mesh: Mesh) -> CellBundle:
    cfg = spec.cell_cfg(shape)
    cell = spec.shapes[shape]
    inputs = spec.input_specs(shape)
    ba = mesh_lib.batch_axes(mesh)
    arch = spec.arch_id

    if arch in ("sasrec", "bert4rec"):
        init, p_specs = seqrec.init_seqrec, seqrec.seqrec_specs(cfg)
        loss_fn = seqrec.sampled_softmax_loss
        serve_fn, retr_fn = seqrec.score_candidates, seqrec.retrieval_scores
    elif arch == "mind":
        init, p_specs = mind.init_mind, mind.mind_specs(cfg)
        loss_fn = mind.mind_loss
        serve_fn, retr_fn = mind.mind_serve, mind.mind_retrieval
    else:                               # dcn-v2
        init, p_specs = dcn_v2.init_dcn, dcn_v2.dcn_specs(cfg)
        loss_fn = dcn_v2.dcn_loss
        serve_fn = retr_fn = None

    params_abs = _abstract(partial(init, cfg=cfg), SDS((2,), jnp.uint32))
    p_sh = _shard_tree(mesh, p_specs)

    if cell.kind == "train":
        opt_abs = _abstract(optimizer.adagrad_init, params_abs)
        opt_sh = optimizer.AdagradState(accum=p_sh)

        if arch == "dcn-v2":
            def step(params, opt, dense_feats, sparse_ids, labels):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, cfg, dense_feats, sparse_ids, labels)
                params, opt = optimizer.adagrad_update(grads, opt, params)
                return params, opt, loss
            args = (params_abs, opt_abs, inputs["dense_feats"],
                    inputs["sparse_ids"], inputs["labels"])
            shardings = (p_sh, opt_sh,
                         NamedSharding(mesh, P(ba, None)),
                         NamedSharding(mesh, P(ba, None)),
                         NamedSharding(mesh, P(ba)))
        else:
            # §Perf: 65536-row batches through a 200-token tower peak at
            # multi-GiB attention transients; microbatch with f32 grad
            # accumulation (identical math; one optimizer step).
            B = inputs["hist"].shape[0]
            mb = 8 if B >= 65_536 else 1

            def step(params, opt, hist, targets, key):
                if mb == 1:
                    loss, grads = jax.value_and_grad(loss_fn)(
                        params, cfg, hist, targets, key)
                else:
                    hb = jax.lax.with_sharding_constraint(
                        hist.reshape(mb, B // mb, -1),
                        NamedSharding(mesh, P(None, ba, None)))
                    tb = targets.reshape((mb, B // mb) + targets.shape[1:])

                    def mb_body(acc, xs):
                        h, t = xs
                        l, g = jax.value_and_grad(loss_fn)(
                            params, cfg, h, t, key)
                        return jax.tree.map(
                            lambda a, gg: a + gg.astype(a.dtype), acc, g), l

                    g0 = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    grads, losses = jax.lax.scan(mb_body, g0, (hb, tb))
                    grads = jax.tree.map(lambda g: g / mb, grads)
                    loss = jnp.mean(losses)
                params, opt = optimizer.adagrad_update(grads, opt, params)
                return params, opt, loss

            t_sh = (NamedSharding(mesh, P(ba, None))
                    if inputs["targets"].ndim == 2
                    else NamedSharding(mesh, P(ba)))
            args = (params_abs, opt_abs, inputs["hist"], inputs["targets"],
                    inputs["key"])
            shardings = (p_sh, opt_sh, NamedSharding(mesh, P(ba, None)), t_sh,
                         NamedSharding(mesh, P()))
        return CellBundle(spec.arch_id, shape, "train", step, args, shardings,
                          None, donate_argnums=(0, 1), note=cell.note)

    # serve cells
    if arch == "dcn-v2":
        def step(params, dense_feats, sparse_ids):
            return dcn_v2.dcn_fwd(params, cfg, dense_feats, sparse_ids)
        args = (params_abs, inputs["dense_feats"], inputs["sparse_ids"])
        shardings = (p_sh, NamedSharding(mesh, P(ba, None)),
                     NamedSharding(mesh, P(ba, None)))
    elif shape == "retrieval_cand":
        def step(params, hist, cand):
            return retr_fn(params, cfg, hist, cand)
        args = (params_abs, inputs["hist"], inputs["cand"])
        # one query replicated; the 10^6-candidate slab shards over
        # every axis (batched dot, per the assignment)
        shardings = (p_sh, NamedSharding(mesh, P(None, None)),
                     NamedSharding(mesh, P(tuple(mesh.axis_names))))
    else:
        # §Perf (serve_bulk): scoring 262144 users x 1000 candidates in one
        # shot peaks at [B, C, d] gathered-candidate tensors; chunking the
        # batch through lax.map bounds the transient at one chunk (the
        # request stream is embarrassingly parallel).
        B = inputs["hist"].shape[0]
        chunk = 16_384
        if B > chunk:
            n_chunks = B // chunk

            def step(params, hist, cand):
                hb = hist.reshape(n_chunks, chunk, -1)
                cb = cand.reshape(n_chunks, chunk, -1)
                hb = jax.lax.with_sharding_constraint(
                    hb, NamedSharding(mesh, P(None, ba, None)))
                cb = jax.lax.with_sharding_constraint(
                    cb, NamedSharding(mesh, P(None, ba, None)))
                out = jax.lax.map(
                    lambda xs: serve_fn(params, cfg, xs[0], xs[1]), (hb, cb))
                return out.reshape(B, -1)
        else:
            def step(params, hist, cand):
                return serve_fn(params, cfg, hist, cand)
        args = (params_abs, inputs["hist"], inputs["cand"])
        shardings = (p_sh, NamedSharding(mesh, P(ba, None)),
                     NamedSharding(mesh, P(ba, None)))
    return CellBundle(spec.arch_id, shape, "serve", step, args, shardings,
                      None, note=cell.note)


# --- bandit (the paper's own cell) ---------------------------------------------


def build_bandit_cell(spec, shape: str, mesh: Mesh) -> CellBundle:
    from ..configs import distclub_paper as dp

    hyper: BanditHyper = spec.cfg
    axes = tuple(mesh.axis_names)
    epoch = distclub_shard.build_epoch_fn(mesh, axes, dp.N_USERS, dp.D_FEAT,
                                          hyper)
    specs = distclub_shard.state_specs(axes)
    inputs = spec.input_specs(shape)
    state_abs = distclub_shard.ShardedDistCLUB(
        **{k: v for k, v in inputs.items() if k != "key"})
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    return CellBundle(
        spec.arch_id, shape, "bandit_epoch", epoch,
        (state_abs, inputs["key"]),
        (state_sh, NamedSharding(mesh, P())),
        None, donate_argnums=(0,), note=spec.shapes[shape].note,
    )


# --- dispatcher ------------------------------------------------------------------

_BUILDERS = {
    "lm": build_lm_cell,
    "gnn": build_gnn_cell,
    "recsys": build_recsys_cell,
    "bandit": build_bandit_cell,
}


def build_cell(arch_id: str, shape: str, mesh: Mesh,
               kv_quant: bool = False) -> CellBundle:
    spec = configs.get(arch_id)
    if spec.family == "lm":
        return build_lm_cell(spec, shape, mesh, kv_quant=kv_quant)
    return _BUILDERS[spec.family](spec, shape, mesh)
