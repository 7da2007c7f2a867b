"""The named scopes of the compiled DistCLUB epoch.

``distclub._run`` is lowered (not run) at a small size and its HLO read
with op metadata: every scope the stage engine and ``_run`` open
appears in some instruction's ``op_name``, and every instruction of the
program's entry and of the epoch loop (body and condition) falls under
at least one scope, so a profile can charge all device time to a stage.
The sharded runtime binds the same stage bodies, so it carries the same
stage and round-step names.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import distclub, env, env_ops
from repro.core.backend import BackendConfig
from repro.core.types import BanditHyper
from test_distributed import _run_with_devices

N, D, K = 64, 8, 10
HYPER = BanditHyper(sigma=8, max_rounds=16, gamma=1.5, n_candidates=K)

STAGE_SCOPES = ("stage1", "stage2", "stage3", "stage4", "env_contexts",
                "env_rewards", "score", "choose", "fold", "round_metrics",
                "prune", "cc", "gram_inverse", "cluster_reduce",
                "cluster_inverse")
SCOPES = STAGE_SCOPES + ("init", "epoch", "refresh_gram")
# instructions that do no work of their own
TRIVIAL = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}

_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\((.*)$")


def _computations(hlo: str):
    """``({computation: [(name, opcode, operands+attrs, op_name)]}, entry)``."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = _HEAD.match(line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, opcode, rest = m.groups()
            on = re.search(r'op_name="([^"]*)"', rest)
            comps[cur].append((name, opcode, rest, on.group(1) if on else ""))
    return comps, entry


def _scoped(op_name: str) -> bool:
    return any(p in SCOPES for p in op_name.split("/"))


def _unscoped(instrs):
    out = []
    for name, opcode, rest, op_name in instrs:
        if opcode in TRIVIAL or _scoped(op_name):
            continue
        # a constant splat the converter writes without metadata
        if opcode == "broadcast" and not op_name and re.match(
                r"\s*%?constant[\w.]*\)", rest):
            continue
        out.append((name, opcode, op_name))
    return out


@pytest.fixture(scope="module")
def planted_ops():
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
    return env_ops.synthetic_ops(e)


@pytest.mark.parametrize("kind", ["reference", "pallas"])
def test_every_epoch_instruction_falls_under_a_scope(planted_ops, kind):
    bc = BackendConfig.create(kind, "f32")
    be = bc.interact(N, D, K)
    gb = bc.graph(N, interpret=be.interpret)
    hlo = distclub._run.lower(planted_ops, jax.random.PRNGKey(1), HYPER, 2,
                              D, be, gb).as_text(dialect="hlo",
                                                 debug_info=True)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    parts = {p for n in names for p in n.split("/")}
    assert set(SCOPES) <= parts, sorted(set(SCOPES) - parts)

    comps, entry = _computations(hlo)
    loops = [(rest, op_name) for _, opcode, rest, op_name in comps[entry]
             if opcode == "while"]
    assert len(loops) == 1 and loops[0][1].endswith("epoch/while")
    body = re.search(r"body=%?([\w.\-]+)", loops[0][0]).group(1)
    cond = re.search(r"condition=%?([\w.\-]+)", loops[0][0]).group(1)
    for comp in (entry, body, cond):
        assert comps[comp], comp
        assert _unscoped(comps[comp]) == [], comp


def test_sharded_epoch_carries_the_stage_scopes():
    out = _run_with_devices(f"""
        import re
        import jax
        from repro.core import env, env_ops
        from repro.core.types import BanditHyper
        from repro.distributed import distclub_shard
        from repro.launch.mesh import make_mesh

        N, D, K = {N}, {D}, {K}
        hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5,
                            n_candidates=K)
        e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), N, D, 4, K)
        mesh = make_mesh((8,), ("users",))
        init_fn, epoch = distclub_shard.make_runtime(
            mesh, ("users",), N, D, hyper, ops=env_ops.synthetic_ops(e))
        hlo = epoch.lower(init_fn(None), jax.random.PRNGKey(1)).as_text(
            dialect="hlo", debug_info=True)
        parts = set()
        for n in re.findall(r'op_name="([^"]*)"', hlo):
            parts.update(n.split("/"))
        print("PARTS", " ".join(sorted(parts)))
    """)
    line = next(l for l in out.splitlines() if l.startswith("PARTS "))
    parts = set(line.split()[1:])
    assert set(STAGE_SCOPES) <= parts, sorted(set(STAGE_SCOPES) - parts)


SERVE_SCOPES = ("serve", "gather_score", "tile_bounds", "retrieve",
                "choose", "env_rewards", "fold", "refresh", "stage2",
                "prune", "cc")


def _serve_reward(key, uids, ctx, slot):
    x = jnp.take_along_axis(ctx, slot[:, None, None], axis=1)[:, 0]
    return (jnp.sum(x, axis=-1) > 0).astype(ctx.dtype)


@pytest.mark.parametrize("kind", ["reference", "pallas"])
def test_catalog_transaction_carries_the_serve_scopes(kind):
    """The cluster-pruned catalog transaction names its parts, and every
    instruction of its entry computation falls under ``serve``."""
    from repro import serve
    from repro.serve import session as session_mod
    sess = serve.OnlineBandit.create(N, D, HYPER, policy="distclub",
                                     refresh_every=N, backend=kind)
    cat = serve.random_catalog(jax.random.PRNGKey(2), 1024, D)
    clusters = serve.build_clusters(cat, tile_items=256, kind=kind)
    rb = session_mod._retrieval_engine(sess, 16)
    fn = session_mod._catalog_step_fn(sess.policy, rb, _serve_reward, None,
                                      (), True)
    uids = jnp.arange(32, dtype=jnp.int32)
    hlo = fn.lower(sess.state, jax.random.PRNGKey(3), uids, cat,
                   clusters).as_text(dialect="hlo", debug_info=True)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    parts = {p for n in names for p in n.split("/")}
    assert set(SERVE_SCOPES) <= parts, sorted(set(SERVE_SCOPES) - parts)
    paths = {"/".join(p for p in n.split("/") if p in SERVE_SCOPES)
             for n in names}
    assert {"serve/refresh/stage2/prune", "serve/tile_bounds",
            "serve/retrieve", "serve/fold"} <= paths

    comps, entry = _computations(hlo)
    unscoped = [(name, opcode, on) for name, opcode, on in
                _unscoped(comps[entry]) if "serve" not in on.split("/")]
    assert unscoped == []
