"""Distributed-runtime tests.

The production-mesh dry-run itself runs via ``python -m repro.launch.dryrun``
(512 host devices; results under results/dryrun).  Here we test:
  * the sharded DistCLUB runtime on a real multi-device mesh (subprocess
    with 8 host devices) agrees qualitatively with the single-host run,
  * the decode shard_map matches the single-host decode reference,
  * dry-run artifacts exist for every assigned cell on both meshes.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
RESULTS = REPO / "results" / "dryrun"


def _run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_distclub_learns_on_8_devices():
    out = _run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.distributed import distclub_shard
        from repro.core.types import BanditHyper

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        hyper = BanditHyper(sigma=8, max_rounds=16, gamma=1.5, n_candidates=10)
        init_fn, epoch = distclub_shard.make_runtime(
            mesh, ("data", "model"), n=64, d=8, hyper=hyper)
        state = init_fn(jax.random.PRNGKey(0))
        tot_r = tot_rand = 0.0
        for i in range(5):
            state, m, nclu = epoch(state, jax.random.PRNGKey(i + 1))
            tot_r += float(m.reward.sum()); tot_rand += float(m.rand_reward.sum())
        print("REWARD", tot_r, "RAND", tot_rand, "CLU", int(nclu))
    """)
    parts = out.split()
    reward, rand = float(parts[1]), float(parts[3])
    assert reward > rand * 1.15, out


def test_decode_shard_map_matches_reference():
    out = _run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.models import transformer as tr
        from repro.distributed import decode_shard

        cfg = tr.LMConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_head=16, d_ff=128, vocab=256, qk_norm=True,
                          dtype=jnp.float32, attn_chunk=32)
        params = tr.init_lm(jax.random.PRNGKey(0), cfg)
        B, S = 8, 32
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, 256)
        _, cache = tr.lm_prefill(params, cfg, toks[:, :S])
        pad = 32
        kc = jnp.pad(cache[0], ((0,0),)*4 + ((0,pad),(0,0)))
        vc = jnp.pad(cache[1], ((0,0),)*4 + ((0,pad),(0,0)))
        ref, _ = tr.lm_decode_step(params, cfg, toks[:, S], (kc, vc),
                                   jnp.int32(S))

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        step, p_sh, c_sh = decode_shard.build_decode_step(mesh, cfg, B, S + pad)
        params_d = jax.device_put(params, p_sh)
        kc_d = jax.device_put(kc, c_sh[0]); vc_d = jax.device_put(vc, c_sh[1])
        got, _ = step(params_d, toks[:, S], (kc_d, vc_d), jnp.int32(S))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

        # int8 KV-cache variant (§Perf decode iteration): same step within
        # a few percent despite 2x less cache traffic
        stepq, p_shq, c_shq = decode_shard.build_decode_step(
            mesh, cfg, B, S + pad, kv_quant=True)
        def quant(a):
            sc = jnp.maximum(jnp.max(jnp.abs(a), -1) / 127.0, 1e-8)
            return (jnp.clip(jnp.round(a / sc[..., None]), -127, 127
                             ).astype(jnp.int8), sc.astype(jnp.float32))
        kq, ks = quant(kc.astype(jnp.float32))
        vq, vs = quant(vc.astype(jnp.float32))
        caches_q = tuple(jax.device_put(a, s) for a, s in
                         zip((kq, vq, ks, vs), c_shq))
        gotq, _ = stepq(jax.device_put(params, p_shq), toks[:, S], caches_q,
                        jnp.int32(S))
        ref_n = np.asarray(ref); got_n = np.asarray(gotq)
        denom = np.maximum(np.abs(ref_n).max(), 1e-6)
        assert np.max(np.abs(got_n - ref_n)) / denom < 0.08, "kv_quant drift"
        print("DECODE-OK")
    """)
    assert "DECODE-OK" in out


@pytest.mark.parametrize("tag", ["pod1", "pod2"])
def test_dryrun_artifacts_complete(tag):
    """Every assigned (arch x shape) compiled on both production meshes."""
    if not RESULTS.exists():
        pytest.skip("dry-run results not generated")
    sys.path.insert(0, str(REPO / "src"))
    from repro import configs

    missing = []
    for arch, shape in configs.all_cells():
        p = RESULTS / f"{arch}__{shape}__{tag}.json"
        if not p.exists():
            missing.append((arch, shape))
            continue
        rec = json.loads(p.read_text())
        assert rec["compile_s"] > 0
        assert rec["memory"]["temp_bytes"] is not None
    assert not missing, f"cells missing a {tag} dry-run: {missing}"


def test_dryrun_multi_pod_uses_pod_axis():
    """The multi-pod pass must actually shard over the 'pod' axis."""
    p = RESULTS / "llama3-8b__train_4k__pod2.json"
    if not p.exists():
        pytest.skip("dry-run results not generated")
    rec = json.loads(p.read_text())
    assert rec["mesh"] == [2, 16, 16]
    assert rec["axes"] == ["pod", "data", "model"]


def test_quantized_gather_matches_exact_loss():
    """int8 feature gathers (ogb_products §Perf iteration) must not change
    the loss materially (straight-through exactness is in the backward)."""
    out = _run_with_devices("""
        import dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.models import gnn

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("d",))
        n, e = 128, 512
        cfg = gnn.GNNConfig(d_feat=16, n_classes=5)
        params = gnn.init_gat(jax.random.PRNGKey(0), cfg)
        feats = jax.random.normal(jax.random.PRNGKey(1), (n, 16))
        # dst-partitioned edges: dst within each shard's 16-row block
        dst = jnp.concatenate([jax.random.randint(jax.random.PRNGKey(i), (e // 8,), i * 16, (i + 1) * 16) for i in range(8)])
        src = jax.random.randint(jax.random.PRNGKey(9), (e,), 0, n)
        labels = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, 5)
        mask = jnp.ones((n,), bool)

        def loss_with(cfg):
            f = jax.shard_map(
                lambda p, fe, s, d_, l, m: gnn.gat_loss_local(
                    p, cfg, fe, s, d_, l, m, ("d",)),
                mesh=mesh,
                in_specs=(P(), P("d", None), P("d"), P("d"), P("d"), P("d")),
                out_specs=P(), check_vma=False)
            return float(f(params, feats, src, dst, labels, mask))

        exact = loss_with(cfg)
        quant = loss_with(dataclasses.replace(cfg, quantized_gather=True))
        print("EXACT", exact, "QUANT", quant)
        assert abs(exact - quant) / abs(exact) < 0.05, (exact, quant)
        print("QGATHER-OK")
    """)
    assert "QGATHER-OK" in out
