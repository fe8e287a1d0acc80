"""Distributed-correctness tests.

These run in SUBPROCESSES with ``--xla_force_host_platform_device_count=8``
so the main pytest process keeps a single device (smoke tests must see one
device; the 512-way override belongs to the dry-run only).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(script: str, n_devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # Pin the subprocess to the CPU platform: these are CPU-emulation tests,
    # and with libtpu installed an unset JAX_PLATFORMS makes backend init
    # probe for (absent) TPU hardware — which can hang past the timeout.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """pjit train step on a 2x4 mesh == single-device step (same seed)."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.nn.module import init_shapes
        from repro.nn.transformer import TransformerLM
        from repro.optim.optimizer import adamw, apply_updates

        cfg = get_config("qwen2-1.5b", preset="smoke")
        model = TransformerLM(cfg)
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (8, 33), 2, cfg.vocab)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        opt = adamw(1e-3, clip_norm=1.0)

        def step(params, opt_state, batch):
            (l, m), g = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
            upd, opt_state, _ = opt.update(g, opt_state, params,
                                           jnp.zeros((), jnp.int32))
            return apply_updates(params, upd), l

        # single device reference
        params = model.init(key)
        ref_params, ref_loss = step(params, opt.init(params), batch)

        # 2x4 mesh, tp rules
        mesh = make_mesh((2, 4), ("data", "model"))
        shapes = init_shapes(model)
        psh = shd.param_shardings(model, mesh, "fsdp_tp", shapes)
        bsh = shd.batch_sharding(mesh, "fsdp_tp", batch=8)
        with mesh:
            params_s = jax.jit(model.init, out_shardings=psh)(key)
            os_ = jax.jit(opt.init)(params_s)
            batch_s = jax.device_put(batch, {"tokens": bsh, "labels": bsh})
            new_params, loss = jax.jit(step)(params_s, os_, batch_s)
        print("LOSS_DIFF", abs(float(loss) - float(ref_loss)))
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(ref_params), jax.tree.leaves(new_params)))
        print("PARAM_DIFF", d)
    """)
    loss_diff = float(out.split("LOSS_DIFF")[1].split()[0])
    param_diff = float(out.split("PARAM_DIFF")[1].split()[0])
    assert loss_diff < 1e-4
    assert param_diff < 1e-4


def test_mosa_head_parallel_matches_replicated():
    """MoSA heads sharded over the model axis == replicated computation."""
    out = run_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import MoSAConfig
        from repro.core.mosa import MoSAAttention
        from repro.launch.mesh import make_mesh

        cfg = MoSAConfig(n_mosa_heads=8, sparsity=4, n_dense_heads=0, d_head=16)
        m = MoSAAttention(64, cfg)
        key = jax.random.PRNGKey(0)
        p = m.init(key)
        x = jax.random.normal(key, (4, 64, 64))
        y_ref = m(p, x)

        mesh = make_mesh((2, 4), ("data", "model"))
        heads = NamedSharding(mesh, P("model"))
        psh = {"router": {"w": heads},
               "wq": heads, "wk": heads, "wv": heads, "wo": heads}
        bsh = NamedSharding(mesh, P("data"))
        with mesh:
            y = jax.jit(m.__call__, in_shardings=(psh, bsh))(p, x)
        print("DIFF", float(jnp.abs(y - y_ref).max()))
    """)
    assert float(out.split("DIFF")[1].split()[0]) < 1e-4


def test_pipeline_parallel_matches_sequential():
    out = run_devices("""
        import jax, jax.numpy as jnp
        from repro.dist.pipeline import pipeline_forward, stack_stage_params
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("pipe",))
        key = jax.random.PRNGKey(0)
        ws = [jax.random.normal(jax.random.fold_in(key, i), (16, 16)) * 0.3
              for i in range(4)]
        stage_params = stack_stage_params([{"w": w} for w in ws])

        def stage(p, x):
            return jnp.tanh(x @ p["w"]) + x

        x = jax.random.normal(key, (8, 16))
        y_seq = x
        for w in ws:
            y_seq = stage({"w": w}, y_seq)
        y_pipe = pipeline_forward(stage, stage_params, x, mesh=mesh,
                                  n_microbatches=4)
        print("DIFF", float(jnp.abs(y_pipe - y_seq).max()))

        # gradients flow through the pipeline
        def loss(sp):
            return jnp.sum(pipeline_forward(stage, sp, x, mesh=mesh,
                                            n_microbatches=4) ** 2)
        g = jax.grad(loss)(stage_params)
        print("GNORM", float(jnp.linalg.norm(g["w"])))
    """)
    assert float(out.split("DIFF")[1].split()[0]) < 1e-5
    assert float(out.split("GNORM")[1].split()[0]) > 0


def test_compressed_psum_cross_pod():
    """top-k compressed all-reduce over a pod axis, with error feedback."""
    out = run_devices("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.grad_compression import compressed_psum

        mesh = make_mesh((2, 4), ("pod", "data"))
        g = jax.random.normal(jax.random.PRNGKey(0), (2, 256))

        @partial(shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                 check_vma=False)
        def reduce_exact(g):
            out, _ = compressed_psum({"g": g}, "pod", "none")
            return out["g"]

        @partial(shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                 check_vma=False)
        def reduce_topk(g):
            out, res = compressed_psum({"g": g}, "pod", "topk", topk_frac=0.5)
            return out["g"] + jax.lax.psum(res["g"], "pod")  # add back residual

        exact = reduce_exact(g)
        approx = reduce_topk(g)
        print("DIFF", float(jnp.abs(exact - approx).max()))
    """)
    # compressed + residual == exact (error feedback is lossless in sum)
    assert float(out.split("DIFF")[1].split()[0]) < 1e-5


def test_dryrun_single_cell_subprocess():
    """The dry-run entry point works end to end for one light cell."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"      # see run_devices: avoid TPU probing
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-1.5b",
         "--shape", "decode_32k", "--out-dir", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "all cells compiled OK" in out.stdout
    with open("/tmp/dryrun_test/16x16/qwen2-1.5b__decode_32k.json") as f:
        rec = json.load(f)
    assert rec["analytic"]["flops_global"] > 0
    assert rec["memory"]["total_per_device"] > 0


def test_moe_ep_shard_map_matches_vmap_path():
    """Expert-parallel shard_map MoE == per-row vmap dispatch (it.11)."""
    out = run_devices("""
        import jax, jax.numpy as jnp
        from repro.configs.base import MoEConfig
        from repro.nn.ffn import MoEFFN
        from repro.launch.mesh import make_mesh
        from repro.dist import hints

        cfg = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=4.0)
        m = MoEFFN(64, cfg)
        key = jax.random.PRNGKey(0)
        p = m.init(key)
        x = jax.random.normal(key, (4, 32, 64))
        y_ref, aux_ref = m(p, x)                    # vmap path (no hints)
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, hints.sharding_hints(mesh=mesh):
            y_ep, aux_ep = jax.jit(m.__call__)(p, x)  # EP path
            g = jax.jit(jax.grad(lambda p_: m(p_, x)[0].sum()))(p)
        print("DIFF", float(jnp.abs(y_ref - y_ep).max()))
        print("AUXDIFF", abs(float(aux_ref) - float(aux_ep)))
        print("GNORM", float(jnp.linalg.norm(g["w_gate"])))
    """)
    assert float(out.split("DIFF")[1].split()[0]) < 1e-4
    assert float(out.split("AUXDIFF")[1].split()[0]) < 1e-5
    assert float(out.split("GNORM")[1].split()[0]) > 0


def test_mosa_kernel_per_shard_matches_unsharded():
    """The fused MoSA kernel under a (data, model) mesh runs per (batch,
    head) shard (a Mosaic kernel cannot be SPMD-partitioned); outputs and
    all four gradients equal the unsharded call."""
    out = run_devices("""
        import jax, jax.numpy as jnp
        from repro.dist import hints
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh

        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        B, H, S, d = 4, 6, 16, 8
        q, k, v = (jax.random.normal(kk, (B, H, S, d)) for kk in ks[:3])
        idx = jnp.sort(jax.random.randint(ks[3], (B, H, S), 0, 64), -1)
        r = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, S)))

        def loss(q, k, v, r):
            return jnp.sum(ops.mosa_attention(q, k, v, idx, r) ** 2)

        def run():
            return (jax.jit(lambda *a: ops.mosa_attention(*a))(q, k, v, idx, r),
                    jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(q, k, v, r))

        want = run()
        mesh = make_mesh((2, 2), ("data", "model"))
        with mesh, hints.sharding_hints(mesh=mesh):
            got = run()
            txt = jax.jit(lambda *a: ops.mosa_attention(*a)).lower(
                q, k, v, idx, r).as_text()
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             want, got)
        print("DIFF", max(jax.tree.leaves(diffs)))
        print("SHARDMAP", "shard_map" in txt or "sdy.manual_computation" in txt)
    """, n_devices=4)
    assert float(out.split("DIFF")[1].split()[0]) < 1e-5
    assert out.split("SHARDMAP")[1].split()[0] == "True"
