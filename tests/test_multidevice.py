"""Multi-device behaviours (subprocess: forced host device count).

XLA fixes the device count at first jax init, and the suite must keep the
default single device for everything else — so these run in subprocesses.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(script: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_vocab_parallel_ce_matches_dense():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.vocab_ce import make_vocab_parallel_ce
        from repro.train.loss import cross_entropy
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,4), ("data","model"))
        B,S,D,V = 4, 16, 32, 64
        h = jax.random.normal(jax.random.PRNGKey(0), (B,S,D))
        w = jax.random.normal(jax.random.PRNGKey(1), (D,V)) * 0.1
        labels = jax.random.randint(jax.random.PRNGKey(2), (B,S), -1, V)
        ce = make_vocab_parallel_ce(mesh, ("data",), ("model",), V, tied=False)
        with mesh:
            got = float(ce(w, h, labels))
            g1 = jax.grad(lambda w: ce(w, h, labels))(w)
        want = float(cross_entropy(jnp.einsum("bsd,dv->bsv", h, w), labels))
        g2 = jax.grad(lambda w: cross_entropy(
            jnp.einsum("bsd,dv->bsv", h, w), labels))(w)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-6)
        print("OK")
        """)
    assert "OK" in out


def test_inter_model_communicator_preserves_values():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.communicator import make_communicator
        from repro.sharding.partition import AxisAssignment
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,4), ("data","model"))
        enc = AxisAssignment(batch=("data","model"), tensor=())
        llm = AxisAssignment(batch=("data",), tensor=("model",))
        comm = make_communicator(mesh, enc, llm)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 16))
        xs = jax.device_put(x, NamedSharding(mesh, P(("data","model"))))
        with mesh:
            y = jax.jit(comm)(xs)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)
        # Output follows the LLM layout — but only where this jax version
        # lets with_sharding_constraint control a jit *boundary* (older
        # GSPMD overrides boundary output shardings via propagation; the
        # constraint still binds intermediates, the communicator's actual
        # position in a step function).  Feature-probe first.
        probe = jax.jit(lambda v: jax.lax.with_sharding_constraint(
            v, NamedSharding(mesh, P("data", None, None))))
        with mesh:
            honors = probe(xs).sharding.spec[0] in (("data",), "data")
        if honors:
            assert y.sharding.spec[0] in (("data",), "data"), y.sharding.spec
        print("OK")
        """)
    assert "OK" in out


def test_pipeline_executor_matches_sequential():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.pipeline.executor import (build_stage_fn,
                                                  pipeline_forward,
                                                  stack_stage_params)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("stage",))
        n_layers, d = 8, 16
        key = jax.random.PRNGKey(0)
        W = jax.random.normal(key, (n_layers, d, d)) * (d ** -0.5)

        def layer(w, x):
            return jnp.tanh(x @ w)

        stage_fn = build_stage_fn(lambda lp, h: layer(lp, h), 2)
        stacked = stack_stage_params(W, 4)
        m, mb, S = 4, 2, 8
        xs = jax.random.normal(jax.random.PRNGKey(1), (m, mb, S, d))
        pipe = pipeline_forward(mesh, stage_fn)
        with mesh:
            got = pipe(jax.device_put(stacked, NamedSharding(mesh, P("stage"))),
                       xs)
        # sequential reference
        ref = xs
        for i in range(n_layers):
            ref = jnp.tanh(ref @ W[i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # differentiable
        g = jax.grad(lambda W4: jnp.sum(pipe(W4, xs)))(
            jax.device_put(stacked, NamedSharding(mesh, P("stage"))))
        assert np.isfinite(np.asarray(jax.tree_util.tree_leaves(g)[0])).all()
        print("OK")
        """)
    assert "OK" in out


def test_reshard_params_preserves_pipeline_outputs():
    """Property: `pipeline_forward` outputs are bit-identical before vs.
    after `reshard_params` across a chain of (tp, pp) transitions —
    including pp values that re-partition layers (4->2, 2->8, 8->1,
    1->2) — and each transition moves exactly the param bytes."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.pipeline.executor import (build_stage_fn,
                                                  pipeline_forward,
                                                  stack_stage_params)
        from repro.core.optimizer.space import (ModuleParallelism,
                                                ParallelismPlan)
        from repro.launch.reshard import plan_mesh, reshard_params

        n_layers, d = 8, 16
        W = jax.random.normal(jax.random.PRNGKey(0), (n_layers, d, d)) \\
            * (d ** -0.5)
        m, mb, S = 4, 2, 8
        xs = jax.random.normal(jax.random.PRNGKey(1), (m, mb, S, d))

        def layer(w, x):
            return jnp.tanh(x @ w)

        def plan(dp, pp, tp):
            return ParallelismPlan(llm=ModuleParallelism(tp, pp, dp),
                                   n_mb=m)

        def run_pipe(stacked, pl):
            mesh = plan_mesh(pl)
            pipe = pipeline_forward(
                mesh, build_stage_fn(layer, n_layers // pl.llm.pp))
            with mesh:
                return np.asarray(pipe(stacked, xs))

        ref = xs
        for i in range(n_layers):
            ref = jnp.tanh(ref @ W[i])
        ref = np.asarray(ref)

        p0 = plan(1, 4, 2)
        params = jax.device_put(stack_stage_params(W, 4),
                                NamedSharding(plan_mesh(p0), P("stage")))
        out0 = run_pipe(params, p0)
        assert np.array_equal(out0, ref), "pp=4 pipeline != sequential"

        total = int(sum(l.nbytes
                        for l in jax.tree_util.tree_leaves(params)))
        prev = p0
        for (dp, pp, tp) in [(1, 4, 1), (2, 2, 1), (1, 8, 1), (1, 1, 4),
                             (1, 2, 2), (1, 4, 1)]:
            nxt = plan(dp, pp, tp)
            params, rep = reshard_params(params, prev, nxt,
                                         stage_stacked=True)
            got = run_pipe(params, nxt)
            assert np.array_equal(got, out0), (prev.llm, nxt.llm)
            # ReshardReport sanity: a layout transition moves every byte
            assert rep.bytes_moved == rep.bytes_total == total, rep
            assert rep.elapsed_s >= 0.0
            assert rep.restacked == (prev.llm.pp != pp)
            prev = nxt
        print("OK")
        """)
    assert "OK" in out


def test_reshard_clamped_mesh_replicates_non_divisible_stage():
    """Emulation path: a clamped mesh can be narrower than the plan's PP
    (pp=3 on a 2-wide stage axis) — the reshard must fall back to
    replication instead of failing device_put on a non-divisible
    P('stage') sharding."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.pipeline.executor import (stack_stage_params,
                                                  unstack_stage_params)
        from repro.core.optimizer.space import (ModuleParallelism,
                                                ParallelismPlan)
        from repro.launch.reshard import clamped_plan_mesh, reshard_params

        W = jnp.arange(6 * 4, dtype=jnp.float32).reshape(6, 4)
        old = ParallelismPlan(llm=ModuleParallelism(1, 1, 1))
        new = ParallelismPlan(llm=ModuleParallelism(1, 3, 1))
        mesh = clamped_plan_mesh(new, devices=jax.devices()[:2])
        assert dict(mesh.shape)["stage"] == 2
        got, rep = reshard_params(stack_stage_params(W, 1), old, new,
                                  stage_stacked=True, new_mesh=mesh)
        assert rep.restacked and got.shape == (3, 2, 4)
        assert got.sharding.spec == jax.sharding.PartitionSpec()
        np.testing.assert_array_equal(
            np.asarray(unstack_stage_params(got)), np.asarray(W))
        print("OK")
        """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_smoke_small_mesh():
    """A miniature dry-run on 8 host devices: gemma reduced config lowers
    and compiles with the production code path."""
    out = run_devices("""
        import jax, dataclasses
        from repro.configs import get_config
        from repro.common.types import INPUT_SHAPES, ShapeSpec
        from repro.launch import dryrun as D
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        spec = get_config("gemma-2b")
        spec = dataclasses.replace(spec, desc=spec.reduced_desc())
        shape = ShapeSpec("mini", 256, 16, "train")
        jitted, args, extra = D.build_train(spec, shape, mesh)
        with mesh:
            co = jitted.lower(*args).compile()
        print("compiled OK", co.memory_analysis().temp_size_in_bytes > 0)
        """, n=8)
    assert "compiled OK" in out


def test_ep_shard_map_moe_matches_dense():
    """Expert-parallel shard_map MoE (§Perf iteration 7) vs the dense
    oracle (high capacity factor -> no drops)."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.common.types import ModelConfig
        from repro.models.layers import moe
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,4), ("data","model"))
        cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=97,
                          ffn_pattern=("moe",), n_experts=8, top_k=2,
                          dtype="float32")
        p = moe.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
        y_ref, _ = moe.apply_dense(p, x, cfg)
        with mesh:
            y_ep, lb = jax.jit(lambda p, x: moe.apply_ep_shard_map(
                p, x, cfg, (mesh, ("data",), ("model",)),
                capacity_factor=8.0))(p, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                                   rtol=2e-3, atol=2e-3)
        g = jax.grad(lambda p: jnp.sum(moe.apply_ep_shard_map(
            p, x, cfg, (mesh, ("data",), ("model",)),
            capacity_factor=8.0)[0]**2))(p)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(g))
        print("OK")
        """)
    assert "OK" in out


def test_sharded_mamba_scan_matches_plain():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.layers.mamba import ssm_scan_xla, ssm_scan_sharded
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,4), ("data","model"))
        B,S,di,N = 4, 32, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        u = jax.random.normal(ks[0], (B,S,di))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B,S,di)))
        Bt = jax.random.normal(ks[2], (B,S,N))
        Ct = jax.random.normal(ks[3], (B,S,N))
        A = -jnp.exp(jax.random.normal(ks[4], (di,N))*0.3)
        Dd = jax.random.normal(ks[5], (di,))
        y0, h0 = ssm_scan_xla(u, dt, Bt, Ct, A, Dd)
        ctx = (mesh, ("data",), ("model",))
        with mesh:
            y1, h1 = jax.jit(lambda *a: ssm_scan_sharded(*a, ctx))(
                u, dt, Bt, Ct, A, Dd)
            g0 = jax.grad(lambda u: jnp.sum(
                ssm_scan_xla(u, dt, Bt, Ct, A, Dd)[0]**2))(u)
            g1 = jax.grad(lambda u: jnp.sum(
                ssm_scan_sharded(u, dt, Bt, Ct, A, Dd, ctx)[0]**2))(u)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                                   rtol=2e-4, atol=2e-5)
        print("OK")
        """)
    assert "OK" in out


def test_tp_expert_shard_map_moe_non_divisible():
    """E ∤ model-axis fallback: TP-sharded experts with local dispatch
    (mixtral 8e / granite 40e on a 16-wide axis)."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.common.types import ModelConfig
        from repro.models.layers import moe
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,4), ("data","model"))
        cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=97,
                          ffn_pattern=("moe",), n_experts=6, top_k=2,
                          dtype="float32")       # 6 experts over 4-wide axis
        p = moe.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
        y_ref, _ = moe.apply_dense(p, x, cfg)
        with mesh:
            y_tp, _ = jax.jit(lambda p, x: moe.apply_ep_shard_map(
                p, x, cfg, (mesh, ("data",), ("model",)),
                capacity_factor=8.0))(p, x)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_tp),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
        """)
    assert "OK" in out
