"""Multi-device semantics, exercised in subprocesses with 8 fake CPU devices
(the main pytest process must keep seeing 1 device)."""
import subprocess
import sys
import textwrap

import pytest


def run_with_devices(body: str, n: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, jax.numpy as jnp
        import numpy as np
        import sys
        sys.path.insert(0, "src")
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, cwd=".")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


class TestCollectives:
    def test_compressed_psum_error_feedback(self):
        run_with_devices("""
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum

        mesh = jax.make_mesh((8,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        xs = jax.random.normal(jax.random.PRNGKey(0), (8, 1024)) * 3
        err0 = jnp.zeros((8, 1024))

        def f(x, e):
            y, ne = compressed_psum(x[0], "pod", e[0])
            return y[None], ne[None]

        g = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                          out_specs=(P("pod"), P("pod")), check_vma=False)
        y, err = g(xs, err0)
        exact = jnp.mean(xs, axis=0)
        # every shard sees the same mean, approx equal to exact
        for i in range(8):
            rel = float(jnp.linalg.norm(y[i] - exact) / jnp.linalg.norm(exact))
            assert rel < 0.02, rel
        # error feedback: residual equals what quantization dropped
        assert float(jnp.max(jnp.abs(err))) < float(jnp.max(jnp.abs(xs))) / 50
        # second round with EF reduces bias vs without
        print("OK")
        """)

    def test_gpipe_matches_dense(self):
        run_with_devices("""
        from repro.distributed.pipeline_parallel import gpipe_forward
        from jax.sharding import PartitionSpec as P

        n_stages, n_micro, mb, d = 4, 8, 4, 16
        mesh = jax.make_mesh((4,), ("stage",))
        ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.3

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        pp = gpipe_forward(stage_fn, mesh, "stage", n_stages)
        xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
        got = pp(ws, xs)

        ref = xs
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
        """)

    def test_dp_tp_train_step_matches_single_device(self):
        run_with_devices("""
        from repro.core import quant as Q
        from repro.distributed.sharding import ShardingPlan, default_rules
        from repro.models import build_model, get_config
        from repro.training.optimizer import AdamW, AdamWConfig
        from repro.training.trainer import init_train_state, make_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_config("qwen1.5-0.5b").reduced().replace(
            compute_dtype="float32")
        model = build_model(cfg)
        opt = AdamW(AdamWConfig(weight_decay=0.0, grad_clip=0.0))
        step = make_train_step(model, opt, lambda s: 1e-3)
        params = model.init(jax.random.PRNGKey(0))
        state = init_train_state(params, opt)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 200),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 200),
            "loss_mask": jnp.ones((8, 16), jnp.float32),
        }
        # single device reference
        s1, m1 = jax.jit(step)(state, batch)

        # dp=2 x tp=4 sharded
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ShardingPlan(mesh, default_rules(False))
        p_sh = plan.tree_shardings(model.param_axes(), params)
        o_sh = plan.tree_shardings(opt.state_axes(model.param_axes()),
                                   state.opt_state)
        from repro.training.trainer import TrainState
        st_sh = TrainState(p_sh, o_sh, NamedSharding(mesh, P()))
        b_sh = {k: plan.sharding(("batch", "seq"), v.shape)
                for k, v in batch.items()}
        with mesh:
            stepd = jax.jit(step, in_shardings=(st_sh, b_sh))
            s2, m2 = stepd(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \
            (float(m1["loss"]), float(m2["loss"]))
        w1 = jax.tree_util.tree_leaves(s1.params)[3]
        w2 = jax.tree_util.tree_leaves(s2.params)[3]
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                                   rtol=5e-3, atol=5e-3)
        print("OK")
        """)


@pytest.mark.slow
class TestDryRunSmoke:
    """End-to-end dry-run machinery on a small cell (512 fake devices) —
    by far the slowest test in the suite (SPMD compile in a subprocess);
    slow-marked, runs in the full tier-1 suite only."""

    def test_dryrun_cell_produces_roofline(self):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             "qwen1.5-0.5b", "--shape", "decode_32k", "--force",
             "--tag", "citest"],
            capture_output=True, text=True, timeout=900, cwd=".",
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
        assert r.returncode == 0, r.stderr[-2000:]
        import json, pathlib
        p = pathlib.Path("benchmarks/results/dryrun/"
                         "qwen1.5-0.5b__decode_32k__1pod__citest.json")
        d = json.loads(p.read_text())
        assert d["status"] == "ok"
        assert d["roofline"]["flops"] > 0
        assert d["roofline"]["bottleneck"] in ("compute", "memory", "collective")
        assert d["n_devices"] == 256
