"""Distributed tests. Multi-device cases run in SUBPROCESSES that set
--xla_force_host_platform_device_count themselves (the main test process
must keep the default single CPU device — see conftest)."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout=560):
    """Run a python snippet in a subprocess with N host devices."""
    prelude = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={n_devices}'\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c",
                           prelude + textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    """A (2,4) data×model mesh with FSDP×TP rules + activation constraints
    computes the same loss as unsharded execution."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.distributed import sharding as sh
        from repro.models.api import build_model
        from repro.trainer import optimizer as opt
        from repro.trainer.train_loop import make_train_step

        cfg = get_config('tinyllama-1.1b').reduced(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=128, vocab_size=64,
            activation_dtype='float32', param_dtype='float32')
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        batch = {'tokens': tok, 'labels': tok}
        tcfg = TrainConfig(warmup_steps=1, total_steps=2)
        step = make_train_step(model, tcfg)
        o0 = opt.init(params)

        # single device reference
        p_ref, _, m_ref = jax.jit(step)(params, o0, batch)

        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with mesh, sh.activation_policy(mesh):
            ps = sh.param_shardings(mesh, params)
            bs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              sh.batch_pspecs(mesh, batch))
            params_d = jax.device_put(params, ps)
            batch_d = jax.device_put(batch, bs)
            o0_d = opt.init(params_d)
            p_sh, _, m_sh = jax.jit(step)(params_d, o0_d, batch_d)
        np.testing.assert_allclose(float(m_ref['loss']),
                                   float(m_sh['loss']), rtol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)
        print('SHARDED == SINGLE OK')
    """)


def test_elastic_remesh_reshard():
    """Lose 4 of 8 devices -> rebuild (1,4) mesh, reshard params, step."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.elastic import surviving_mesh, reshard_params
        from repro.configs import get_config
        from repro.models.api import build_model

        cfg = get_config('smollm-360m').reduced(num_layers=2, d_model=64,
                                                num_heads=4, num_kv_heads=2,
                                                head_dim=16, d_ff=128,
                                                vocab_size=64)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh0 = jax.make_mesh((2, 4), ('data', 'model'),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2)
        from repro.distributed.sharding import param_shardings
        params = jax.device_put(params, param_shardings(mesh0, params))
        lost = [d.id for d in jax.devices()[:4]]
        mesh1 = surviving_mesh(('data', 'model'), (2, 4), lost)
        assert mesh1.devices.shape == (1, 4), mesh1.devices.shape
        params1 = reshard_params(params, mesh1)
        tok = jnp.zeros((4, 8), jnp.int32)
        loss = model.loss_fn(params1, {'tokens': tok, 'labels': tok})
        assert jnp.isfinite(loss)
        print('ELASTIC OK', mesh1.devices.shape)
    """)


def test_pipeline_shard_map_matches_sequential():
    """4-stage ppermute pipeline == sequential stage application."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_shard_map

        S = 4
        mesh = jax.make_mesh((S,), ('stage',),
                             axis_types=(jax.sharding.AxisType.Auto,) * 1)
        Ws = jax.random.normal(jax.random.PRNGKey(0), (S, 16, 16)) * 0.3

        def stage_fn(stage, x):
            W = jax.lax.dynamic_index_in_dim(Ws, stage, 0, keepdims=False)
            return jnp.tanh(x @ W)

        M, b = 8, 4
        x = jax.random.normal(jax.random.PRNGKey(1), (M, b, 16))
        piped = pipeline_shard_map(stage_fn, mesh, n_microbatches=M)
        y = piped(x)
        # sequential reference
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ Ws[s])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5)
        print('PIPELINE OK')
    """, n_devices=4)


def test_compressed_psum_error_feedback():
    """int8 grad all-reduce: one step is approximate; error feedback makes
    the bias vanish over repeated steps."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum

        mesh = jax.make_mesh((8,), ('data',),
                             axis_types=(jax.sharding.AxisType.Auto,) * 1)
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))

        def one_round(g, r):
            return compressed_psum(g, 'data', r)

        f = jax.shard_map(one_round, mesh=mesh,
                          in_specs=(P('data'), P('data')),
                          out_specs=(P('data'), P('data')), check_vma=False)
        want = jnp.mean(g, axis=0)
        r = jnp.zeros_like(g)
        acc_true = jnp.zeros(128)
        acc_comp = jnp.zeros(128)
        for _ in range(30):
            out, r = f(g, r)
            acc_comp = acc_comp + out[0]
            acc_true = acc_true + want
        rel = float(jnp.linalg.norm(acc_comp - acc_true) /
                    jnp.linalg.norm(acc_true))
        assert rel < 0.02, rel     # EF drives accumulated bias to ~0
        single, _ = f(g, jnp.zeros_like(g))
        rel1 = float(jnp.linalg.norm(single[0] - want) /
                     jnp.linalg.norm(want))
        assert rel1 < 0.2           # single round is lossy but close
        print('COMPRESSED PSUM OK', rel, rel1)
    """)


def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery end-to-end on an 8-device mesh with a reduced
    config (fast proxy for the 512-device production run)."""
    run_with_devices("""
        import jax, dataclasses
        from jax.sharding import NamedSharding
        from repro.configs import get_config, get_shape
        from repro.configs.base import ShapeConfig
        from repro.distributed import sharding as sh
        from repro.launch import roofline as rl
        from repro.launch.dryrun import build_lowerable

        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeConfig('train_tiny', 128, 8, 'train')
        with mesh, sh.activation_policy(mesh):
            fn, args = build_lowerable(
                'tinyllama-1.1b', shape, mesh,
                overrides={'num_layers': 2, 'd_model': 64, 'num_heads': 4,
                           'num_kv_heads': 2, 'head_dim': 16, 'd_ff': 128,
                           'vocab_size': 256})
            compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        roof = rl.derive('tinyllama-1.1b', shape, 'test', 8, cost,
                         compiled.as_text(), get_config('tinyllama-1.1b'))
        assert roof.flops_per_device > 0
        assert roof.collective_ops > 0    # FSDP gathers + grad reductions
        print('DRYRUN-SMALL OK', roof.dominant, roof.collective_ops)
    """)


def test_decode_cell_small_mesh():
    run_with_devices("""
        import jax
        from repro.configs.base import ShapeConfig
        from repro.distributed import sharding as sh
        from repro.launch.dryrun import build_lowerable

        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeConfig('decode_tiny', 256, 8, 'decode')
        with mesh, sh.activation_policy(mesh):
            fn, args = build_lowerable(
                'granite-34b', shape, mesh,
                overrides={'num_layers': 2, 'd_model': 64, 'num_heads': 4,
                           'num_kv_heads': 1, 'head_dim': 16, 'd_ff': 128,
                           'vocab_size': 256, 'max_position': 512})
            compiled = fn.lower(*args).compile()
        print('DECODE-MQA OK')
    """)
