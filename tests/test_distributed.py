"""Distribution-layer tests on 8 forced host devices (subprocess: jax fixes the
device count at first init, so these run in children)."""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str) -> str:
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-3000:]}"
    return p.stdout


def test_sharded_train_step_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import Mesh
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.optim import AdamWConfig, adamw_init
        from repro.training import make_train_step, train_state_shardings
        from repro.distributed.sharding import batch_sharding, param_sharding

        cfg = get_smoke_config('qwen2.5-3b')
        model = build_model(cfg)
        params = model.init_params(jax.random.key(0))
        opt = adamw_init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)
        batch = {'tokens': toks, 'targets': toks}
        step = make_train_step(model, AdamWConfig(lr=1e-3, total_steps=10))

        # single device reference
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = make_mesh((4, 2), ('data', 'model'))
        with mesh:
            p_sh, o_sh, b_sh = train_state_shardings(model, mesh,
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None))
            p2, o2, m2 = fn(jax.device_put(params, p_sh),
                            jax.device_put(opt, o_sh),
                            jax.device_put(batch, b_sh))
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-2, (m1['loss'], m2['loss'])
        d = jax.tree.map(lambda a, b: float(jnp.abs(a.astype(jnp.float32) -
                                                    b.astype(jnp.float32)).max()), p1, p2)
        md = max(jax.tree.leaves(d))
        assert md < 0.05, md
        print('SHARDED_OK', float(m1['loss']), md)
    """)
    assert "SHARDED_OK" in out


def test_elastic_remesh_preserves_values():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.core.elastic.remesh import scale_replicas

        cfg = get_smoke_config('smollm-360m')
        model = build_model(cfg)
        params = model.init_params(jax.random.key(0))
        ref = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
        devs = jax.devices()
        for n, tp in [(4, 2), (8, 2), (4, 4), (2, 2)]:
            mesh, params = scale_replicas(params, devices=devs[:n], model_parallel=tp)
            cur = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
            for r, c in zip(jax.tree.leaves(ref), jax.tree.leaves(cur)):
                np.testing.assert_array_equal(r, c)
        print('REMESH_OK')
    """)
    assert "REMESH_OK" in out


def test_checkpoint_restore_resharded():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.checkpoint import save_checkpoint, restore_resharded
        from repro.distributed.sharding import param_sharding

        cfg = get_smoke_config('smollm-135m')
        model = build_model(cfg)
        params = model.init_params(jax.random.key(0))
        d = tempfile.mkdtemp()
        p = os.path.join(d, 'ck.npz')
        save_checkpoint(p, params, step=1)
        # restore onto a DIFFERENT mesh shape than the save-time layout
        mesh = make_mesh((2, 4), ('data', 'model'))
        abstract = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        sh = param_sharding(abstract, mesh)
        restored, meta = restore_resharded(p, params, sh)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        print('RESHARD_OK')
    """)
    assert "RESHARD_OK" in out


def test_int8_pod_gradient_compression():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (
            compress_allreduce_pod, init_error_state)

        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        grads = {'w': jnp.linspace(-1, 1, 64).reshape(8, 8)}
        err = init_error_state(grads)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=(P(), P()), axis_names=frozenset({'pod'}),
                 check_vma=False)
        def f(g, e):
            return compress_allreduce_pod(g, e)

        with mesh:                      # partial-auto shard_map needs the mesh context
            red, new_err = jax.jit(f)(grads, err)
        # identical replicas => reduction == original up to int8 error
        q_err = float(jnp.abs(red['w'] - grads['w']).max())
        assert q_err < 2.0 / 127.0, q_err
        # error feedback: residual matches quantization error exactly
        assert float(jnp.abs(new_err['w'] + red['w'] - grads['w'] - err['w']).max()) < 1e-6
        print('COMPRESS_OK', q_err)
    """)
    assert "COMPRESS_OK" in out


def test_dryrun_cell_small_mesh():
    """A miniature dry-run cell: lower+compile on an in-test 8-device mesh."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import Mesh
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.optim import AdamWConfig, adamw_init
        from repro.training import make_train_step, train_state_shardings

        cfg = get_smoke_config('olmoe-1b-7b')     # MoE: exercises EP sharding
        model = build_model(cfg)
        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        with mesh:
            p_abs = model.abstract_params()
            specs = {'tokens': jax.ShapeDtypeStruct((8, 32), jnp.int32),
                     'targets': jax.ShapeDtypeStruct((8, 32), jnp.int32)}
            step = make_train_step(model, AdamWConfig())
            p_sh, o_sh, b_sh = train_state_shardings(model, mesh, specs)
            o_abs = jax.eval_shape(adamw_init, p_abs)
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None))
            compiled = fn.lower(p_abs, o_abs, specs).compile()
            assert compiled.cost_analysis() is not None
        print('MINIDRYRUN_OK')
    """)
    assert "MINIDRYRUN_OK" in out
