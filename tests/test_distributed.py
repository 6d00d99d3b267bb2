"""Distribution-layer tests. Mesh-dependent tests run in subprocesses with
fake devices (XLA device count is locked at first jax init — the main pytest
process stays at 1 CPU device)."""
import json
import subprocess
import sys
import textwrap

import pytest


def _run_fake_devices(script: str, n_devices: int = 8, timeout: int = 360) -> str:
    env = {
        # the children compile for fake CPU devices and must never take an
        # accelerator the parent or another test process may hold
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "PYTHONPATH": "src",
        "PATH": "/usr/bin:/bin",
        "HOME": "/tmp",
    }
    import os

    env["PATH"] = os.environ.get("PATH", env["PATH"])
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, **env},
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_spec_builder_shape_checks():
    """Pure sharding-rule logic (no mesh state needed beyond construction)."""
    out = _run_fake_devices("""
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import ShardingConfig, _spec_for
        mesh = make_mesh((4, 2), ("data", "model"))
        scfg = ShardingConfig()
        rules = scfg.rules()
        # weight (embed, mlp): fsdp over data + tp over model
        s = _spec_for((64, 128), ("embed", "mlp"), rules, mesh, True, ("data",))
        assert s == P("data", "model"), s
        # vocab-dim weight: no fsdp on embed
        s = _spec_for((100, 64), ("vocab", "embed"), rules, mesh, True, ("data",))
        assert s == P("model"), s
        # non-divisible dims degrade to replication (batch=1)
        s = _spec_for((1, 7), ("batch", "mlp"), rules, mesh, False, ("data",))
        assert s == P(), s
        # an axis is never used twice
        s = _spec_for((8, 8, 8), ("experts", "mlp", "heads"), rules, mesh, False, ("data",))
        assert str(s).count("model") == 1, s
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_small_mesh_train_and_serve_lower():
    """A miniature end-to-end dry-run on an 8-device (4×2) mesh: train and
    decode steps lower+compile with the production sharding rules."""
    out = _run_fake_devices("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke_config
        from repro.models.registry import get_model
        from repro.models.layers import abstract_params, logical_specs
        from repro.distributed.sharding import (ShardingConfig, build_param_specs,
                                                build_cache_specs)
        from repro.train.optimizer import AdamWConfig, abstract_opt_state
        from repro.train.train_step import make_train_step

        mesh = make_mesh((4, 2), ("data", "model"))
        scfg = ShardingConfig()
        cfg = get_smoke_config("qwen3_8b")
        model = get_model(cfg)
        defs = model.param_defs()
        ap = abstract_params(defs); la = logical_specs(defs)
        ps = build_param_specs(ap, la, mesh, scfg)
        oa = abstract_opt_state(ap)
        osd = {"m": build_param_specs(oa["m"], la, mesh, scfg),
               "v": build_param_specs(oa["v"], la, mesh, scfg),
               "count": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        bs = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
        step = make_train_step(model, AdamWConfig(), microbatches=2)
        with jax.set_mesh(mesh):
            c = jax.jit(step, in_shardings=(ps, osd, {"tokens": bs, "labels": bs}),
                        donate_argnums=(0, 1)).lower(ap, oa, batch).compile()
        assert c.memory_analysis().temp_size_in_bytes > 0
        # decode
        cache = model.cache_shape(8, 64)
        cs = build_cache_specs(cache, mesh, scfg, cfg.n_kv_heads)
        tok = jax.ShapeDtypeStruct((8,), jnp.int32)
        ts = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
        fn = lambda p, t, c: model.decode_step(p, t, c)
        with jax.set_mesh(mesh):
            c2 = jax.jit(fn, in_shardings=(ps, ts, cs), donate_argnums=(2,)).lower(
                ap, tok, cache).compile()
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_dp_train_step_numerics():
    """shard_map DP training with int8 error-feedback compression tracks the
    uncompressed path."""
    out = _run_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke_config
        from repro.models.registry import get_model
        from repro.models.layers import init_params
        from repro.train.dp_compressed import make_dp_train_step, init_error_feedback
        from repro.train.optimizer import AdamWConfig, adamw_init

        mesh = make_mesh((8,), ("data",))
        cfg = get_smoke_config("granite_3_8b")
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
        model = get_model(cfg)
        params = init_params(jax.random.key(0), model.param_defs())
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, size=(16, 16)).astype(np.int32)
        batch = {"tokens": jnp.asarray(tokens),
                 "labels": jnp.asarray(np.roll(tokens, -1, 1))}
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1)
        s_c = make_dp_train_step(model, mesh, opt_cfg, compress=True)
        s_u = make_dp_train_step(model, mesh, opt_cfg, compress=False)
        # independent copies: the steps donate their inputs
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
        pc, oc, e = copy(params), adamw_init(copy(params)), init_error_feedback(params)
        pu, ou = copy(params), adamw_init(copy(params))
        for _ in range(3):
            pc, oc, e, mc = s_c(pc, oc, e, batch)
            pu, ou, _, mu = s_u(pu, ou, init_error_feedback(params), batch)
        # same loss trajectory within quantization noise
        assert abs(float(mc["loss"]) - float(mu["loss"])) < 0.05, (mc["loss"], mu["loss"])
        l1 = jax.tree_util.tree_leaves(pc)[3]; l2 = jax.tree_util.tree_leaves(pu)[3]
        diff = float(jnp.max(jnp.abs(l1 - l2)))
        # Adam normalizes step sizes to ~lr, so after 3 steps the compressed
        # trajectory may deviate by a few lr's worth of quantization noise;
        # error feedback bounds it (it does not grow with steps — see the
        # accumulation test in test_checkpoint_and_train).
        assert diff < 3 * 3 * 1e-3, diff
        print("OK")
    """)
    assert "OK" in out


def test_elastic_remesh_and_restore(tmp_path):
    """Checkpoint on a 2×4 mesh, lose half the fleet, restore onto 1×4 —
    values identical, shardings valid on the new mesh."""
    out = _run_fake_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.checkpoint import save_checkpoint
        from repro.configs import get_smoke_config
        from repro.distributed.elastic import build_mesh, remesh_plan, reshard_restore
        from repro.distributed.sharding import ShardingConfig, build_param_specs
        from repro.models.layers import abstract_params, init_params, logical_specs
        from repro.models.registry import get_model

        cfg = get_smoke_config("qwen3_8b")
        model = get_model(cfg)
        defs = model.param_defs()
        params = init_params(jax.random.key(0), defs)
        mesh1 = make_mesh((4, 2), ("data", "model"))
        save_checkpoint(r"{tmp_path}", 7, params)

        plan = remesh_plan((4, 2), ("data", "model"), n_healthy=4)
        assert plan.new_shape == (2, 2), plan
        mesh2 = build_mesh(plan)
        ap = abstract_params(defs)
        la = logical_specs(defs)
        restored, meta = reshard_restore(r"{tmp_path}", ap, la, mesh2)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            assert len(b.sharding.mesh.axis_names) == 2
        print("OK")
    """)
    assert "OK" in out


def test_remesh_plan_preserves_model_axis():
    from repro.distributed.elastic import remesh_plan

    plan = remesh_plan((2, 16, 16), ("pod", "data", "model"), n_healthy=300)
    assert plan.new_shape[2] == 16                      # TP width preserved
    import numpy as np

    assert np.prod(plan.new_shape) <= 300
    assert np.prod(plan.new_shape) == 256               # largest pow2 fit
    with pytest.raises(ValueError):
        remesh_plan((2, 16, 16), ("pod", "data", "model"), n_healthy=8)
