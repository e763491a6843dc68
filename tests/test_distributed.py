"""Multi-device tests (subprocess with host devices): sharded train step,
seq-sharded flash decode, compressed allreduce wire-savings, elastic restore,
mini dry-run of the production machinery at 8 devices."""
import pytest


def test_sharded_train_step_runs(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs.base import smoke_config, ShapeConfig, input_specs
        from repro.distributed import sharding as shd
        from repro.launch.policy import cell_policy
        from repro.models.model import Model
        from repro.optim import adamw
        from repro.train import step as steps

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4, 2), ("data", "model"))
        cfg = smoke_config("deepseek-67b")
        shape = ShapeConfig("t", 32, 8, "train")
        with shd.use_mesh(mesh):
            policy = cell_policy(cfg, shape, mesh)
            model = Model(cfg)
            params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                jax.tree.map(lambda s: NamedSharding(mesh, s), model.partition_specs()))
            opt_cfg = adamw.AdamWConfig()
            opt = adamw.init(params, opt_cfg)
            fn = jax.jit(steps.make_train_step(model, opt_cfg, policy))
            batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                     "labels": jnp.zeros((8, 32), jnp.int32)}
            p2, o2, metrics = fn(params, opt, batch)
            print("LOSS", float(metrics["loss"]))
        """)
    assert "LOSS" in out


def test_compressed_allreduce_saves_wire(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.grad_compress import make_compressed_allreduce
        from repro.launch.hlo_analysis import HloAnalysis
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((8,), ("data",))
        n = 1 << 20

        def plain(x):
            return jnp.mean(x, axis=0)
        xs = jax.ShapeDtypeStruct((8, n), jnp.float32)
        with mesh:
            sh = NamedSharding(mesh, P("data", None))
            c_plain = jax.jit(plain, in_shardings=(sh,),
                              out_shardings=NamedSharding(mesh, P())).lower(xs).compile()
            f = make_compressed_allreduce(mesh, "data", planes=6)
            c_comp = jax.jit(f, in_shardings=(sh,)).lower(xs).compile()
        wp = HloAnalysis(c_plain.as_text()).summary()["collective_wire_bytes_per_device"]
        wc = HloAnalysis(c_comp.as_text()).summary()["collective_wire_bytes_per_device"]
        print("PLAIN", wp, "COMP", wc)
        # correctness
        with mesh:
            x = jax.device_put(np.random.default_rng(0).normal(size=(8, n)).astype(np.float32), sh)
            out, _ = jax.jit(f)(x)
        err = np.abs(np.asarray(out)[0] - np.asarray(x).mean(0)).max()
        rng_scale = np.abs(np.asarray(x).mean(0)).max()
        print("ERR", err / rng_scale)
        assert err / rng_scale < 2**-6
        """)
    vals = {k: float(v) for k, v in zip(
        ["PLAIN", "COMP"], out.split("PLAIN ")[1].split("ERR")[0]
        .replace("COMP", "").split())}
    # compressed all-gather phase must move far fewer bytes than a plain
    # all-reduce (sign+6 planes of 31 bits + rs phase ~= 55% of 2x full)
    assert vals["COMP"] < 0.62 * vals["PLAIN"], vals


def test_elastic_restore_across_meshes(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs.base import smoke_config
        from repro.distributed import sharding as shd
        from repro.models.model import Model
        from repro.ckpt import manager as ck

        cfg = smoke_config("qwen2-7b")
        model = Model(cfg)
        # save under an 8-device (4,2) mesh
        from repro.launch.mesh import auto_mesh
        mesh_a = auto_mesh((4, 2), ("data", "model"))
        with shd.use_mesh(mesh_a):
            sh_a = jax.tree.map(lambda s: NamedSharding(mesh_a, s),
                                model.partition_specs())
            params = jax.device_put(model.init(jax.random.PRNGKey(0)), sh_a)
            ck.save("/tmp/elastic_ck", 1, params)
        # 'lose half the nodes': restore onto a 4-device (2,2) mesh
        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh_b = jax.sharding.Mesh(devs, ("data", "model"))
        with shd.use_mesh(mesh_b):
            sh_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s),
                                model.partition_specs())
            restored, _ = ck.load("/tmp/elastic_ck", 1, model.shape_structs(),
                                  shardings=sh_b)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC_OK", len(jax.tree.leaves(restored)))
        """)
    assert "ELASTIC_OK" in out


def test_mini_dryrun_all_step_kinds(subproc):
    """The full dry-run machinery at 8-device scale on two archs."""
    out = subproc("""
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs.base import smoke_config, input_specs, ShapeConfig
        from repro.distributed import sharding as shd
        from repro.launch.policy import cell_policy
        from repro.launch.hlo_analysis import HloAnalysis
        from repro.models.model import Model
        from repro.optim import adamw
        from repro.train import step as steps

        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch in ["jamba-v0.1-52b", "deepseek-v2-236b"]:
            cfg = smoke_config(arch)
            for kind, b, s in [("train", 8, 32), ("prefill", 4, 64),
                               ("decode", 8, 64)]:
                shape = ShapeConfig(kind, s, b, kind)
                with shd.use_mesh(mesh):
                    policy = cell_policy(cfg, shape, mesh)
                    model = Model(cfg)
                    pshape = model.shape_structs()
                    pshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                                          model.partition_specs())
                    bspecs = input_specs(cfg, shape)
                    bshard = steps.batch_shardings(bspecs, policy, mesh)
                    if kind == "train":
                        oc = adamw.AdamWConfig()
                        osh = jax.eval_shape(lambda p: adamw.init(p, oc), pshape)
                        ospecs = adamw.state_partition_specs(model.partition_specs())
                        oshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), ospecs,
                            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
                        fn = steps.make_train_step(model, oc, policy)
                        c = jax.jit(fn, in_shardings=(pshard, oshard, bshard),
                                    out_shardings=(pshard, oshard, None)).lower(
                            pshape, osh, bspecs).compile()
                    elif kind == "prefill":
                        fn = steps.make_prefill_step(model)
                        c = jax.jit(fn, in_shardings=(pshard, bshard)).lower(
                            pshape, bspecs).compile()
                    else:
                        cfg2 = dataclasses.replace(
                            cfg, seq_shard_decode=policy.seq_shard,
                            decode_batch_axes=tuple(policy.batch_axes))
                        model2 = Model(cfg2)
                        cache = model2.init_cache_structs(b, policy.cache_len)
                        cshard = steps.cache_shardings(cache, policy, mesh)
                        fn = steps.make_decode_step(model2)
                        c = jax.jit(fn, in_shardings=(pshard, cshard, None, bshard),
                                    out_shardings=(None, cshard)).lower(
                            pshape, cache, jax.ShapeDtypeStruct((), jnp.int32),
                            bspecs).compile()
                    a = HloAnalysis(c.as_text()).summary()
                    assert a["flops_per_device"] > 0, (arch, kind)
                    print("MINI_OK", arch, kind)
        """)
    assert out.count("MINI_OK") == 6
