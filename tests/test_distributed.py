"""Multi-device tests (subprocess with xla_force_host_platform_device_count):
sharded train step parity, pipeline (CH) parity, dry-run on a small mesh,
elastic checkpoint resharding."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_sub(body: str, ndev: int = 8, timeout: int = 900) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import sys
        sys.path.insert(0, {repr(os.path.join(ROOT, 'src'))})
        sys.path.insert(0, {repr(ROOT)})
        import jax

        def make_mesh(shape, names):
            # Auto axes: the flow places arrays through sharding
            # constraints, which Explicit axes (JAX's default) refuse
            return jax.make_mesh(
                shape, names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(names))
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout[-3000:] + "\n" + r.stderr[-3000:]
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.configs.base import FlowConfig, ShapeConfig
        from repro.core import lowering
        from repro.core.plan import build_plan
        from repro.distributed.sharding import ShardingRules
        cfg = get_smoke("llama3.2-1b")
        shape = ShapeConfig("s", "train", 16, 4)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = ShardingRules(mesh, dp=("data",))
        flow = FlowConfig(mode="folded", precision="fp32")
        plan_s = build_plan(cfg, flow, shape, mesh_axes=("data", "model"),
                            rules=rules)
        plan_1 = build_plan(cfg, flow, shape)
        params = lowering.init_params(plan_1, jax.random.key(0))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32),
                 "labels": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32)}
        l1, _ = lowering.make_loss_fn(plan_1)(params, batch)
        with mesh:
            psh = rules.params_shardings(plan_s)
            sp = jax.tree.map(jax.device_put, params, psh)
            sb = {k: jax.device_put(v, s) for (k, v), s in
                  zip(batch.items(), rules.batch_sharding(
                      {k: v for k, v in batch.items()}).values())}
            l2, _ = jax.jit(lowering.make_loss_fn(plan_s))(sp, sb)
        err = abs(float(l1) - float(l2)) / (abs(float(l1)) + 1e-9)
        assert err < 2e-5, (float(l1), float(l2))
        print("PARITY OK", float(l1), float(l2))
    """)
    assert "PARITY OK" in out


def test_pipeline_loss_matches_folded():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.configs.base import FlowConfig, ShapeConfig
        from repro.core import lowering
        from repro.core.plan import build_plan
        from repro.distributed.pipeline_parallel import make_pipeline_loss
        cfg = get_smoke("llama3.2-1b")   # 3 layers -> pad to 4 for 2 stages
        import dataclasses
        cfg = dataclasses.replace(cfg, n_layers=4)
        shape = ShapeConfig("s", "train", 16, 4)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        flow = FlowConfig(mode="folded", precision="fp32", remat="none",
                          pp_axis="pod",
                          mesh_split=(("pod", 2), ("data", 2), ("model", 2)))
        plan = build_plan(cfg, flow, shape, mesh_axes=tuple(mesh.axis_names))
        # the ShardingPass assigned the pipeline stages on the plan
        sp = plan.sharding
        assert sp is not None and sp.pp_axis == "pod" and sp.n_stages == 2
        assert sp.stage_of_layer == (0, 0, 1, 1), sp.stage_of_layer
        params = lowering.init_params(plan, jax.random.key(0))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32),
                 "labels": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32)}
        base, _ = lowering.make_loss_fn(plan)(params, batch)
        pipe_loss = make_pipeline_loss(plan, mesh, n_microbatches=2)
        with mesh:
            lp = jax.jit(pipe_loss)(params, batch)
        err = abs(float(base) - float(lp)) / (abs(float(base)) + 1e-9)
        assert err < 2e-4, (float(base), float(lp))
        # gradients flow through ppermute
        g = jax.jit(jax.grad(pipe_loss))(params, batch)
        gn = sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(g))
        assert gn > 0
        print("PIPE OK", float(base), float(lp), gn)
    """, ndev=8, timeout=1200)
    assert "PIPE OK" in out


def test_moe_shard_map_parity():
    """The manual shard_map MoE (EP + expert-TP) must match single-device CE
    exactly; only the aux load-balance term differs (per-shard means — the
    GShard semantics)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.configs.base import FlowConfig, ShapeConfig
        from repro.core import lowering
        from repro.core.plan import build_plan
        from repro.distributed.sharding import ShardingRules
        for arch in ("mixtral-8x7b", "deepseek-moe-16b"):
            cfg = get_smoke(arch)
            shape = ShapeConfig("s", "train", 16, 4)
            mesh = make_mesh((2, 4), ("data", "model"))
            rules = ShardingRules(mesh, dp=("data",))
            flow = FlowConfig(mode="folded", precision="fp32")
            plan_s = build_plan(cfg, flow, shape, mesh_axes=("data", "model"),
                                rules=rules)
            plan_1 = build_plan(cfg, flow, shape)
            params = lowering.init_params(plan_1, jax.random.key(0))
            rng = np.random.RandomState(0)
            batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32),
                     "labels": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32)}
            _, m1 = lowering.make_loss_fn(plan_1)(params, batch)
            with mesh:
                psh = rules.params_shardings(plan_s)
                sp = jax.tree.map(jax.device_put, params, psh)
                _, m2 = jax.jit(lowering.make_loss_fn(plan_s))(sp, batch)
            err = abs(float(m1["loss"]) - float(m2["loss"]))
            err /= abs(float(m1["loss"])) + 1e-9
            assert err < 1e-5, (arch, float(m1["loss"]), float(m2["loss"]))
        print("MOE PARITY OK")
    """, timeout=1200)
    assert "MOE PARITY OK" in out


def test_dryrun_cell_small_mesh():
    out = run_sub("""
        import jax
        from repro.launch.dryrun import run_cell
        mesh = make_mesh((2, 4), ("data", "model"))
        r = run_cell("llama3.2-1b", "decode_32k", mesh=mesh)
        assert r["memory"]["per_device_bytes"] > 0
        assert r["hlo"]["collective_bytes"] >= 0
        print("DRYRUN OK", r["compile_s"])
    """)
    assert "DRYRUN OK" in out


def test_elastic_checkpoint_reshard():
    """Save sharded on a (2,4) mesh, restore onto (4,2) — elastic scaling."""
    out = run_sub("""
        import jax, jax.numpy as jnp, tempfile, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt
        m1 = make_mesh((2, 4), ("data", "model"))
        m2 = make_mesh((4, 2), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(m1, P("data", "model")))
        d = tempfile.mkdtemp()
        ckpt.save(d, 1, {"x": xs})
        out = ckpt.restore(d, 1, {"x": xs},
                           {"x": NamedSharding(m2, P("model", "data"))})
        np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))
        assert out["x"].sharding.spec == P("model", "data")
        print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out


def test_compile_mesh_dict_acceptance():
    """ISSUE acceptance: compile(..., mesh={'data': 2, 'model': 2}) on 4
    forced host devices records the sharding decisions on the plan, and
    dse.explore over the same setup enumerates >= 2 distinct mesh
    factorizations and returns a candidate that compiles and runs."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import flow as rflow
        from repro.configs import get_smoke
        from repro.configs.base import ShapeConfig
        from repro.core import dse
        from repro.distributed.meshspec import MeshSpec
        cfg = get_smoke("llama3.2-1b")
        shape = ShapeConfig("s", "prefill", 16, 4)
        cm = rflow.compile(cfg, shape, mesh={"data": 2, "model": 2})
        d = cm.plan.describe()
        assert "sharding: mesh={data:2,model:2} dp=data:2 tp=model:2" in d, d
        assert cm.plan.sharding.param_specs
        params = cm.init_params(jax.random.key(0))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 16)), jnp.int32)}
        logits, _, _ = cm.prefill(params, batch)
        assert logits.shape[0] == 4

        # the DSE searches the factorizations of the 4 local devices...
        r = dse.explore(cfg, shape, devices=4,
                        validator=dse.compile_validator(cfg, shape))
        splits = {c.flow.mesh_split for c in r.candidates}
        assert len(splits) >= 2, splits
        assert r.best.flow.mesh_split is not None
        # ...and the winner compiles and runs on its own mesh
        best_cm = rflow.compile(cfg, shape, r.best.flow,
                                mesh=MeshSpec.of(r.best.flow.mesh_split))
        lg, _, _ = best_cm.prefill(best_cm.init_params(jax.random.key(0)),
                                   batch)
        assert bool(jnp.all(jnp.isfinite(lg.astype(jnp.float32))))
        print("MESH DSE OK", sorted(splits), r.best.flow.mesh_split)
    """, ndev=4, timeout=1200)
    assert "MESH DSE OK" in out


def test_measure_validation_on_mesh():
    """validate='measure': the DSE ranks top-k survivors by measured step
    time of the actual sharded executable."""
    out = run_sub("""
        import jax
        from repro import flow as rflow
        from repro.configs import get_smoke
        from repro.configs.base import ShapeConfig
        cfg = get_smoke("llama3.2-1b")
        shape = ShapeConfig("s", "prefill", 16, 4)
        cm = rflow.compile(cfg, shape, mesh={"data": 2, "model": 2},
                           autotune=True, validate="measure")
        er = cm.explore_result
        assert er is not None and er.validated
        assert all(v["measured_step_s"] > 0 for v in er.validated)
        assert cm.plan.sharding is not None
        print("MEASURE OK", len(er.validated))
    """, ndev=4, timeout=1200)
    assert "MEASURE OK" in out


def test_multipod_mesh_axes():
    out = run_sub("""
        from repro.launch.mesh import make_production_mesh
        # only 8 host devices: build the small analogue directly
        import jax
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert tuple(mesh.axis_names) == ("pod", "data", "model")
        print("MESH OK")
    """)
    assert "MESH OK" in out
