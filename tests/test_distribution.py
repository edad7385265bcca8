"""Distribution tests: sharding rules, HLO cost model, collective-bytes
parsing, plus multi-device (forced host devices) subprocess tests for
mesh-agnostic checkpointing, overlap matmuls and compressed reductions.

Multi-device cases run in a subprocess because jax locks the device count
at first init (the same reason dryrun.py sets XLA_FLAGS first)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import HloCostModel, _shape_bytes, _shape_dims
from repro.launch.mesh import make_custom_mesh
from repro.sharding.specs import default_rules, resolve

jax.config.update("jax_platform_name", "cpu")

REPO = Path(__file__).resolve().parent.parent


def run_subprocess(code: str, devices: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": "/tmp"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# ---------------------------------------------------------------- rules
class TestShardingRules:
    def _mesh(self):
        return make_custom_mesh((1, 1), ("data", "model"))

    def test_resolve_drops_nondivisible(self):
        mesh = make_custom_mesh((1, 1), ("data", "model"))
        rules = default_rules()
        # 1-sized mesh axes divide everything -> axes assigned
        spec = resolve(("vocab", "embed"), (50304, 2560), rules, mesh)
        assert spec == jax.sharding.PartitionSpec("model", "data")

    def test_resolve_skips_missing_axes(self):
        mesh = make_custom_mesh((1, 1), ("data", "model"))
        rules = default_rules()  # fsdp = ("pod", "data"); no pod on this mesh
        spec = resolve(("embed",), (128,), rules, mesh)
        assert spec == jax.sharding.PartitionSpec("data")

    def test_resolve_no_axis_reuse(self):
        mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
        rules = {"a": ("model",), "b": ("model",)}
        spec = resolve(("a", "b"), (4, 4), rules, mesh)
        # second use of "model" must be dropped
        assert spec == jax.sharding.PartitionSpec("model", None)

    def test_long_context_rules(self):
        rules = default_rules(long_context=True)
        assert rules["batch"] == ()
        assert rules["cache_seq"] == ("data",)


# ---------------------------------------------------------------- hlo cost
class TestHloCost:
    def test_shape_parsing(self):
        assert _shape_bytes("f32[16,128]{1,0}") == 16 * 128 * 4
        assert _shape_bytes("(bf16[8,8], s32[4])") == 8 * 8 * 2 + 16
        assert _shape_dims("f32[3,5,7]") == [3, 5, 7]

    def test_trip_count_multiplier(self):
        hlo = textwrap.dedent("""\
        HloModule m

        %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
          %p = (s32[], f32[8,8]) parameter(0)
          %a = f32[8,8]{1,0} get-tuple-element(%p), index=1
          %d = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %c = s32[] constant(1)
          %i = s32[] get-tuple-element(%p), index=0
          ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
        }

        %cond (p2: (s32[], f32[8,8])) -> pred[] {
          %p2 = (s32[], f32[8,8]) parameter(0)
          %i2 = s32[] get-tuple-element(%p2), index=0
          %n = s32[] constant(5)
          ROOT %lt = pred[] compare(%i2, %n), direction=LT
        }

        ENTRY %main (x: f32[8,8]) -> f32[8,8] {
          %x = f32[8,8]{1,0} parameter(0)
          %z = s32[] constant(0)
          %tup = (s32[], f32[8,8]) tuple(%z, %x)
          %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
          ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
        }
        """)
        cost = HloCostModel(hlo, 1).entry_cost()
        assert cost.flops == 5 * 2 * 8 * 8 * 8  # dot x trip count

    def test_collective_wire_bytes(self):
        hlo = textwrap.dedent("""\
        HloModule m

        ENTRY %main (x: f32[16]) -> f32[16] {
          %x = f32[16]{0} parameter(0)
          ROOT %ar = f32[16]{0} all-reduce(%x), replica_groups=[1,4]<=[4], to_apply=%add
        }
        """)
        cost = HloCostModel(hlo, 4).entry_cost()
        assert cost.wire == pytest.approx(2 * 64 * 3 / 4)
        assert cost.coll_counts == {"all-reduce": 1}

    def test_real_compiled_module(self):
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), ()
            out, _ = jax.lax.scan(body, x, None, length=3)
            return out.sum()
        x = jnp.ones((32, 32))
        c = jax.jit(f).lower(x, x).compile()
        cost = HloCostModel(c.as_text(), 1).entry_cost()
        assert cost.flops == pytest.approx(3 * 2 * 32**3)


# ---------------------------------------------------------------- multi-device
class TestMultiDevice:
    def test_checkpoint_across_meshes(self, tmp_path):
        """Save on a (4,2) mesh, restore onto (2,4) and (8,1) — the elastic
        restore path (mesh-agnostic checkpoints)."""
        out = run_subprocess(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import ckpt
        tree = {{"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.arange(8.0)}}
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = {{"w": NamedSharding(mesh_a, P("data", "model")),
                "b": NamedSharding(mesh_a, P("model"))}}
        sharded = jax.tree.map(jax.device_put, tree, sh_a)
        ckpt.save(sharded, r"{tmp_path}", step=1)
        for shape, axes in [((2, 4), ("data", "model")), ((8, 1), ("data", "model"))]:
            mesh_b = make_mesh(shape, axes)
            sh_b = {{"w": NamedSharding(mesh_b, P("model", "data")),
                    "b": NamedSharding(mesh_b, P(None))}}
            restored, step = ckpt.restore(tree, r"{tmp_path}", shardings=sh_b)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(tree["w"]))
            np.testing.assert_array_equal(np.asarray(restored["b"]),
                                          np.asarray(tree["b"]))
        print("CKPT_OK")
        """)
        assert "CKPT_OK" in out

    def test_overlap_matmuls_correct(self):
        out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from repro.sharding.overlap import psum_matmul, ring_weight_gather_matmul
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 24)).astype(np.float32))
        want = np.asarray(x @ w)
        got1 = np.asarray(psum_matmul(x, w, mesh, "data"))
        np.testing.assert_allclose(got1, want, rtol=1e-4, atol=1e-4)
        got2 = np.asarray(ring_weight_gather_matmul(x, w, mesh, "data"))
        np.testing.assert_allclose(got2, want, rtol=1e-4, atol=1e-4)
        print("OVERLAP_OK")
        """)
        assert "OVERLAP_OK" in out

    def test_sparse_psum_matches_dense(self):
        out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.sharding.compression import compress_topk, decompress
        mesh = make_mesh((8,), ("data",))

        def body():
            i = jax.lax.axis_index("data").astype(jnp.float32)
            g = jnp.zeros((32,)).at[(jax.lax.axis_index("data") * 3) % 32].set(1.0 + i)
            c = compress_topk(g, k=4)
            all_i = jax.lax.all_gather(c.indices, "data").reshape(-1)
            all_v = jax.lax.all_gather(c.values, "data").reshape(-1)
            dense = jnp.zeros((32,)).at[all_i].add(all_v) / 8
            ref = jax.lax.pmean(g, "data")
            return jnp.abs(dense - ref).max()

        diff = jax.shard_map(body, mesh=mesh, in_specs=(), out_specs=P(),
                             check_vma=False)()
        assert float(diff.max()) < 1e-6, float(diff.max())
        print("SPARSE_OK")
        """)
        assert "SPARSE_OK" in out

    def test_mini_dryrun_16dev(self):
        """A reduced arch through the real dry-run path on a 4x4 mesh:
        lower + compile + roofline extraction all work end to end."""
        out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from repro.configs import ARCHS
        from repro.configs.base import ShapeConfig
        from repro.launch import roofline as rl
        from repro.models.registry import build_model
        from repro.sharding.specs import default_rules, tree_shardings, set_constraint_mesh
        from repro.train import optimizer as opt

        mesh = make_mesh((4, 4), ("data", "model"))
        model = build_model(ARCHS["stablelm-3b"].SMOKE)
        shape = ShapeConfig("mini", 256, 8, "train")
        rules = default_rules()
        set_constraint_mesh(mesh, rules)
        ocfg = opt.AdamWConfig()
        ap = model.abstract_params(jnp.float32)
        state = opt.abstract_state(ap, ocfg)
        st_ax = opt.state_logical_axes(model.logical_axes())
        st_sh = opt.TrainState(
            step=jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            params=tree_shardings(mesh, st_ax.params, state.params, rules),
            mu=tree_shardings(mesh, st_ax.mu, state.mu, rules),
            nu=tree_shardings(mesh, st_ax.nu, state.nu, rules))
        batch = model.input_specs(shape)
        b_sh = tree_shardings(mesh, model.input_axes(shape), batch, rules)

        def step(st, b):
            (l, m), g = jax.value_and_grad(lambda p: model.loss(p, b),
                                           has_aux=True)(st.params)
            return opt.adamw_update(st, g, ocfg), l

        fn = jax.jit(step, in_shardings=(st_sh, b_sh))
        with mesh:
            compiled = fn.lower(state, batch).compile()
            roof = rl.analyze(compiled, 16, device_kind=rl.V5E,
                              model_flops=1e9)
        assert roof.flops_per_device > 0 and roof.bytes_per_device > 0
        assert roof.bottleneck in ("compute", "memory", "collective")
        print("DRYRUN_OK", roof.bottleneck)
        """, devices=16)
        assert "DRYRUN_OK" in out


class TestPipelineAndQuantizedCollectives:
    def test_pipeline_matches_sequential(self):
        out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from repro.sharding.pipeline import pipeline_apply, bubble_fraction
        mesh = make_mesh((4,), ("pp",))
        rng = np.random.default_rng(0)
        n_stages, d = 4, 16
        ws = jnp.asarray(rng.normal(size=(n_stages, d, d)).astype(np.float32) * 0.3)

        def stage(p, x):
            return jnp.tanh(x @ p["w"])

        x = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32))
        want = x
        for s in range(n_stages):
            want = jnp.tanh(want @ ws[s])
        got = pipeline_apply(stage, {"w": ws}, x, mesh=mesh, axis="pp",
                             n_microbatches=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(4, 4) - 3/7) < 1e-9
        print("PIPELINE_OK")
        """, devices=4)
        assert "PIPELINE_OK" in out

    def test_quantized_pmean_unbiased(self):
        out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.sharding.compression import quantized_pmean
        mesh = make_mesh((8,), ("data",))

        def body(seed):
            g = jax.random.normal(jax.random.fold_in(
                jax.random.PRNGKey(0), jax.lax.axis_index("data")), (64,))
            ref = jax.lax.pmean(g, "data")
            got = quantized_pmean(g, jax.random.fold_in(seed, jax.lax.axis_index("data")), "data")
            return jnp.abs(got - ref).max() / jnp.abs(ref).max()

        errs = []
        for s in range(5):
            e = jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False)(
                jax.random.PRNGKey(s))
            errs.append(float(e.max()))
        assert np.mean(errs) < 0.2, errs   # int8 noise, not bias
        print("QPMEAN_OK", [round(e, 3) for e in errs])
        """, devices=8)
        assert "QPMEAN_OK" in out

    def test_sharded_batcher(self):
        out = run_subprocess("""
        import jax, numpy as np
        from repro.launch.mesh import make_custom_mesh as make_mesh
        from repro.data.synthetic import ShardedBatcher, TokenStream
        mesh = make_mesh((4, 2), ("data", "model"))
        b = ShardedBatcher(TokenStream(vocab=64, seed=0), 8, 16, mesh=mesh,
                           batch_axes=("data",))
        batch = b(step=3)
        tok = batch["tokens"]
        assert tok.shape == (8, 16)
        assert "data" in str(tok.sharding.spec)
        host = TokenStream(vocab=64, seed=0).batch(3, 8, 16)["tokens"]
        np.testing.assert_array_equal(np.asarray(tok), host)
        print("BATCHER_OK")
        """, devices=8)
        assert "BATCHER_OK" in out
