import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           "--xla_allow_excess_precision=false "
                           + os.environ.get("XLA_FLAGS", ""))
# ^^ MUST precede any jax import: jax locks the device count on first init.
# excess_precision=false: XLA:CPU otherwise elides our f32->bf16->f32
# mixed-precision casts (it has no native bf16 dots); a TPU backend keeps
# bf16 natively, so the flag makes CPU dry-run accounting match the target.

"""Multi-pod dry-run driver (deliverable e) — docstring after the env-var
preamble on purpose; see the two lines above.

For one (arch x shape x mesh) cell:
  1. build the production mesh (16x16 or 2x16x16),
  2. build the model + abstract params/optimizer state/caches
     (ShapeDtypeStructs — nothing is allocated),
  3. jit the step function with explicit in/out shardings,
  4. ``.lower(...).compile()`` — success proves the distribution config is
     coherent (shardings consistent, collectives supported, memory sane),
  5. print ``memory_analysis()`` + ``cost_analysis()`` and write the
     roofline terms (launch/roofline.py) to a JSON cell file.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
      --shape train_4k --mesh single --out results/
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Exit code 0 = every requested cell compiled (or was a documented skip).
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path


def _build_step(model, shape, mesh, rules, opt_cfg, compute_dtype=None,
                naive_decode=False):
    """Returns (fn, example_args, in_shardings, out_shardings)."""
    import jax
    import jax.numpy as jnp

    from repro.sharding.specs import replicated, tree_shardings
    from repro.train import optimizer as opt

    cfg = model.cfg
    batch_specs = model.input_specs(shape)
    batch_axes = model.input_axes(shape)
    batch_shardings = tree_shardings(mesh, batch_axes, batch_specs, rules)
    if shape.phase == "train":
        # param_dtype bf16 = production mixed precision: bf16 weights &
        # grads (collectives halve), fp32 Adam moments (optimizer.py
        # upcasts the update math)
        abstract_params = model.abstract_params(
            jnp.bfloat16 if compute_dtype is not None else jnp.float32)
        state = opt.abstract_state(abstract_params, opt_cfg)
        state_axes = opt.state_logical_axes(model.logical_axes())
        state_shardings = opt.TrainState(
            step=replicated(mesh),
            params=tree_shardings(mesh, state_axes.params, state.params, rules),
            mu=tree_shardings(mesh, state_axes.mu, state.mu, rules),
            nu=tree_shardings(mesh, state_axes.nu, state.nu, rules))

        def train_step(st, batch):
            def loss_of(p):
                if compute_dtype is not None:  # mixed precision: bf16 compute,
                    p = jax.tree.map(            # fp32 master params + moments
                        lambda t: t.astype(compute_dtype)
                        if t.dtype == jnp.float32 and t.ndim > 1 else t, p)
                return model.loss(p, batch)

            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(st.params)
            new_state = opt.adamw_update(st, grads, opt_cfg)
            return new_state, (loss, metrics["ce"])

        fn = jax.jit(train_step,
                     in_shardings=(state_shardings, batch_shardings),
                     out_shardings=(state_shardings,
                                    (replicated(mesh), replicated(mesh))))
        return fn, (state, batch_specs)

    abstract_params = model.abstract_params(jnp.bfloat16)
    param_shardings = tree_shardings(mesh, model.logical_axes(), abstract_params, rules)
    max_seq = shape.seq_len
    if cfg.family == "vlm":
        max_seq += cfg.n_vision_tokens
    if shape.phase == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch, max_seq=max_seq)

        # let XLA choose cache/logit shardings; inputs pinned
        fn = jax.jit(prefill_step, in_shardings=(param_shardings, batch_shardings))
        return fn, (abstract_params, batch_specs)

    # decode
    cache, cache_axes = model.cache_structure(shape.global_batch, max_seq,
                                              abstract=True)
    cache_shardings = tree_shardings(mesh, cache_axes, cache, rules)

    def decode_fn(params, cache, batch):
        return model.decode(params, cache, batch)

    # donate the cache: the in-place dynamic-update-slice then aliases the
    # input buffer instead of copying ~GBs of KV per step
    donate = () if naive_decode else (1,)
    fn = jax.jit(decode_fn, donate_argnums=donate,
                 in_shardings=(param_shardings, cache_shardings, batch_shardings),
                 out_shardings=(None, cache_shardings))
    return fn, (abstract_params, cache, batch_specs)


def run_cell(arch_id: str, shape_name: str, mesh_kind: str, out_dir: Path,
             rules_override=None, opt_cfg=None, tag: str = "baseline",
             verbose: bool = True, save_hlo: bool = False,
             compute_dtype=None, moe_impl: str = "gather",
             mesh_override=None, naive_decode: bool = False) -> dict:
    import jax

    from repro.configs import ARCHS, SHAPES, skip_reason
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh
    from repro.models.registry import build_model
    from repro.sharding.specs import default_rules
    from repro.train.optimizer import AdamWConfig

    shape = SHAPES[shape_name]
    # the meshes are compiled on the host's virtual devices but stand for
    # v5e pods: their roofline terms divide by the v5e peaks
    target = rl.V5E
    cell = {"arch": arch_id, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
            "device_kind": target}
    reason = skip_reason(arch_id, shape_name)
    if reason:
        cell.update(status="skipped", reason=reason)
        return cell
    t0 = time.time()
    try:
        if mesh_override is not None:
            from repro.launch.mesh import make_custom_mesh
            mesh = make_custom_mesh(*mesh_override)
        else:
            mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        import dataclasses as _dc
        fcfg = ARCHS[arch_id].FULL
        if moe_impl != "gather" and fcfg.n_experts:
            fcfg = _dc.replace(fcfg, moe_impl=moe_impl)
        model = build_model(fcfg)
        long_ctx = shape_name == "long_500k"
        rules = rules_override or default_rules(phase=shape.phase,
                                                long_context=long_ctx)
        if naive_decode:  # pre-optimization serving layout (Perf baselines)
            rules = default_rules(phase="train", long_context=long_ctx)
        opt_cfg = opt_cfg or AdamWConfig()
        from repro.sharding.specs import set_constraint_mesh
        set_constraint_mesh(mesh, rules)
        fn, args = _build_step(model, shape, mesh, rules, opt_cfg,
                               compute_dtype=compute_dtype,
                               naive_decode=naive_decode)
        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            n_dev = mesh.devices.size
            n_active = _active_params(model)
            tokens = shape.global_batch * (shape.seq_len if shape.phase != "decode" else 1)
            mf = rl.model_flops_estimate(n_active, tokens, shape.phase)
            hlo_text = compiled.as_text()
            roof = rl.analyze(compiled, n_dev, device_kind=target,
                              model_flops=mf, hlo_text=hlo_text)
            if save_hlo:
                import gzip
                out_dir.mkdir(parents=True, exist_ok=True)
                with gzip.open(out_dir / f"{arch_id}__{shape_name}__{mesh_kind}__{tag}.hlo.txt.gz",
                               "wt") as fh:
                    fh.write(hlo_text)
        cell.update(status="ok", seconds_lower=round(t_lower, 1),
                    seconds_compile=round(t_compile, 1),
                    n_params=model.n_params(), n_params_active=n_active,
                    roofline=roof.to_dict())
        if verbose:
            print(f"[{arch_id} x {shape_name} x {mesh_kind}] OK "
                  f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
                  f"bottleneck={roof.bottleneck} "
                  f"t=(c {roof.t_compute*1e3:.1f} | m {roof.t_memory*1e3:.1f} "
                  f"| x {roof.t_collective*1e3:.1f}) ms")
            print("  memory_analysis:", (roof.memory_analysis or "")[:400])
    except Exception as e:
        cell.update(status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch_id} x {shape_name} x {mesh_kind}] FAILED: {e}")
    finally:
        from repro.sharding.specs import set_constraint_mesh
        set_constraint_mesh(None)
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch_id}__{shape_name}__{mesh_kind}__{tag}.json"
    (out_dir / fname).write_text(json.dumps(cell, indent=1))
    return cell


def _active_params(model) -> float:
    """Active parameter count (MoE: routed top-k + shared + non-expert)."""
    import math

    from repro.models.common import is_spec
    import jax

    cfg = model.cfg
    total = 0.0
    for path, spec in jax.tree_util.tree_flatten_with_path(
            model.specs, is_leaf=is_spec)[0]:
        n = math.prod(spec.shape)
        keys = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if "we_" in keys and cfg.n_experts:  # routed expert tensors
            n = n * cfg.top_k / cfg.n_experts
        total += n
    return total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run every (arch x shape)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--compute-dtype", choices=["f32", "bf16"], default="f32")
    ap.add_argument("--moe-impl", choices=["gather", "sharded"], default="gather")
    ap.add_argument("--naive-decode", action="store_true",
                    help="pre-optimization decode (no cache donation, FSDP "
                         "weight layout) — Perf baseline reproduction")
    ap.add_argument("--mesh-shape", default=None,
                    help="axis refactor of the same chip count, e.g. "
                         "'data=16,model=8,seq=2' (Perf experiments)")
    args = ap.parse_args(argv)

    from repro.configs import ARCHS, SHAPES

    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    out_dir = Path(args.out)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                import jax.numpy as _jnp
                cdt = _jnp.bfloat16 if args.compute_dtype == "bf16" else None
                mo = None
                if args.mesh_shape:
                    pairs = [kv.split("=") for kv in args.mesh_shape.split(",")]
                    mo = (tuple(int(v) for _, v in pairs),
                          tuple(k for k, _ in pairs))
                cell = run_cell(arch, shape, mesh_kind, out_dir, tag=args.tag,
                                save_hlo=args.save_hlo, compute_dtype=cdt,
                                moe_impl=args.moe_impl, mesh_override=mo,
                                naive_decode=args.naive_decode)
                failures += cell["status"] == "error"
    print(f"dry-run finished: {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
