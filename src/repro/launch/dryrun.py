"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * lower jit(train_step | serve_step) over ShapeDtypeStruct stand-ins
    (no allocation),
  * compile; print memory_analysis() (proves it fits) and cost_analysis(),
  * parse collective traffic from the optimized HLO,
  * write the JSON artifact that EXPERIMENTS.md Sec Roofline reads.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all            # every applicable cell
Variants (hillclimbing levers): --no-fsdp --sp --cache-dtype int8
  --capacity-factor F --moe-groups N --no-remat --variant NAME

The dry-run is a host-device rehearsal: ``main`` pins JAX to the CPU with
512 forced host devices before JAX initializes a backend, and every cell
child inherits that environment, so no process of it touches an
accelerator.
"""
import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time


def run_cell(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import SHAPES, get_config
    from repro.configs.base import applicable_shapes
    from repro.launch import specs as SP
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import roofline_terms
    from repro.roofline.hlo import analyze
    from repro.train.step import (ParallelConfig, make_prefill_step,
                                  make_serve_step, make_train_step)

    cfg = get_config(args.arch)
    if args.capacity_factor:
        cfg = dataclasses.replace(cfg, capacity_factor=args.capacity_factor)
    if args.no_remat:
        cfg = dataclasses.replace(cfg, remat=False)
    if args.attn_block:
        cfg = dataclasses.replace(cfg, attn_block=args.attn_block)
    if args.scan_chunk:
        cfg = dataclasses.replace(cfg, scan_chunk=args.scan_chunk)
    shape = SHAPES[args.shape]
    if args.shape not in applicable_shapes(cfg):
        return {"arch": args.arch, "shape": args.shape, "status": "skipped",
                "reason": "long_500k needs sub-quadratic attention "
                          "(DESIGN.md Sec. 4)"}

    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    n_chips = mesh.devices.size
    pcfg = ParallelConfig(fsdp=not args.no_fsdp,
                          tensor_parallel=not args.no_tp,
                          sequence_parallel=args.sp,
                          grad_compress=args.grad_compress,
                          moe_groups=args.moe_groups)
    cache_dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8,
                   "f32": jnp.float32}[args.cache_dtype]

    t0 = time.time()
    if shape.kind == "train":
        state_shapes = SP.state_specs(cfg, pcfg, param_dtype=jnp.bfloat16)
        batch_shapes = SP.input_specs(cfg, shape)
        _, compile_step, _ = make_train_step(cfg, mesh, pcfg)
        jitted = compile_step(state_shapes, batch_shapes)
        lowered = jitted.lower(state_shapes, batch_shapes)
    elif shape.kind == "prefill":
        param_shapes = SP.param_specs(cfg, param_dtype=jnp.bfloat16)
        cache_shapes = SP.cache_specs(cfg, shape, cache_dtype=cache_dtype)
        batch = SP.input_specs(cfg, shape)
        _, compile_step = make_prefill_step(cfg, mesh, pcfg)
        jitted = compile_step(param_shapes, cache_shapes, batch)
        lowered = jitted.lower(param_shapes, cache_shapes, batch)
    else:
        param_shapes = SP.param_specs(cfg, param_dtype=jnp.bfloat16)
        cache_shapes = SP.cache_specs(cfg, shape, cache_dtype=cache_dtype)
        inp = SP.input_specs(cfg, shape)
        _, compile_step = make_serve_step(cfg, mesh, pcfg)
        jitted = compile_step(param_shapes, cache_shapes, inp["tokens"])
        lowered = jitted.lower(param_shapes, cache_shapes, inp["tokens"],
                               inp["pos"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:                                   # noqa: BLE001
        mem_info = {"error": str(e)}

    t0 = time.time()
    hlo = compiled.as_text()
    hc = analyze(hlo)
    t_analyze = time.time() - t0
    terms = roofline_terms(hc, n_chips, cfg, shape)

    art = {
        "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
        "variant": args.variant, "status": "ok", "n_chips": n_chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "analyze_s": round(t_analyze, 1),
        "cost_analysis_raw": {k: cost.get(k) for k in
                              ("flops", "bytes accessed", "transcendentals")},
        "hlo_cost": {k: v for k, v in hc.items() if k != "collectives"},
        "memory": mem_info,
        "collectives": hc["collectives"],
        "roofline": terms,
        "parallel": dataclasses.asdict(pcfg),
        "cache_dtype": args.cache_dtype,
    }
    return art


def _parser():
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--mesh", choices=["single", "multi"], default="single")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out-dir", default="experiments/dryrun")
    p.add_argument("--variant", default="baseline")
    p.add_argument("--no-fsdp", action="store_true")
    p.add_argument("--no-tp", action="store_true")
    p.add_argument("--sp", action="store_true")
    p.add_argument("--grad-compress", action="store_true")
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--moe-groups", type=int, default=0)
    p.add_argument("--capacity-factor", type=float, default=0.0)
    p.add_argument("--attn-block", type=int, default=0)
    p.add_argument("--scan-chunk", type=int, default=0)
    p.add_argument("--cache-dtype", default="bf16",
                   choices=["bf16", "int8", "f32"])
    p.add_argument("--timeout", type=int, default=3000)
    return p


def main() -> None:
    # MUST precede JAX's backend initialization: the device count and the
    # platform lock at first use.  Children inherit both.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    args = _parser().parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    if args.all:
        # one subprocess per cell: isolates compiles, survives hangs
        from repro.configs import ARCH_NAMES, get_config
        from repro.configs.base import applicable_shapes
        cells = [(a, s, m)
                 for a in ARCH_NAMES
                 for s in applicable_shapes(get_config(a))
                 for m in ("single", "multi")]
        for a, s, m in cells:
            out = os.path.join(args.out_dir, f"{a}_{s}_{m}_{args.variant}.json")
            if os.path.exists(out):
                print(f"[skip] {out}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m,
                   "--out-dir", args.out_dir, "--variant", args.variant]
            print(f"[run ] {a} {s} {m}", flush=True)
            r = subprocess.run(cmd, timeout=args.timeout)
            if r.returncode != 0:
                with open(out, "w") as f:
                    json.dump({"arch": a, "shape": s, "mesh": m,
                               "variant": args.variant, "status": "failed",
                               "returncode": r.returncode}, f,
                              allow_nan=False)
        return

    art = run_cell(args)
    name = f"{args.arch}_{args.shape}_{args.mesh}_{args.variant}.json"
    path = os.path.join(args.out_dir, name)
    with open(path, "w") as f:
        json.dump(art, f, indent=1, allow_nan=False)
    print(json.dumps({k: art[k] for k in
                      ("arch", "shape", "mesh", "status") if k in art},
                     allow_nan=False))
    if art.get("status") == "ok":
        print("memory:", art["memory"])
        print("hlo flops=%.3e bytes=%.3e link_bytes=%.3e" % (
            art["hlo_cost"]["flops"], art["hlo_cost"]["bytes"],
            art["hlo_cost"]["link_bytes_total"]))
        r = art["roofline"]
        print("roofline: compute=%.4fs memory=%.4fs collective=%.4fs "
              "dominant=%s useful=%.3f frac=%.3f" % (
                  r["compute_s"], r["memory_s"], r["collective_s"],
                  r["dominant"], r["useful_flops_ratio"],
                  r["roofline_fraction"]))


if __name__ == "__main__":
    main()
