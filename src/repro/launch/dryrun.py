import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
ShapeDtypeStruct inputs (no allocation) and extract the roofline terms.

The two lines above MUST run before any other import (jax locks the device
count on first init).  Do not replicate this flag anywhere else — tests and
benchmarks must see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch <id> ...] [--shape <name> ...] [--multipod|--singlepod|--both]
        [--out experiments/dryrun] [--skip-done]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import ARCH_IDS, get_config  # noqa: E402
from ..distributed.context import use_mesh  # noqa: E402
from ..distributed.sharding import (batch_shardings, cache_shardings,  # noqa: E402
                                    param_shardings, replicated)
from ..models import Model  # noqa: E402
from ..training.step import (default_optimizer, make_serve_step,  # noqa: E402
                             make_prefill_step, make_train_step)
from .mesh import make_debug_mesh, make_production_mesh  # noqa: E402
from .specs import SHAPES, input_specs, param_specs, shape_applicable  # noqa: E402

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*(\([^)]*\)|\S+)\s")
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|c64)\[([\d,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "c64": 8}


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device operand bytes of every collective op in the HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    count = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\(?)([a-z0-9\[\],{}\s\-]*?)"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start|-done)?\(", s)
        if not m:
            continue
        kind = m.group(2)
        if "-done" in s.split("(")[0]:
            continue  # avoid double count of async pairs
        # operand bytes: shapes on the result side of the assignment
        lhs = s.split("=", 1)[1]
        shapes = SHAPE_RE.findall(lhs.split("(")[0])
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES.get(dt, 4)
        out[kind] += nbytes
        count[kind] += 1
    out["counts"] = count
    return out


def model_flops(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); decode counts one token/seq."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * n * tokens
    return 2.0 * n * shape.batch  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, mesh, multi_pod: bool, *,
             tiny: bool = False, shape=None, opt: bool = False) -> dict:
    from ..configs import get_tiny_config
    cfg = get_tiny_config(arch) if tiny else get_config(arch)
    if opt:
        # §Perf optimized configuration (beyond-paper; see EXPERIMENTS.md
        # §Perf): sequence-parallel residual stream + larger loss slabs.
        # (Sequence-sharding the decode cache was tried and REFUTED — the
        # SPMD select-based DUS doubles decode HBM traffic.)
        import dataclasses as _dc
        cfg = _dc.replace(cfg, seq_shard_activations=True, loss_chunk=8192)
    shape = shape or SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skip", "reason": why}
    model = Model(cfg)
    t0 = time.time()
    with use_mesh(mesh):
        specs = input_specs(cfg, shape)
        pspecs = param_specs(cfg)
        pshard = param_shardings(pspecs, mesh)
        bshard = batch_shardings(specs["batch"], mesh)

        def attach(tree, shardings):
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                tree, shardings)

        params_in = attach(pspecs, pshard)
        batch_in = attach(specs["batch"], bshard)

        if shape.kind == "train":
            opt = default_optimizer(cfg)
            ostate = jax.eval_shape(lambda p: opt.init(p), pspecs)
            oshard = param_shardings(ostate, mesh)  # same rules; scalars -> P()
            state_in = {"params": params_in, "opt": attach(ostate, oshard)}
            step = make_train_step(model, opt)
            lowered = jax.jit(step).lower(state_in, batch_in)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            lowered = jax.jit(step).lower(params_in, batch_in)
        else:
            cshard = cache_shardings(specs["cache"], mesh, seq_shard=False)
            cache_in = attach(specs["cache"], cshard)
            step = make_serve_step(model)
            # pin the output cache sharding to the input's — otherwise XLA
            # picks an unsharded layout for the scan's stacked cache output
            # and gathers/upcasts the whole cache every step (§Perf iter. 4)
            lowered = jax.jit(
                step, out_shardings=(None, cshard),
                donate_argnums=(1,),
            ).lower(params_in, cache_in, batch_in)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 - backend-dependent
            mem = None
        try:
            cost = compiled.cost_analysis() or {}
        except Exception:  # noqa: BLE001
            cost = {}
        if isinstance(cost, (list, tuple)):  # jax <= 0.4.x returns [dict]
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        # trip-count-aware HLO accounting (XLA's cost_analysis counts while
        # bodies once — wrong by ~num_layers for scanned models)
        from .hlo_costs import analyze
        acc = analyze(hlo)

    chips = math.prod(mesh.devices.shape)
    rec = {
        "arch": arch, "shape": shape.name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": acc["flops"],
        "bytes_per_device": acc["bytes"],
        "bytes_per_device_kernelized": acc["bytes_kernelized"],
        "flash_loop_bytes_per_device": acc["flash_loop_bytes"],
        "collective_bytes_per_device": acc["collective_bytes"],
        "collective_counts": acc["collective_counts"],
        "xla_flops_per_device_loopbody_once": cost.get("flops", -1.0),
        "xla_bytes_per_device_loopbody_once": cost.get("bytes accessed", -1.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "model_flops_global": model_flops(cfg, shape),
        "hlo_bytes": len(hlo),
    }
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=ARCH_IDS)
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--singlepod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced configs (pipeline validation only)")
    ap.add_argument("--opt", action="store_true",
                    help="§Perf optimized config (SP activations, "
                         "seq-sharded decode cache, bigger loss slabs)")
    ap.add_argument("--mesh-shape", default=None,
                    help="debug override, e.g. 2,2,2 (axes pod,data,model)")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args(argv)

    modes = []
    if args.both or (not args.multipod and not args.singlepod):
        modes = [False, True]
    else:
        if args.singlepod:
            modes.append(False)
        if args.multipod:
            modes.append(True)

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for multi in modes:
        if args.mesh_shape:
            dims = tuple(int(x) for x in args.mesh_shape.split(","))
            axes = (("pod", "data", "model") if len(dims) == 3
                    else ("data", "model"))
            mesh = make_debug_mesh(dims, axes)
            if multi:
                continue  # custom mesh: run once
        else:
            mesh = make_production_mesh(multi_pod=multi)
        print(f"=== mesh {'multi(2,16,16)' if multi else 'single(16,16)'} "
              f"axes={mesh.axis_names} devices={math.prod(mesh.devices.shape)}",
              flush=True)
        for arch in args.arch:
            for shape_name in args.shape:
                tag = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                path = outdir / f"{tag}.json"
                if args.skip_done and path.exists():
                    rec = json.loads(path.read_text())
                    if rec.get("status") in ("ok", "skip"):
                        print(f"[cached] {tag}", flush=True)
                        continue
                t0 = time.time()
                shape = SHAPES[shape_name]
                if args.seq or args.batch:
                    import dataclasses as _dc
                    shape = _dc.replace(shape, seq=args.seq or shape.seq,
                                        batch=args.batch or shape.batch)
                try:
                    rec = run_cell(arch, shape_name, mesh, multi,
                                   tiny=args.tiny, shape=shape, opt=args.opt)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                path.write_text(json.dumps(rec, indent=2, default=float))
                status = rec["status"]
                extra = (f"compile={rec.get('compile_s')}s "
                         f"flops/dev={rec.get('flops_per_device', 0):.3g}"
                         if status == "ok" else rec.get("reason",
                                                        rec.get("error", "")))
                print(f"[{status}] {tag} ({time.time()-t0:.0f}s) {extra}",
                      flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
