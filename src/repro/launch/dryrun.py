import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The first two lines above MUST run before any jax import — jax locks the
device count at first init. Do not set that flag globally (smoke tests and
benches must see 1 device).

Per cell this driver:
  1. builds the production mesh ((16,16) or (2,16,16)),
  2. builds ShapeDtypeStruct stand-ins (launch.specs.input_specs),
  3. builds shardings (runtime.sharding) with divisibility fallbacks,
  4. jit(...).lower(...).compile()  — failure = a sharding bug in this repo,
  5. records lowering and compile time, memory_analysis and XLA's
     cost_analysis into artifacts/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun                         # full sweep
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k --mesh multi
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_bundle
from repro.configs.shapes import ALL_SHAPES, SHAPES, shape_skip_reason
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.models.model import decode_step, prefill
from repro.runtime.sharding import (
    ShardingReport, batch_shardings, cache_shardings,
    make_activation_constraint, param_shardings, train_state_shardings,
)
from repro.runtime.train_loop import make_train_step

MESHES = {"single": dict(multi_pod=False), "multi": dict(multi_pod=True)}


def _mem_dict(ma) -> Dict[str, float]:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               overrides: Optional[Dict[str, Any]] = None):
    """Returns (lowered, compiled, context dict). Raises on failure."""
    bundle = get_bundle(arch)
    if overrides:
        import dataclasses
        overrides = dict(overrides)
        ssm_chunk = overrides.pop("ssm_chunk", None)
        model = bundle.model
        if ssm_chunk is not None:
            model = dataclasses.replace(
                model, ssm=dataclasses.replace(model.ssm, chunk=ssm_chunk))
        bundle = bundle.replace(
            model=model,
            mesh=dataclasses.replace(bundle.mesh, **overrides))
    cfg = bundle.model
    shape = SHAPES[shape_name]
    skip = shape_skip_reason(cfg, shape)
    if skip:
        return None, None, {"skip": skip}
    mesh = make_production_mesh(**MESHES[mesh_kind])
    n_chips = mesh.devices.size
    report = ShardingReport()
    cell = specs_mod.input_specs(cfg, bundle, shape)

    if shape.kind == "train":
        constrain = make_activation_constraint(
            mesh, bundle.mesh, shape.global_batch, shape.seq_len)
        step = make_train_step(cfg, bundle, constrain=constrain)
        st_sh = train_state_shardings(cfg, mesh, bundle.mesh, cell["state"],
                                      report)
        b_sh = batch_shardings(cfg, mesh, bundle.mesh, cell["batch"])
        lowered = jax.jit(step, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None),
                          donate_argnums=(0,)).lower(cell["state"],
                                                     cell["batch"])
    elif shape.kind == "prefill":
        max_len = specs_mod.decode_cache_len(cfg, shape)

        def prefill_step(params, batch):
            return prefill(params, batch.get("tokens"), cfg, max_len,
                           enc_feats=batch.get("enc_feats"),
                           input_embeds=batch.get("input_embeds"),
                           remat=bundle.mesh.remat)

        p_sh = param_shardings(cfg, mesh, bundle.mesh, report)
        b_sh = batch_shardings(cfg, mesh, bundle.mesh, cell["batch"])
        lowered = jax.jit(prefill_step, in_shardings=(p_sh, b_sh),
                          ).lower(cell["params"], cell["batch"])
    else:  # decode
        def serve_step(params, dstate, token, enc_out=None):
            logits, new_state = decode_step(params, dstate, token, cfg,
                                            enc_out=enc_out)
            return jnp.argmax(logits, -1).astype(jnp.int32), new_state

        p_sh = param_shardings(cfg, mesh, bundle.mesh, report)
        c_sh = cache_shardings(cfg, mesh, bundle.mesh, cell["dstate"],
                               shape.global_batch, report)
        tok_spec = batch_shardings(cfg, mesh, bundle.mesh,
                                   {"t": cell["token"]})["t"]
        args = [cell["params"], cell["dstate"], cell["token"]]
        in_sh = [p_sh, c_sh, tok_spec]
        if cell["enc_out"] is not None:
            args.append(cell["enc_out"])
            in_sh.append(batch_shardings(cfg, mesh, bundle.mesh,
                                         {"e": cell["enc_out"]})["e"])
        lowered = jax.jit(serve_step, in_shardings=tuple(in_sh),
                          donate_argnums=(1,)).lower(*args)

    ctx = {"bundle": bundle, "cfg": cfg, "shape": shape, "mesh": mesh,
           "n_chips": n_chips, "fallbacks": report.fallbacks}
    return lowered, ctx


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides: Optional[Dict[str, Any]] = None,
             tag: str = "") -> Dict[str, Any]:
    # real lowering/compile wall time for the dry-run report — host
    # tooling measurement, not simulation state
    t0 = time.time()  # hemt-lint: disable=HL003
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "tag": tag}
    try:
        res = lower_cell(arch, shape_name, mesh_kind, overrides)
        if res[0] is None:
            rec["status"] = "skipped"
            rec["reason"] = res[-1]["skip"]
            return _write(rec, out_dir)
        lowered, ctx = res
        t_lower = time.time() - t0  # hemt-lint: disable=HL003  (compile timing)
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower  # hemt-lint: disable=HL003  (compile timing)

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        rec.update({
            "status": "ok",
            "n_chips": ctx["n_chips"],
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory_analysis": _mem_dict(ma),
            "xla_cost_analysis": {k: float(v) for k, v in ca.items()
                                  if isinstance(v, (int, float))},
            "sharding_fallbacks": ctx["fallbacks"],
        })
    except Exception as e:  # noqa: BLE001 — recorded, sweep continues
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return _write(rec, out_dir)


def _write(rec: Dict[str, Any], out_dir: str) -> Dict[str, Any]:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    line = f"{rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:7s} {status:8s}"
    if status == "ok":
        ma = rec["memory_analysis"]
        line += (f" compile={rec['compile_s']:6.1f}s"
                 f" args={ma.get('argument_size_in_bytes', 0) / 1e9:7.2f}GB"
                 f" temp={ma.get('temp_size_in_bytes', 0) / 1e9:7.2f}GB")
    elif status == "skipped":
        line += f" ({rec['reason'][:60]})"
    else:
        line += f" {rec['error'][:90]}"
    print(line, flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in ALL_SHAPES] + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else [s.name for s in ALL_SHAPES]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                rec = run_cell(arch, shape, mesh, args.out, tag=args.tag)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_err += rec["status"] == "error"
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
