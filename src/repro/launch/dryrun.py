import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 host-platform
placeholder devices.  (Smoke tests and benchmarks never import this
module, so they see the single real device.)

Per cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. jits the cell's step function with full input/output shardings,
  3. ``.lower().compile()`` — success proves the distribution config is
     coherent; ``memory_analysis()`` proves it fits,
  4. records cost_analysis + per-chip collective traffic (HLO parse),
  5. (single-pod only) compiles unrolled 1-layer and 2-layer variants to
     linearly extrapolate scan-hidden FLOPs/bytes/collectives — XLA's
     cost analysis counts while-loop bodies ONCE regardless of trip
     count, so the scanned full model under-reports by ~num_layers x.
     Layers are structurally identical, making c1 + (L-1)(c2-c1) exact.

Usage:
  python -m repro.launch.dryrun --arch granite_8b --shape train_4k \
      --mesh pod1 --out experiments/dryrun
  python -m repro.launch.dryrun --all          # every cell, both meshes
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.distributed import sharding as SH
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models.config import SHAPES
from repro.roofline.hlo import collective_bytes
import repro.configs as configs

REPO = Path(__file__).resolve().parents[3]
DEFAULT_OUT = REPO / "experiments" / "dryrun"


# --------------------------------------------------------------------- #
def build_shardings(cell, specs, mesh):
    """(in_shardings, out_shardings) trees for the cell's step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import optim

    rules = _rules(cell.step_kind)
    rep = NamedSharding(mesh, P())

    def batch_shardings(bspecs):
        out = {}
        for k, v in bspecs.items():
            if k == "positions3":               # (3, B, S)
                logical = (None, "batch", None)
            elif v.ndim == 1:
                logical = ("batch",)
            elif k in ("tokens", "targets"):
                logical = ("batch", "seq" if cell.step_kind == "train"
                           else None)
            else:                                # (B, S|P, d)
                logical = ("batch",) + (None,) * (v.ndim - 1)
            out[k] = NamedSharding(
                mesh, SH.spec_for(v.shape, logical, mesh, rules))
        return out

    p_sh = SH.param_shardings(specs[0], mesh, rules)
    if cell.step_kind == "train":
        o_sh = optim.AdamWState(step=rep, mu=p_sh, nu=p_sh, master=p_sh)
        b_sh = batch_shardings(specs[2])
        in_sh = (p_sh, o_sh, b_sh)
        out_sh = (p_sh, o_sh, rep)
    else:
        c_sh = SH.tree_shardings(specs[1], SH.cache_logical_axes(specs[1]),
                                 mesh, rules)
        b_sh = batch_shardings(specs[2])
        in_sh = (p_sh, c_sh, b_sh)
        B = specs[2]["tokens"].shape[0]
        V = cell.cfg.vocab_size
        logits_sh = NamedSharding(
            mesh, SH.spec_for((B, V), ("batch", "vocab"), mesh, rules))
        out_sh = (logits_sh, c_sh)
    return in_sh, out_sh


def _rules(step_kind):
    return SH.TRAIN_RULES if step_kind == "train" else SH.SERVE_RULES


def production_cfg(cfg, mesh, step_kind):
    """Per-mesh model-impl switches (hillclimb, EXPERIMENTS.md §Perf):
    query heads pad to the ways the mesh shards them; MoE uses the
    expert-local shard_map path on production meshes — the sort-based
    ragged path forces GSPMD to globalize every token."""
    cfg = dataclasses.replace(
        cfg, head_shards=SH.head_shards(mesh, _rules(step_kind)))
    if cfg.family == "moe":
        return dataclasses.replace(cfg, moe_impl="ep")
    return cfg


def compile_cell(cell, mesh, remat=True):
    """Returns (compiled, lowered, stats dict)."""
    from repro.distributed.context import mesh_context
    cell = dataclasses.replace(
        cell, cfg=production_cfg(cell.cfg, mesh, cell.step_kind))
    step = steps_lib.make_step(cell, remat=remat)
    specs = steps_lib.input_specs(cell)
    in_sh, out_sh = build_shardings(cell, specs, mesh)
    # Serve steps donate the KV cache so the updated cache aliases the
    # input buffers (no copy of multi-GB caches per decode step).
    donate = (1,) if cell.step_kind != "train" else ()
    t0 = time.perf_counter()
    with mesh_context(mesh), mesh:
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*specs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    stats = {"lower_s": round(t1 - t0, 2),
             "compile_s": round(t2 - t1, 2)}
    return compiled, lowered, stats


def analyze_compiled(compiled):
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll_total, coll_by_op, coll_counts = collective_bytes(txt)
    return {
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        },
        "cost": {
            "flops": ca.get("flops", 0.0),
            "bytes": ca.get("bytes accessed", 0.0),
        },
        "collectives": {
            "per_chip_bytes": coll_total,
            "by_op": coll_by_op,
            "counts": coll_counts,
        },
    }


def _unrolled_cfg(cfg, units: int):
    """Config with ``units`` structural layer units, scan disabled."""
    if cfg.family == "hybrid":
        return dataclasses.replace(
            cfg, num_layers=units * cfg.hybrid_attn_every)
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, num_layers=units,
                                   encoder_layers=units)
    return dataclasses.replace(cfg, num_layers=units)


def _layer_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid_attn_every
    return cfg.num_layers


def extrapolate_roofline(cell, mesh, remat=True):
    """Compile unrolled 1-unit and 2-unit variants; extrapolate."""
    import repro.launch.steps as S

    def one(units):
        from repro.distributed.context import mesh_context
        cfg_u = production_cfg(_unrolled_cfg(cell.cfg, units), mesh,
                               cell.step_kind)
        cell_u = dataclasses.replace(cell, cfg=cfg_u)
        step = _make_unrolled_step(cell_u, remat)
        specs = S.input_specs(cell_u)
        in_sh, out_sh = build_shardings(cell_u, specs, mesh)
        with mesh_context(mesh), mesh:
            compiled = jax.jit(step, in_shardings=in_sh,
                               out_shardings=out_sh).lower(*specs).compile()
        a = analyze_compiled(compiled)
        return (a["cost"]["flops"], a["cost"]["bytes"],
                a["collectives"]["per_chip_bytes"],
                a["collectives"]["by_op"])

    f1, b1, c1, ops1 = one(1)
    f2, b2, c2, ops2 = one(2)
    L = _layer_units(cell.cfg)
    ops = {k: ops1.get(k, 0.0) + (L - 1) * (ops2.get(k, 0.0) -
                                            ops1.get(k, 0.0))
           for k in set(ops1) | set(ops2)}
    return {
        "flops": f1 + (L - 1) * (f2 - f1),
        "bytes": b1 + (L - 1) * (b2 - b1),
        "collective_per_chip_bytes": c1 + (L - 1) * (c2 - c1),
        "collective_by_op": ops,
        "layer_units": L,
        "unit1": {"flops": f1, "bytes": b1, "coll": c1},
        "unit2": {"flops": f2, "bytes": b2, "coll": c2},
    }


def _make_unrolled_step(cell, remat):
    from repro.models import model as M
    from repro.train import optim
    cfg = cell.cfg
    if cell.step_kind == "train":
        ocfg = optim.AdamWConfig()

        def train_step(params, opt_state, batch):
            def lf(p):
                kw = {}
                if cfg.family == "vlm":
                    kw["patch_embeds"] = batch["patch_embeds"]
                    kw["positions3"] = batch["positions3"]
                if cfg.family == "encdec":
                    kw["enc_embeds"] = batch["enc_embeds"]
                return M.loss_fn(p, cfg, batch["tokens"],
                                 batch["targets"], remat=remat,
                                 scan_layers=False, **kw)
            loss, grads = jax.value_and_grad(lf)(params)
            p2, o2 = optim.apply(ocfg, grads, opt_state, params)
            return p2, o2, loss
        return train_step

    if cell.step_kind == "prefill":
        def prefill_step(params, cache, batch):
            kw = {}
            if cfg.family == "vlm":
                kw["patch_embeds"] = batch["patch_embeds"]
                kw["positions3"] = batch["positions3"]
            if cfg.family == "encdec":
                kw["enc_embeds"] = batch["enc_embeds"]
            return M.prefill(params, cfg, batch["tokens"], cache,
                             scan_layers=False, **kw)
        return prefill_step

    def decode_step(params, cache, batch):
        kw = {}
        if cfg.family == "vlm":
            kw["positions3"] = batch["positions3"]
        return M.decode_step(params, cfg, batch["tokens"], cache,
                             batch["pos"], scan_layers=False, **kw)
    return decode_step


# --------------------------------------------------------------------- #
def run_cell(arch: str, shape: str, mesh_name: str,
             with_extrapolation: bool = True, remat: bool = True) -> dict:
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "ok": False}
    defined, reason = steps_lib.cell_is_defined(arch, shape)
    if not defined:
        rec.update(skipped=True, skip_reason=reason)
        return rec
    multi = mesh_name == "pod2"
    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
    cell = steps_lib.get_cell(arch, shape)
    compiled, lowered, stats = compile_cell(cell, mesh, remat=remat)
    rec.update(stats)
    rec.update(analyze_compiled(compiled))
    rec["devices"] = int(len(mesh.devices.flatten()))
    rec["ok"] = True
    if with_extrapolation and not multi:
        rec["extrapolated"] = extrapolate_roofline(cell, mesh,
                                                   remat=remat)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod1", "pod2"], default="pod1")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", default=",".join(configs.ASSIGNED),
                    help="comma list for --all")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        # one subprocess per cell: isolates jit caches and failures
        cells = [(a, s, m)
                 for a in args.archs.split(",")
                 for s in SHAPES
                 for m in ("pod1", "pod2")]
        failures = 0
        for a, s, m in cells:
            outfile = out_dir / f"{a}.{s}.{m}.json"
            if outfile.exists():
                print(f"[skip existing] {outfile.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m,
                   "--out", str(out_dir)]
            if args.no_extrapolate:
                cmd.append("--no-extrapolate")
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            dt = time.time() - t0
            ok = r.returncode == 0
            failures += 0 if ok else 1
            print(f"[{'ok' if ok else 'FAIL'}] {a} {s} {m} ({dt:.0f}s)")
            if not ok:
                (out_dir / f"{a}.{s}.{m}.err").write_text(
                    r.stdout + "\n" + r.stderr)
        return 1 if failures else 0

    assert args.arch and args.shape
    rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh}
    try:
        rec = run_cell(args.arch, args.shape, args.mesh,
                       with_extrapolation=not args.no_extrapolate,
                       remat=not args.no_remat)
    except Exception:
        rec["ok"] = False
        rec["error"] = traceback.format_exc()
    outfile = Path(args.out) / \
        f"{args.arch}.{args.shape}.{args.mesh}.json"
    outfile.write_text(json.dumps(rec, indent=1))
    if rec.get("ok") or rec.get("skipped"):
        status = "SKIP" if rec.get("skipped") else "OK"
        print(f"[{status}] {args.arch} {args.shape} {args.mesh} "
              f"compile={rec.get('compile_s')}s "
              f"coll={rec.get('collectives', {}).get('per_chip_bytes', 0) / 1e6:.1f}MB")
        return 0
    print(rec.get("error", "unknown failure"))
    return 1


if __name__ == "__main__":
    sys.exit(main())
