import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Hillclimb helper: compile one (arch x shape) cell (optionally unrolled
1/2-layer variant) and print the largest collectives + cost summary."""
import argparse
import dataclasses
import re
from collections import defaultdict

import jax

from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.distributed.context import mesh_context
from repro.launch.dryrun import (_make_unrolled_step, _unrolled_cfg,
                                 analyze_compiled, build_shardings,
                                 compile_cell, production_cfg)
from repro.roofline.hlo import _GROUPS_IOTA_RE, _SHAPE_RE, _shape_bytes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--units", type=int, default=0,
                    help="0 = full scan model; N = unrolled N units")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--mesh", default="pod1")
    args = ap.parse_args()

    mesh = mesh_lib.make_production_mesh(multi_pod=args.mesh == "pod2")
    cell = steps_lib.get_cell(args.arch, args.shape)
    if args.units:
        cfg_u = production_cfg(_unrolled_cfg(cell.cfg, args.units), mesh,
                               cell.step_kind)
        cell = dataclasses.replace(cell, cfg=cfg_u)
        step = _make_unrolled_step(cell, remat=True)
        specs = steps_lib.input_specs(cell)
        in_sh, out_sh = build_shardings(cell, specs, mesh)
        with mesh_context(mesh), mesh:
            compiled = jax.jit(step, in_shardings=in_sh,
                               out_shardings=out_sh).lower(
                                   *specs).compile()
    else:
        compiled, _, _ = compile_cell(cell, mesh)

    a = analyze_compiled(compiled)
    print("cost:", {k: f"{v:.3e}" for k, v in a["cost"].items()})
    print("coll:", {k: f"{v:.3e}" for k, v in
                    a["collectives"]["by_op"].items()})
    print("mem:", {k: f"{v / 1e9:.2f}GB" for k, v in a["memory"].items()})

    txt = compiled.as_text()
    rows = []
    for line in txt.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        for op in ("all-gather", "all-reduce", "reduce-scatter",
                   "all-to-all", "collective-permute"):
            if f"{op}(" in s or f"{op}-start(" in s:
                head = s.split("(")[0]
                b = sum(_shape_bytes(d, dims)
                        for d, dims in _SHAPE_RE.findall(head))
                rows.append((b, op, s[:220]))
                break
    rows.sort(reverse=True)
    print(f"\ntop {args.top} collectives by result bytes:")
    for b, op, s in rows[:args.top]:
        print(f"  {b / 1e9:8.3f}GB {op:18} {s[:160]}")


if __name__ == "__main__":
    main()
