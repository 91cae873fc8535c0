"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, from the compiled per-device SPMD module:
  * memory_analysis  — bytes/device (args, temps, peak): proves it fits;
  * cost_analysis    — per-device HLO FLOPs and HBM bytes;
  * collective bytes — regex over the post-scheduling HLO, summing operand
                       sizes of all-gather/all-reduce/reduce-scatter/
                       all-to-all/collective-permute, split ICI vs DCN
                       (a collective whose replica group crosses the 256-chip
                       pod boundary moves at DCN, not ICI, bandwidth);
  * roofline terms   — compute/memory/collective seconds + dominant term
                       (EXPERIMENTS.md §Roofline reads these JSONs).

Scan-body correction: XLA's cost_analysis does NOT multiply while-loop body
costs by the trip count, so each cell also compiles ONE superblock segment
under the same shardings and totals  full + (num_superblocks - 1) * segment
(DESIGN.md §7).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k \
      --mesh single --out results/dryrun
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse
import json
import os
import re
import time
import traceback

import jax
import numpy as np

from repro.configs.base import SHAPES, get_config, list_archs, supported_shapes
from repro.hw.specs import TPU_V5E
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_bundle, superblock_segment

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

# result-side instruction: "%name = <shapes> <op>(...), ..."
_COLL_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _shape_bytes(text: str) -> float:
    b = 0.0
    for sm in _SHAPE_RE.finditer(text):
        n = 1
        if sm.group(2):
            for d in sm.group(2).split(","):
                n *= int(d)
        b += n * _DTYPE_BYTES[sm.group(1)]
    return b


def _first_group(tail: str) -> list[int] | None:
    """Device ids of the LAST replica group (iota groups may be uniform but
    the later groups are the ones that cross pod boundaries first)."""
    gm = _GROUPS_LIST_RE.search(tail)
    if gm:
        return [int(x) for x in gm.group(1).split(",")]
    gm = _GROUPS_IOTA_RE.search(tail)
    if gm:
        g, s = int(gm.group(1)), int(gm.group(2))
        dims = [int(x) for x in gm.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        if gm.group(4):
            perm = [int(x) for x in gm.group(4).split(",")]
            ids = ids.reshape(dims).transpose(perm).reshape(-1)
        groups = ids.reshape(g, s)
        return list(groups[-1])
    return None


def collective_bytes(hlo_text: str, *, pod_size: int = 256) -> dict:
    """Per-device interconnect bytes from the post-SPMD HLO module.

    Wire-byte model per op (ring algorithms, R = result bytes, gs = group
    size): all-gather R*(gs-1)/gs; all-reduce 2*R*(gs-1)/gs; reduce-scatter
    R*(gs-1); all-to-all R*(gs-1)/gs; collective-permute R.
    A collective whose replica group spans two pods (id // pod_size differs)
    is classed as DCN traffic.
    Returns {"ici": bytes, "dcn": bytes, "ops": {opname: count}}.
    """
    ici = 0.0
    dcn = 0.0
    ops: dict[str, int] = {}
    for m in _COLL_LINE_RE.finditer(hlo_text):
        shapes, opname = m.group(1), m.group(2)
        r = _shape_bytes(shapes)
        tail = hlo_text[m.end():m.end() + 4000]
        group = _first_group(tail)
        gs = len(group) if group else 1
        if gs <= 1:
            continue                      # degenerate / single-device group
        if opname == "all-gather":
            b = r * (gs - 1) / gs
        elif opname == "all-reduce":
            b = 2.0 * r * (gs - 1) / gs
        elif opname == "reduce-scatter":
            b = r * (gs - 1)
        elif opname == "all-to-all":
            b = r * (gs - 1) / gs
        else:                             # collective-permute
            b = r
        ops[opname] = ops.get(opname, 0) + 1
        crosses = group is not None and len({i // pod_size for i in group}) > 1
        if crosses:
            dcn += b
        else:
            ici += b
    return {"ici": ici, "dcn": dcn, "ops": ops}


def _compile_bundle(bundle, mesh):
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     donate_argnums=bundle.donate_argnums)
    t0 = time.time()
    lowered = jitted.lower(*bundle.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return compiled, t_lower, t_compile


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # jaxlib < 0.5: one dict per program
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def _memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, k)) for k in
           ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}
    # jaxlib < 0.5 has no peak_memory_in_bytes; args + outputs + temps -
    # aliased is the live-set upper bound XLA reports as peak on newer
    # releases.
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None:
        peak = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    out["peak_memory_in_bytes"] = int(peak)
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1           # one decode token per sequence
    return 2.0 * n * tokens


def roofline(per_dev: dict, mesh_devices: int, spec=TPU_V5E) -> dict:
    """Three roofline terms (seconds, per step) from per-device totals."""
    t_compute = per_dev["flops"] / spec.peak_flops_bf16
    t_memory = per_dev["bytes"] / spec.hbm_bw
    ici_bw = spec.ici_bw_per_link * spec.ici_links
    t_coll = per_dev["coll_ici"] / ici_bw + per_dev["coll_dcn"] / spec.dcn_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=lambda k: terms[k])
    terms["dominant"] = dom
    terms["step_s"] = max(t_compute, t_memory, t_coll)
    return terms


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             attn_impl: str = "xla", skip_segment: bool = False,
             tcfg_overrides: dict | None = None,
             sharding_preset: str = "global-fsdp") -> dict:
    from repro.sharding.rules import set_sharding_preset
    set_sharding_preset(sharding_preset)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = int(np.prod(list(mesh.shape.values())))
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "devices": ndev, "attn_impl": attn_impl,
                 "sharding_preset": sharding_preset}
    t_start = time.time()

    kw = {}
    if shape.kind == "train":
        overrides = dict(tcfg_overrides or {})
        overrides.setdefault("attn_impl", attn_impl)
        from repro.train.train_step import TrainConfig
        kw["tcfg"] = TrainConfig(**overrides)
    else:
        kw["attn_impl"] = attn_impl

    bundle = make_bundle(cfg, shape, mesh, **kw)
    compiled, t_lower, t_compile = _compile_bundle(bundle, mesh)
    rec["t_lower_s"] = round(t_lower, 1)
    rec["t_compile_s"] = round(t_compile, 1)
    rec["memory"] = _memory_dict(compiled)
    full_cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    rec["hlo_bytes"] = len(hlo)
    full_coll = collective_bytes(hlo)
    del hlo

    nsb = cfg.num_superblocks
    if not skip_segment and nsb > 1:
        seg = superblock_segment(cfg, shape, mesh,
                                 train=(shape.kind == "train"),
                                 attn_impl=attn_impl,
                                 remat=(tcfg_overrides or {}).get("remat", True)
                                 if shape.kind == "train" else True)
        seg_compiled, _, seg_t = _compile_bundle(seg, mesh)
        seg_cost = _cost_dict(seg_compiled)
        seg_hlo = seg_compiled.as_text()
        seg_coll = collective_bytes(seg_hlo)
        del seg_hlo
        rec["t_segment_compile_s"] = round(seg_t, 1)
        rec["segment"] = {"flops": seg_cost["flops"],
                          "bytes": seg_cost["bytes"],
                          "coll_ici": seg_coll["ici"],
                          "coll_dcn": seg_coll["dcn"]}
        k = nsb - 1
        per_dev = {
            "flops": full_cost["flops"] + k * seg_cost["flops"],
            "bytes": full_cost["bytes"] + k * seg_cost["bytes"],
            "coll_ici": full_coll["ici"] + k * seg_coll["ici"],
            "coll_dcn": full_coll["dcn"] + k * seg_coll["dcn"],
        }
    else:
        per_dev = {"flops": full_cost["flops"], "bytes": full_cost["bytes"],
                   "coll_ici": full_coll["ici"], "coll_dcn": full_coll["dcn"]}

    rec["per_device"] = per_dev
    rec["collective_ops"] = full_coll["ops"]
    rec["roofline"] = roofline(per_dev, ndev)
    mf = model_flops(cfg, shape)
    rec["model_flops_global"] = mf
    hlo_global = per_dev["flops"] * ndev
    rec["model_flops_ratio"] = mf / hlo_global if hlo_global else 0.0
    # useful-compute fraction of the step (the §Perf score numerator)
    step_s = rec["roofline"]["step_s"]
    ideal_s = mf / ndev / TPU_V5E.peak_flops_bf16
    rec["roofline_fraction"] = ideal_s / step_s if step_s > 0 else 0.0
    rec["t_total_s"] = round(time.time() - t_start, 1)
    rec["status"] = "ok"
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", type=str, default=None)
    p.add_argument("--shape", type=str, default=None)
    p.add_argument("--mesh", choices=("single", "multi", "both"),
                   default="single")
    p.add_argument("--all", action="store_true")
    p.add_argument("--attn-impl", default="xla")
    p.add_argument("--sharding-preset", default="global-fsdp",
                   choices=("global-fsdp", "pod-fsdp"))
    p.add_argument("--remat", default=None, choices=("full", "dots", "none"))
    p.add_argument("--skip-segment", action="store_true")
    p.add_argument("--microbatches", type=int, default=None)
    p.add_argument("--out", type=str, default="results/dryrun")
    args = p.parse_args()

    cells: list[tuple[str, str]] = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    for a in archs:
        cfg = get_config(a)
        shapes = ([args.shape] if args.shape else supported_shapes(cfg))
        for s in shapes:
            cells.append((a, s))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    tcfg_overrides = {}
    if args.microbatches:
        tcfg_overrides["microbatches"] = args.microbatches
    if args.remat:
        tcfg_overrides["remat"] = {"full": True, "none": False,
                                   "dots": "dots"}[args.remat]
    tcfg_overrides = tcfg_overrides or None
    failures = 0
    for arch, shape in cells:
        for multi in meshes:
            mesh_name = "2x16x16" if multi else "16x16"
            tag = f"{arch}__{shape}__{mesh_name}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[cell] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape, multi, attn_impl=args.attn_impl,
                               skip_segment=args.skip_segment,
                               tcfg_overrides=tcfg_overrides,
                               sharding_preset=args.sharding_preset)
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec.get("status") == "ok":
                r = rec["roofline"]
                print(f"[ ok ] {tag}: peak={rec['memory']['peak_memory_in_bytes']/2**30:.2f} GiB/dev  "
                      f"compute={r['compute_s']*1e3:.2f}ms mem={r['memory_s']*1e3:.2f}ms "
                      f"coll={r['collective_s']*1e3:.2f}ms dom={r['dominant']} "
                      f"frac={rec['roofline_fraction']:.3f}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    # jax fixes the host device count when its CPU backend starts, which no
    # import above does; only the program (never an importer) forces 512
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()
    main()
