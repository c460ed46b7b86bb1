"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
CPU devices stand in for 2 pods of 256 v5e chips.  For each cell we lower
the real step function against abstract inputs (zero allocation), compile,
and record memory_analysis / cost_analysis / collective bytes for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch stablelm-3b --shape train_4k
  python -m repro.launch.dryrun --all                 # every cell, both meshes
  python -m repro.launch.dryrun --all --single-pod-only
"""
# The placeholder-device flag MUST precede any jax import.
import os  # noqa: E402
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse                     # noqa: E402
import json                         # noqa: E402
import re                           # noqa: E402
import time                         # noqa: E402
import traceback                    # noqa: E402

import jax                          # noqa: E402
import jax.numpy as jnp             # noqa: E402

from repro.configs.base import ALL_SHAPES, ArchConfig, ShapeCell, get_config, list_configs, shapes_for  # noqa: E402
from repro.distributed.sharding import mesh_context, named_sharding, strategy_rules, tree_shardings  # noqa: E402
from repro.launch import specs as S  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import registry as R  # noqa: E402
from repro.models.param import Axes, abstract_tree, axes_tree  # noqa: E402
from repro.training.optimizer import OptConfig, abstract_opt_state  # noqa: E402
from repro.training.train_step import make_train_step  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# Collective accounting from optimized HLO
# ---------------------------------------------------------------------------
_DEF_RE = re.compile(r"%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([\d,]*)\]")
_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum result bytes per collective opcode (per-partition program)."""
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _DEF_RE.match(stripped)
        if not m:
            continue
        rest = stripped[m.end():]
        for op in _COLL:
            # match opcode usage like "= bf16[...] all-gather(" incl. -start
            if re.search(rf"\s{op}(-start)?\(", rest):
                out[op] = out.get(op, 0.0) + _shape_bytes(m.group(2), m.group(3))
                counts[op] = counts.get(op, 0) + 1
                break
        # tuple-shaped collectives: "= (bf16[..], bf16[..]) all-reduce-start("
        if "(" == stripped.split("=")[-1].strip()[:1]:
            for op in _COLL:
                if re.search(rf"\)\s{op}(-start)?\(", stripped):
                    for dt, dims in re.findall(r"([a-z0-9]+)\[([\d,]*)\]",
                                               stripped.split(op)[0]):
                        out[op] = out.get(op, 0.0) + _shape_bytes(dt, dims)
                    counts[op] = counts.get(op, 0) + 1
                    break
    return {"bytes_by_op": out, "counts": counts,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------
def _batch_axes(batch: dict) -> dict:
    ax = {}
    for k, v in batch.items():
        if k in ("tokens", "targets"):
            ax[k] = Axes(("batch", "seq")) if len(v.shape) == 2 else Axes(("batch",))
        elif k in ("patch_embeds", "frames"):
            ax[k] = Axes(("batch", "seq", None))
        elif k == "positions":
            ax[k] = Axes(("batch",))
        else:
            raise KeyError(k)
    return ax


def build_cell(cfg: ArchConfig, cell: ShapeCell, mesh, strategy: str):
    """-> (fn, abstract_args, in_shardings, out_shardings, donate)."""
    prules, arules = strategy_rules(strategy)
    aparams = R.abstract_params(cfg)
    p_sh = tree_shardings(R.param_axes(cfg), aparams, mesh, prules)

    if cell.kind == "train":
        opt_cfg = OptConfig()
        aopt = abstract_opt_state(aparams, opt_cfg)
        o_sh = {"m": tree_shardings(R.param_axes(cfg), aopt["m"], mesh, prules),
                "v": tree_shardings(R.param_axes(cfg), aopt["v"], mesh, prules),
                "step": named_sharding((), (), mesh)}
        batch = S.batch_specs(cfg, cell)
        b_sh = tree_shardings(_batch_axes(batch), batch, mesh, arules)
        from repro.util import opt_flags
        mb = 8 if "microbatch8" in opt_flags() else 1
        step = make_train_step(cfg, opt_cfg, impl="ref", microbatches=mb)
        return (step, (aparams, aopt, batch), (p_sh, o_sh, b_sh),
                (p_sh, o_sh, None), (0, 1))

    if cell.kind == "prefill":
        batch = S.batch_specs(cfg, cell)
        b_sh = tree_shardings(_batch_axes(batch), batch, mesh, arules)

        def step(params, batch):
            return R.prefill(cfg, params, batch, max_len=cell.seq_len, impl="ref")

        return step, (aparams, batch), (p_sh, b_sh), None, ()

    # decode
    d = S.decode_specs(cfg, cell)
    enc_len = S.WHISPER_ENC_LEN if cfg.enc_dec else None
    cache_axes = axes_tree(R.cache_specs(cfg, cell.global_batch, cell.seq_len,
                                         enc_len=enc_len))
    c_sh = tree_shardings(cache_axes, d["cache"], mesh, arules)
    t_sh = named_sharding((cell.global_batch,), ("batch",), mesh, arules)

    def step(params, cache, tokens, positions):
        # abstract arguments have no layout at rest: the compiler picks it
        return R.decode_step(cfg, params, cache, tokens, positions,
                             cache_layouts=None, impl="ref")

    return (step, (aparams, d["cache"], d["tokens"], d["positions"]),
            (p_sh, c_sh, t_sh, t_sh), None, (1,))


DEFAULT_STRATEGY = {"train": "sp", "prefill": "tp", "decode": "tp"}


def _lower_compile(cfg, cell, mesh, strategy):
    prules, arules = strategy_rules(strategy)
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_cell(cfg, cell, mesh, strategy)
    with mesh_context(mesh, arules):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, strategy: str = "",
             with_cost: bool = True, opts: str = "", tag: str = "") -> dict:
    if opts:
        os.environ["REPRO_OPTS"] = opts
    cfg = get_config(arch)
    cell = {c.name: c for c in ALL_SHAPES}[shape_name]
    strategy = strategy or DEFAULT_STRATEGY[cell.kind]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    # 1) production lowering: scanned layers -> compile proof + memory
    compiled, t_lower, t_compile = _lower_compile(cfg, cell, mesh, strategy)
    mem = compiled.memory_analysis()
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "chips": int(n_chips),
        "multi_pod": multi_pod, "strategy": strategy,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {k: int(getattr(mem, k)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                   if hasattr(mem, k)},
        "params": R.count_params(cfg),
        "params_active": R.count_params(cfg, active=True),
    }
    del compiled

    # 2) cost lowering: scans unrolled -> true HLO FLOPs + collectives.
    # Unrolling the full depth is too slow to compile, but every layer group
    # is identical, so cost is linear in depth: measure at G=2 and G=4
    # unrolled and extrapolate — exact for boundary + G * per_group.
    if with_cost:
        os.environ["REPRO_COST_MODE"] = "1"
        try:
            t0 = time.time()
            plen = len(cfg.resolved_pattern)
            G = cfg.n_groups
            probes = {}
            for g in (2, min(4, max(G, 2))):
                if g in probes:
                    continue
                import dataclasses
                enc = (cfg.num_encoder_layers * g // G) if cfg.enc_dec else 0
                cfg_g = dataclasses.replace(cfg, num_layers=plen * g,
                                            num_encoder_layers=max(enc, 1) if cfg.enc_dec else 0)
                costc, _, _ = _lower_compile(cfg_g, cell, mesh, strategy)
                cost = costc.cost_analysis()
                if isinstance(cost, (list, tuple)):
                    cost = cost[0]
                coll = parse_collectives(costc.as_text())
                probes[g] = {"flops": float(cost.get("flops", 0.0)),
                             "bytes": float(cost.get("bytes accessed", 0.0)),
                             "coll": coll}
                del costc
            gs = sorted(probes)
            if len(gs) == 1:
                lo = hi = probes[gs[0]]
                g_lo = g_hi = gs[0]
            else:
                (g_lo, g_hi) = gs
                lo, hi = probes[g_lo], probes[g_hi]

            def extrap(vlo, vhi):
                if g_hi == g_lo:
                    return vhi * G / g_hi
                per_g = (vhi - vlo) / (g_hi - g_lo)
                return vhi + per_g * (G - g_hi)

            coll_ops = {}
            for op in set(lo["coll"]["bytes_by_op"]) | set(hi["coll"]["bytes_by_op"]):
                coll_ops[op] = extrap(lo["coll"]["bytes_by_op"].get(op, 0.0),
                                      hi["coll"]["bytes_by_op"].get(op, 0.0))
            result.update({
                "cost_compile_s": round(time.time() - t0, 1),
                "flops": extrap(lo["flops"], hi["flops"]),
                "bytes_accessed": extrap(lo["bytes"], hi["bytes"]),
                "collectives": {"bytes_by_op": coll_ops,
                                "total_bytes": sum(coll_ops.values()),
                                "counts": hi["coll"]["counts"],
                                "probe_groups": gs, "total_groups": G},
            })
        finally:
            os.environ["REPRO_COST_MODE"] = "0"

    result["opts"] = opts
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        name = f"{arch}_{shape_name}_{'multipod' if multi_pod else 'pod'}"
        if tag:
            name += f"_{tag}"
        with open(os.path.join(ARTIFACT_DIR, name + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    if opts:
        os.environ.pop("REPRO_OPTS", None)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--strategy", default="")
    ap.add_argument("--no-cost", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_configs():
            cfg = get_config(arch)
            for cell in shapes_for(cfg):
                cells.append((arch, cell.name))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if (args.both or (args.all and not args.single_pod_only and not args.multipod)) \
        else ([True] if args.multipod else [False])

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                r = run_cell(arch, shape, mp, strategy=args.strategy,
                             with_cost=not args.no_cost)
                print(f"OK   {tag}: compile={r['compile_s']}s "
                      f"flops={r.get('flops', -1):.3e} "
                      f"coll={r.get('collectives', {}).get('total_bytes', -1):.3e}B "
                      f"temp={r['memory'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
            except Exception as e:
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
