"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be executed as ``python -m repro.launch.dryrun`` — the XLA_FLAGS
export below has to run before ANY jax initialization, which is why these
are the very first statements of the module.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Optional  # noqa: E402

import jax           # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS, get_config, get_peft, get_shapes,
)
from repro.configs.shapes import SHAPES  # noqa: E402
from repro.launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from repro.launch.hlo_cost import cpu_upcast_param_bytes, hlo_cost  # noqa: E402
from repro.launch.roofline import (  # noqa: E402
    parse_collective_bytes, roofline_terms,
)
from repro.launch.shardings import (  # noqa: E402
    batch_shardings, cache_shardings, state_shardings,
)
from repro.launch.steps import build_programs  # noqa: E402


def _mem_stats(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
    except (AttributeError, NotImplementedError, RuntimeError) as e:
        # some backends (older CPU plugins) don't expose memory_analysis;
        # the stats are advisory, so log and move on — anything else
        # (a genuine bug) propagates.
        print(f"[dryrun] memory_analysis unavailable: {e!r}", flush=True)
        return {}                                          # pragma: no cover
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}
    out["total_hbm_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0)
    )
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True, cfg_overrides: Optional[dict] = None,
               shape_overrides: Optional[dict] = None,
               decode_shardings: bool = False, cache_seq_shard: bool = True,
               tag: str = "") -> dict:
    """Lower + compile one cell; return the roofline/memory record.

    ``cfg_overrides`` / ``shape_overrides``: §Perf hillclimb variants
    (e.g. ``{"fast_softmax": True}``, ``{"microbatches": 16}``)."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    peft_cfg = get_peft(arch)
    shape = next(s for s in SHAPES if s.name == shape_name)
    if shape_overrides:
        import dataclasses as _dc
        shape = _dc.replace(shape, **shape_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    dp = dp_axes(mesh)
    axis_sizes = dict(mesh.shape)
    dp_size = 1
    for a in dp:
        dp_size *= axis_sizes[a]
    if cfg.is_moe:
        # group-local MoE dispatch: one token group per DP shard
        cfg = cfg.replace(moe_groups=dp_size, dp_axes=dp)
    elif cfg.seq_parallel_residual:
        cfg = cfg.replace(dp_axes=dp)
    cfg_lowered = cfg
    if cfg.attn_backend == "pallas":
        # The flash kernel is an opaque custom-call in TPU HLO (an
        # interpreter loop on this CPU backend) — unparseable by
        # hlo_cost either way.  Lower the reference program and let
        # roofline_terms swap the attention terms analytically
        # (attention_backend_adjustment), the same convention as the
        # collective-bytes model.
        cfg_lowered = cfg.replace(attn_backend="reference")
    if cfg.base_quant is not None:
        # Same convention: the fused dequant-matmul is a custom-call the
        # cost parser can't see through.  Lower the fp program; the
        # roofline rebills the quantizable weight streams at packed bytes
        # (quantized_base_adjustment).
        cfg_lowered = cfg_lowered.replace(base_quant=None)
    if getattr(cfg, "kv_quant", None) is not None:
        # And again for quantized KV-cache blocks: the dequant-in-VMEM
        # paged decode kernel is opaque, so lower the fp-cache program
        # and let the roofline rebill the per-step KV gather at packed
        # code+scale bytes (quantized_kv_adjustment).
        cfg_lowered = cfg_lowered.replace(kv_quant=None)
    progs = build_programs(cfg_lowered, shape, dp_axes=dp)

    t0 = time.time()
    if shape.kind == "train":
        state_specs = progs.state_specs(peft_cfg)
        state_shard = state_shardings(cfg, mesh, state_specs)
        batch_shard = batch_shardings(mesh, progs.batch_specs)
        jitted = jax.jit(
            progs.step_fn,
            in_shardings=(state_shard, batch_shard),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_specs, progs.batch_specs)
    elif shape.kind == "prefill":
        state_specs = progs.state_specs(peft_cfg)
        param_shard = state_shardings(cfg, mesh, state_specs,
                                      decode=decode_shardings)
        batch_shard = batch_shardings(mesh, progs.batch_specs)
        jitted = jax.jit(
            progs.step_fn,
            in_shardings=(param_shard.params, param_shard.peft, batch_shard),
        )
        with mesh:
            lowered = jitted.lower(
                state_specs.params, state_specs.peft, progs.batch_specs
            )
    else:  # decode
        state_specs = progs.state_specs(peft_cfg)
        param_shard = state_shardings(cfg, mesh, state_specs,
                                      decode=decode_shardings)
        cache_specs = progs.cache_specs()
        cache_shard = cache_shardings(cfg, mesh, cache_specs,
                                      seq_shard=cache_seq_shard)
        batch_shard = batch_shardings(mesh, progs.batch_specs)
        jitted = jax.jit(
            progs.step_fn,
            in_shardings=(
                param_shard.params, param_shard.peft, cache_shard, batch_shard
            ),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = jitted.lower(
                state_specs.params, state_specs.peft, cache_specs,
                progs.batch_specs,
            )
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = _mem_stats(compiled)
    hlo_text = compiled.as_text()
    # XLA's own cost_analysis() counts while (scan) bodies ONCE — useless
    # with scanned layers/microbatches; use the trip-count-aware parser and
    # keep the raw numbers for reference.
    xla_cost = dict(compiled.cost_analysis() or {})
    cost = hlo_cost(hlo_text)
    coll = parse_collective_bytes(hlo_text)
    terms = roofline_terms(cfg, shape, n_chips, cost, coll)
    # XLA:CPU hoists f32 copies of bf16 weights (no native bf16 matmul on
    # CPU); a TPU compile would not allocate these.  Report both numbers.
    upcast = cpu_upcast_param_bytes(hlo_text)
    mem["cpu_f32_upcast_bytes"] = upcast
    mem["tpu_corrected_hbm_bytes"] = max(
        0, mem.get("total_hbm_bytes", 0) - upcast
    )

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": shape.kind,
        "tag": tag,
        "cfg_overrides": cfg_overrides or {},
        "shape_overrides": shape_overrides or {},
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost_analysis": {
            k: cost[k] for k in ("flops", "bytes accessed") if k in cost
        },
        "xla_cost_analysis_raw": {
            k: xla_cost[k] for k in ("flops", "bytes accessed")
            if k in xla_cost
        },
        "roofline": terms,
    }
    if verbose:
        hbm_gb = mem.get("tpu_corrected_hbm_bytes",
                         mem.get("total_hbm_bytes", 0)) / 2**30
        print(
            f"[dryrun] {arch} {shape_name} mesh={record['mesh']} OK  "
            f"hbm/dev={hbm_gb:.2f}GiB  "
            f"compute={terms['compute_s']:.4f}s "
            f"memory={terms['memory_s']:.4f}s "
            f"collective={terms['collective_s']:.4f}s "
            f"dominant={terms['dominant']} "
            f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
            flush=True,
        )
        print(f"  memory_analysis: {mem}", flush=True)
        print(
            "  cost_analysis: flops=%.3e bytes=%.3e" % (
                terms["hlo_flops"], terms["hlo_bytes"]
            ),
            flush=True,
        )
    return record


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=("single", "multi", "both"))
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for arch in archs:
        valid = {s.name for s in get_shapes(arch)}
        shape_names = (
            [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
        )
        for shape_name in shape_names:
            if shape_name not in valid:
                print(f"[dryrun] {arch} {shape_name}: SKIP "
                      f"(inapplicable, see DESIGN.md)", flush=True)
                continue
            for multi_pod in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[dryrun] {tag}: cached", flush=True)
                    continue
                try:
                    record = lower_cell(arch, shape_name, multi_pod)
                    with open(path, "w") as f:
                        json.dump(record, f, indent=1)
                except (ValueError, TypeError, KeyError, RuntimeError,
                        OSError) as e:
                    # config errors (ValueError/KeyError), lowering bugs
                    # (TypeError/RuntimeError from jax), and json/write
                    # failures (OSError) mark the cell failed but let the
                    # sweep finish; programming errors outside those
                    # classes abort the sweep loudly.
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] {tag}: FAILED {e!r}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}", flush=True)
        return 1
    print("[dryrun] all requested cells compiled.", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
