"""§Roofline report generator: reads the dry-run JSON records and emits
the per-(arch x shape x mesh) roofline table (markdown + CSV), flagging
the dominant term and the MODEL_FLOPS/HLO_FLOPs useful ratio."""

from __future__ import annotations

import glob
import json
import os
from typing import List

DEFAULT_DIR = os.path.join(
    os.path.dirname(__file__), "results", "dryrun"
)


def load_records(directory: str = DEFAULT_DIR) -> List[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            recs.append(_migrate(json.load(f)))
    return recs


def _migrate(rec: dict) -> dict:
    """Recompute roofline terms for records written before the
    per-device/global convention fix (terms were divided by n_chips
    twice).  Raw cost/collective data in the record is authoritative."""
    if "hlo_flops_per_device" in rec.get("roofline", {}):
        return rec
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.roofline import roofline_terms

    cfg = get_config(rec["arch"])
    shape = next(s for s in SHAPES if s.name == rec["shape"])
    rec["roofline"] = roofline_terms(
        cfg, shape, rec["n_chips"], rec["cost_analysis"],
        rec["roofline"]["collective_breakdown"],
    )
    return rec


def markdown_table(recs: List[dict], mesh: str = "16x16") -> str:
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant |"
        " HBM/dev GiB | useful ratio | MFU bound |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["mesh"] != mesh:
            continue
        t = r["roofline"]
        hbm = r["memory"].get(
            "tpu_corrected_hbm_bytes", r["memory"].get("total_hbm_bytes", 0)
        ) / 2**30
        ur = t.get("useful_flop_ratio")
        mfu = t.get("mfu_bound")
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.4f} | "
            f"{t['memory_s']:.4f} | {t['collective_s']:.4f} | "
            f"{t['dominant']} | {hbm:.2f} | "
            f"{ur:.2f} | " if ur else "| n/a | "
        )
        lines[-1] = (
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.4f} | "
            f"{t['memory_s']:.4f} | {t['collective_s']:.4f} | "
            f"{t['dominant']} | {hbm:.2f} | "
            f"{(f'{ur:.2f}' if ur else 'n/a')} | "
            f"{(f'{mfu:.3f}' if mfu else 'n/a')} |"
        )
    return "\n".join(lines)


def main() -> None:
    for name, directory in (
        ("baseline", DEFAULT_DIR),
        ("optimized", DEFAULT_DIR.replace("dryrun", "dryrun_optimized")),
    ):
        recs = load_records(directory)
        if not recs:
            print(f"roofline/{name},0,no records in {directory}")
            continue
        for mesh in ("16x16", "2x16x16"):
            doms = {}
            for r in recs:
                if r["mesh"] == mesh:
                    doms[r["roofline"]["dominant"]] = doms.get(
                        r["roofline"]["dominant"], 0) + 1
            n = sum(1 for r in recs if r["mesh"] == mesh)
            print(f"roofline/{name}/{mesh},0,cells={n};dominant_counts={doms}")
        out_md = os.path.join(
            os.path.dirname(DEFAULT_DIR), f"roofline_{name}.md"
        )
        with open(out_md, "w") as f:
            f.write(f"# Roofline — {name} (single-pod 16x16)\n\n")
            f.write(markdown_table(recs, "16x16"))
            f.write(f"\n\n# Roofline — {name} (multi-pod 2x16x16)\n\n")
            f.write(markdown_table(recs, "2x16x16"))
            f.write("\n")
        print(f"roofline/table_{name},0,written={out_md}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
