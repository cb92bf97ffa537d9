"""§Perf hillclimb driver: baseline -> hypothesis -> change -> re-lower ->
measure, for the three selected (arch x shape) cells.

Run:  PYTHONPATH=src python -m benchmarks.hillclimb [--cell A|B|C|all]

Cells (selection rationale in EXPERIMENTS.md §Perf):
  A phi3-medium-14b  train_4k   — worst memory pressure + most
                                   representative of the paper's technique
                                   (the QuanTA fine-tuning step itself)
  B minicpm-2b       decode_32k — most collective-bound cell of the grid
  C mixtral-8x7b     train_4k   — MoE representative, mixed memory/
                                   collective profile

Each variant re-lowers the full step program on the production mesh and
records the three roofline terms + HBM; results land in
benchmarks/results/hillclimb/ and feed the EXPERIMENTS.md §Perf log.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json      # noqa: E402

from repro.launch.dryrun import lower_cell  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "results", "hillclimb")

# (tag, arch, shape, kwargs)
VARIANTS = {
    "A": [
        ("A0_baseline", "phi3-medium-14b", "train_4k", {}),
        ("A1_fast_softmax", "phi3-medium-14b", "train_4k",
         dict(cfg_overrides={"fast_softmax": True})),
        ("A2_fast_softmax_mb16", "phi3-medium-14b", "train_4k",
         dict(cfg_overrides={"fast_softmax": True},
              shape_overrides={"microbatches": 16})),
        ("A3_fast_softmax_mb4", "phi3-medium-14b", "train_4k",
         dict(cfg_overrides={"fast_softmax": True},
              shape_overrides={"microbatches": 4})),
        ("A4_mb16", "phi3-medium-14b", "train_4k",
         dict(shape_overrides={"microbatches": 16})),
        ("A5_mb16_qblock256", "phi3-medium-14b", "train_4k",
         dict(cfg_overrides={"q_block": 256},
              shape_overrides={"microbatches": 16})),
        ("A6_flash_attn", "phi3-medium-14b", "train_4k",
         dict(cfg_overrides={"attn_backend": "pallas"})),
        ("A7_flash_attn_mb16", "phi3-medium-14b", "train_4k",
         dict(cfg_overrides={"attn_backend": "pallas"},
              shape_overrides={"microbatches": 16})),
    ],
    "B": [
        ("B0_baseline", "minicpm-2b", "decode_32k", {}),
        ("B1_embed_dshard", "minicpm-2b", "decode_32k",
         dict(decode_shardings=True)),
        ("B2_embed_dshard_fast", "minicpm-2b", "decode_32k",
         dict(decode_shardings=True,
              cfg_overrides={"fast_softmax": True})),
        ("B3_cache_seq_shard", "minicpm-2b", "decode_32k",
         dict(cache_seq_shard=True)),
        ("B4_flash_decode", "minicpm-2b", "decode_32k",
         dict(decode_shardings=True,
              cfg_overrides={"attn_backend": "pallas"})),
        ("B5_paged_decode", "minicpm-2b", "decode_32k",
         dict(decode_shardings=True,
              cfg_overrides={"attn_backend": "pallas",
                             "kv_cache": "paged"})),
        ("B6_nf4_decode", "minicpm-2b", "decode_32k",
         dict(decode_shardings=True,
              cfg_overrides={"attn_backend": "pallas",
                             "kv_cache": "paged",
                             "base_quant": "nf4"})),
        ("B7_nf4_kv_decode", "minicpm-2b", "decode_32k",
         dict(decode_shardings=True,
              cfg_overrides={"attn_backend": "pallas",
                             "kv_cache": "paged",
                             "base_quant": "nf4",
                             "kv_quant": "nf4"})),
    ],
    "C": [
        ("C0_baseline", "mixtral-8x7b", "train_4k", {}),
        ("C1_fast_softmax", "mixtral-8x7b", "train_4k",
         dict(cfg_overrides={"fast_softmax": True})),
        ("C2_fast_softmax_mb4", "mixtral-8x7b", "train_4k",
         dict(cfg_overrides={"fast_softmax": True},
              shape_overrides={"microbatches": 4})),
        ("C3_fast_softmax_mb2", "mixtral-8x7b", "train_4k",
         dict(cfg_overrides={"fast_softmax": True},
              shape_overrides={"microbatches": 2})),
        ("C4_qblock1024", "mixtral-8x7b", "train_4k",
         dict(cfg_overrides={"q_block": 1024})),
        ("C5_capacity1.0", "mixtral-8x7b", "train_4k",
         dict(cfg_overrides={"capacity_factor": 1.0})),
        ("C6_flash_attn", "mixtral-8x7b", "train_4k",
         dict(cfg_overrides={"attn_backend": "pallas"})),
    ],
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="all", choices=("A", "B", "C", "all"))
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)
    cells = list(VARIANTS) if args.cell == "all" else [args.cell]
    for cell in cells:
        for tag, arch, shape, kw in VARIANTS[cell]:
            path = os.path.join(OUT, tag + ".json")
            try:
                rec = lower_cell(arch, shape, multi_pod=False, tag=tag, **kw)
                rec["tag"] = tag
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                t = rec["roofline"]
                print(f"[hillclimb] {tag}: compute={t['compute_s']:.4f} "
                      f"memory={t['memory_s']:.4f} "
                      f"collective={t['collective_s']:.4f} "
                      f"hbm={rec['memory']['tpu_corrected_hbm_bytes']/2**30:.2f}GiB",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"[hillclimb] {tag} FAILED: {e!r}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
