"""Paper Table 2 + Fig. 4 proxy: HIGH-intrinsic-rank task (DROP stand-in).

Teacher carries a planted FULL-RANK update on q/v (see benchmarks.common).
The paper's claim under test: QuanTA reaches (here: exceeds) FT-level
recovery where every low-rank-budget LoRA provably floors — because the
required update is high-rank (paper §3, Thm. 6.2)."""

from __future__ import annotations


from benchmarks.common import csv_row, finetune, make_task


def main(steps: int = 300) -> list:
    task = make_task("high")
    rows = []
    runs = [
        ("ft", "ft", dict()),
        ("lora_r4", "lora", dict(rank=4)),
        ("lora_r8", "lora", dict(rank=8)),
        ("lora_r24", "lora", dict(rank=24)),
        ("quanta_n3", "quanta", dict(n_axes=3)),
        ("dora_r8", "dora", dict(rank=8)),
        ("krona", "krona", dict(krona_a=16)),
    ]
    for name, method, kw in runs:
        res = finetune(method, task, steps=steps, **kw)
        rows.append((name, res))
        print(csv_row(
            f"drop_proxy/{name}",
            1e6 * res.seconds / steps,
            f"acc={res.accuracy:.3f};params_pct={res.param_pct:.3f};"
            f"planted_rank={task.planted_rank}",
        ))
    by = dict(rows)
    # the paper's high-rank ordering: QuanTA >= FT > low-rank LoRA
    assert by["quanta_n3"].accuracy > by["lora_r8"].accuracy + 0.2, (
        "QuanTA must beat low-rank LoRA decisively on the high-rank task"
    )
    assert by["quanta_n3"].accuracy > 0.9
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
