"""Paper Table 1 proxy: LOW-intrinsic-rank task (RTE stand-in).

Teacher carries a planted rank-4 update: the low-rank hypothesis HOLDS, so
small-rank LoRA matches QuanTA — reproducing the paper's observation that
RTE saturates already at small LoRA rank (increasing rank does not help).

A final serving-side leg decodes the trained QuanTA student through the
paged quantized KV cache (``cfg.kv_quant``) against the fp-cache engine
under TEACHER FORCING (same fp-generated prefix fed to both, one next
token compared per depth — free-running greedy streams compound a single
flip into total divergence, which measures stream stability, not cache
quality) and gates the per-step argmax agreement — the KV-quantization
quality gate (the ``quanta_n3_nf4`` training leg covers ``base_quant``).
int8 KV must be essentially exact; nf4's gate is loose for the same
reason ``make_task`` documents for the base: on this d=64 / head_dim=16
proxy nf4's ~9% elementwise error is huge against toy logit margins,
while at paper scale the flip rate is the (separately benchmarked)
format quality, not a serving property."""

from __future__ import annotations

import numpy as np

from benchmarks.common import csv_row, finetune, make_task


def _kv_step_agreement(task, res, fmt: str, n_prompts: int = 8,
                       prompt_len: int = 12, max_depth: int = 16) -> float:
    """Teacher-forced per-step greedy agreement between the trained
    student served over the paged ``kv_quant=fmt`` cache and over the fp
    cache: both engines get the SAME fp-generated prefix at each depth
    and exactly ONE next token is compared, so one flipped step cannot
    cascade into the rest of the measurement."""
    from repro.models import build_model
    from repro.serve import Request, ServingEngine

    def streams(kv, prompts, max_new):
        model = build_model(task.model.cfg.replace(kv_quant=kv))
        engine = ServingEngine(
            model, res.base_params, res.peft_state,
            n_slots=4, max_len=64,
            cache="paged" if kv else "dense", block_size=8,
        )
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        return [r.output for r in reqs]

    rng = np.random.default_rng(999)
    base = [rng.integers(1, 255, (prompt_len,)).tolist()
            for _ in range(n_prompts)]
    fp_free = streams(None, base, max_depth)        # fp prefixes to force
    agrs = []
    for depth in range(0, max_depth, 2):
        forced = [p + o[:depth] for p, o in zip(base, fp_free)]
        fp1 = streams(None, forced, 1)
        q1 = streams(fmt, forced, 1)
        agrs.append(float(np.mean([a == b for a, b in zip(fp1, q1)])))
    return float(np.mean(agrs))


def main(steps: int = 300) -> list:
    task = make_task("low")
    # QLoRA-style leg: teacher planted on the fake-quantized base, student
    # trains QuanTA against the nf4-stored base (serving's base_quant
    # format) — see make_task's docstring for why the gate is built on the
    # quantized base rather than comparing against the fp teacher
    task_nf4 = make_task("low", base_quant="nf4")
    rows = []
    for name, method, kw in [
        ("ft", "ft", {}),
        ("lora_r4", "lora", dict(rank=4)),
        ("lora_r8", "lora", dict(rank=8)),
        ("quanta_n3", "quanta", dict(n_axes=3, keep_state=True)),
        ("quanta_n3_nf4", "quanta", dict(n_axes=3, base_quant="nf4")),
    ]:
        res = finetune(method, task_nf4 if "nf4" in name else task,
                       steps=steps, **kw)
        rows.append((name, res))
        print(csv_row(
            f"rte_proxy/{name}",
            1e6 * res.seconds / steps,
            f"acc={res.accuracy:.3f};params_pct={res.param_pct:.3f};"
            f"planted_rank={task.planted_rank}",
        ))
    by = dict(rows)
    # low-rank regime: small-rank LoRA is sufficient (Table 1), and
    # rank escalation brings ~nothing
    assert by["lora_r4"].accuracy > 0.9
    assert by["lora_r8"].accuracy - by["lora_r4"].accuracy < 0.08
    assert by["quanta_n3"].accuracy > 0.9
    # quantized-base fine-tuning stays within tolerance of the fp base
    assert by["quanta_n3_nf4"].accuracy > by["quanta_n3"].accuracy - 0.05
    # serving-side KV-quantization gates (see module docstring for the
    # toy-scale nf4 tolerance)
    for fmt, floor in (("int8", 0.95), ("nf4", 0.70)):
        agr = _kv_step_agreement(task, by["quanta_n3"], fmt)
        print(csv_row(
            f"rte_proxy/quanta_n3_kv_{fmt}", 0.0,
            f"step_agreement={agr:.3f};cache=paged_{fmt}_vs_fp;"
            f"gate>={floor}",
        ))
        assert agr >= floor, (
            f"{fmt} KV cache step agreement {agr:.3f} < {floor}"
        )
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
