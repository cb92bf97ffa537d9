"""Paper Tables 2-4, "# Params (%)" columns — exact analytic reproduction.

The parameter fractions in the paper are pure arithmetic over the QuanTA
schemes and base-model sizes; this benchmark recomputes every QuanTA row
and checks it against the published number.
"""

from __future__ import annotations

import time

from benchmarks.common import csv_row
from repro.core.factorize import pair_schedule, param_count, parse_scheme

# (model, scheme, adapted matrices/layer, layers, base params, paper %,
#  strict) — strict=False rows: the paper's number is not reproducible
#  with the stated one-tensor-per-axis-pair rule (16-16-16 gives 0.187%
#  analytically vs 0.261% printed; consistent with an extra tensor round,
#  cf. Fig. E.4 variants).  Reported, not asserted.
ROWS = [
    ("llama2-7b",  "16-8-8-4",  2, 32, 6.74e9,  0.041, True),
    ("llama2-7b",  "16-16-16",  2, 32, 6.74e9,  0.261, False),
    ("llama2-13b", "16-8-8-5",  2, 40, 13.0e9,  0.029, True),
    ("llama2-70b", "16-8-8-8",  2, 80, 69.0e9,  0.014, True),
    ("llama3-8b",  "16-8-8-4",  2, 32, 8.03e9,  0.035, True),
]


def quanta_fraction(scheme: str, n_matrices: int, n_layers: int,
                    base_params: float) -> float:
    dims = parse_scheme(scheme)
    per = param_count(dims, pair_schedule(len(dims)))
    return 100.0 * per * n_matrices * n_layers / base_params


def main() -> list:
    out = []
    t0 = time.time()
    for model, scheme, mats, layers, base, paper_pct, strict in ROWS:
        pct = quanta_fraction(scheme, mats, layers, base)
        ok = abs(pct - paper_pct) < 0.012
        out.append((model, scheme, pct, paper_pct, ok))
        print(csv_row(
            f"param_efficiency/{model}_{scheme}",
            1e6 * (time.time() - t0),
            f"ours={pct:.3f}%;paper={paper_pct:.3f}%;match={ok}"
            + ("" if strict else ";note=paper-count-not-reproducible"),
        ))
        if strict:
            assert ok, (model, scheme, pct, paper_pct)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
