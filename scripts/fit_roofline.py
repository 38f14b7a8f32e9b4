#!/usr/bin/env python
"""Fit the recurrent-scan roofline from a saved bench line.

Reads the ``char_rnn_recurrent_roofline`` grid out of the JSON line
``python bench.py --suite rnn`` printed on a TPU and fits, per batch size,

    t_pass = flops / eff_peak + (2 * seq) * tau

across the hidden sizes measured - two unknowns (effective peak
throughput and per-sequential-step overhead tau), two H points per B.
The tau estimate is the deep-vs-wide MFU gap's explanation candidate:
deep (4 x 1280) runs 2x the sequential steps of wide (2 x 2048) per
token at ~2.56x smaller per-step matmuls, so a fixed tau taxes it twice.

Usage: python scripts/fit_roofline.py BENCH_LINE.json
"""

import json
import sys
from pathlib import Path


def fit(rows):
    """rows: list of roofline row dicts sharing a batch size.  Least
    squares over ALL rows (exact at two points; overdetermined when the
    grid grows a third H), solving t = f/P + s*tau with t in seconds,
    f = training FLOPs, s = sequential steps (2*seq)."""
    if len(rows) < 2:
        return None
    import numpy as np

    def f(r):
        return 3.0 * r["seq"] * 2 * r["batch"] * r["hidden"] * 4 * r["hidden"]

    a = np.array([[f(r), 2 * r["seq"]] for r in rows])
    t = np.array([r["ms_per_pass"] / 1e3 for r in rows])
    (inv_p, tau), *_ = np.linalg.lstsq(a, t, rcond=None)
    if inv_p == 0:
        return None
    return {"eff_peak_tflops": round(1e-12 / inv_p, 1),
            "tau_us_per_step": round(tau * 1e6, 3)}


def main():
    if len(sys.argv) != 2:
        sys.exit(__doc__.strip().splitlines()[-1])
    line = json.loads(Path(sys.argv[1]).read_text())
    grid = line["extra_metrics"]["char_rnn_recurrent_roofline"]
    cells = [v for v in grid.values() if isinstance(v, dict)]
    for batch in sorted({c["batch"] for c in cells}):
        sub = sorted((c for c in cells if c["batch"] == batch),
                     key=lambda c: c["hidden"])
        out = fit(sub)
        print(f"B={batch}: cells="
              + ", ".join(f"H{c['hidden']}={c['ms_per_pass']}ms"
                          f"(mfu {c['mfu_vs_bf16_peak']})"
                          for c in sub)
              + (f" -> eff_peak={out['eff_peak_tflops']} TF/s, "
                 f"tau={out['tau_us_per_step']} us/step" if out else
                 " -> not enough cells to fit"))


if __name__ == "__main__":
    main()
