"""The int8-vs-bf16 inference A/B driver runs end-to-end (CPU tiny).

Reference headline it measures: BigQuant's ~4x size / up-to-2x inference
speedup (docs/docs/whitepaper.md:192); the size ratio is asserted here,
the speedup is hardware evidence to be collected on the chip
(tools/quant_perf.py): not measured.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))


def test_quant_perf_tiny():
    from quant_perf import run

    r = run(batch=4, steps=2, depth=18, image=32, classes=10)
    assert r["bf16"]["imgs_per_sec"] > 0
    assert r["int8"]["imgs_per_sec"] > 0
    # reference Fig. 10's ~4x is model-file (fp32) vs int8; the served
    # bf16 weights are already half of fp32 -> ~2x serving-memory ratio.
    # BN params stay full precision so both land just under the ideal.
    assert r["size_ratio_vs_fp32"] > 3.5
    assert r["size_ratio_vs_bf16"] > 1.8
