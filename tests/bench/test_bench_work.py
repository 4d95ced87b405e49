"""Work counts from shapes, checked against hand counts at smoke sizes."""
import json
from pathlib import Path

import pytest

import bench_smoke
from bench import work

ROOT = Path(__file__).resolve().parents[2]

# dense smoke: d 64, 2 layers, 4 heads of 16 (2 kv), ff 128, vocab 512.
# matrices per layer: wq 64x64, wk 64x32, wv 64x32, wo 64x64, MLP 3x64x128
#   = 36864 -> 73728 for two layers; norms 2x2x64 + 64 = 320; head 64x512.
# one cached position: 2 layers x (k, v) x 2 kv heads x 16 x 2 B = 256 B.
# mamba2 smoke: d 64, 2 layers, d_inner 128, 8 heads of 16, state 16,
# conv 4 over 160 channels; in_proj 64x296 and out_proj 128x64 per layer
#   = 27136 -> 54272; conv, conv bias, norm and gated-norm scales
#   2x(640+160+128+64) + 64 = 2048 bf16; A_log, dt_bias, D 2x24 f32;
# state of a row 2 x (8x16x16x4 + 3x160x2) = 18304 B.
CASES = [
    # (config, kind, rows, ctx_or_prompt, flops, bytes)
    (bench_smoke.DENSE, "decode", 3, 30,
     3 * 2 * (73728 + 64 * 512) + 2 * 4 * 4 * 16 * 33,
     (73728 + 320 + 64 * 512) * 2 + 3 * 64 * 2 + 256 * 30 + 256 * 3),
    (bench_smoke.DENSE, "prefill", 2, 8,
     2 * (2 * 73728 * 8 + 2 * 4 * 4 * 16 * 36 + 2 * 64 * 512),
     (73728 + 320 + 64 * 512) * 2 + 2 * 8 * (64 * 2 + 256)),
    (bench_smoke.MAMBA2, "decode", 3, 0,
     3 * (2 * (54272 + 64 * 512) + 2 * (2 * 4 * 160 + 5 * 8 * 16 * 16)),
     (54272 + 2048 + 64 * 512) * 2 + 48 * 4 + 3 * (64 * 2 + 2 * 18304)),
    # chunk min(256, 8) = 8: C B^T 2x8x16 once, per head 2x8x16 + 4x16x16
    (bench_smoke.MAMBA2, "prefill", 2, 8,
     2 * (8 * (2 * 54272 + 2 * (2 * 4 * 160 + 256 + 8 * (256 + 1024)))
          + 2 * 64 * 512),
     (54272 + 2048 + 64 * 512) * 2 + 48 * 4 + 2 * (8 * 64 * 2 + 18304)),
]


@pytest.mark.parametrize("conf,kind,rows,n,flops,nbytes", CASES,
                         ids=[f"{c[0]['name']}-{c[1]}" for c in CASES])
def test_counts_match_hand_counts(conf, kind, rows, n, flops, nbytes):
    w = (work.decode_step(conf, rows, n) if kind == "decode"
         else work.prefill_call(conf, rows, n))
    assert w == work.Work(flops, nbytes)


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flop_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.Work(1000.0, 50.0).least_s(peak) == 10.0
    assert work.Work(100.0, 50.0).least_s(peak) == 5.0


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "mamba2-130m"])
def test_decode_step_of_full_configs_is_bound_by_bytes(name):
    """At full width one decode step reads every weight once: far more
    bytes than its operations take on the v5e at batch 16."""
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    peak = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    w = work.decode_step(conf, 16, 16 * 600)
    assert w.bytes / peak["hbm_bytes_per_s"] > w.flops / peak["bf16_flop_per_s"]
