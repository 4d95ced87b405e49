"""Work counts from shapes, checked against hand counts at smoke sizes and
against frozen counts at full size, and found through the family module."""
import json
import sys
import types
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_smoke
from bench import run, work

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


# counts of the full configurations, frozen from the benchmark as it was
# before each family's counting moved into its own module; the cells' own
# shapes are among them (decode at 16 x 640 positions on phi4-mini.decode,
# prefills of 16 x 512, 2 x 4096, 128 x 256, 32 x 4096, and refill buckets)
FROZEN = [
    # (config, kind, rows, ctx_or_prompt, flops, bytes)
    ("phi4-mini-3.8b", "decode", 16, 10240, 126779129856, 9016416256),
    ("phi4-mini-3.8b", "decode", 16, 9000, 126291542016, 8853886976),
    ("phi4-mini-3.8b", "decode", 1, 300, 7790002176, 7711502336),
    ("phi4-mini-3.8b", "prefill", 16, 512, 53622469558272, 8796116992),
    ("phi4-mini-3.8b", "prefill", 2, 4096, 59377696899072, 8796116992),
    ("phi4-mini-3.8b", "prefill", 4, 256, 6653727473664, 7812552704),
    ("mamba2-130m", "decode", 128, 45568, 36013867008, 5156065536),
    ("mamba2-130m", "decode", 32, 131200, 9003466752, 1482494208),
    ("mamba2-130m", "decode", 3, 0, 844075008, 372769536),
    ("mamba2-130m", "prefill", 128, 256, 7214156218368, 2757251328),
    ("mamba2-130m", "prefill", 32, 4096, 28819554435072, 1071534336),
    ("mamba2-130m", "prefill", 1, 256, 56360595456, 277496064),
]


@pytest.mark.parametrize("name,kind,rows,n,flops,nbytes", FROZEN,
                         ids=[f"{c[0]}-{c[1]}-{c[2]}x{c[3]}" for c in FROZEN])
def test_counts_of_full_configs_are_unchanged(name, kind, rows, n, flops,
                                              nbytes):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    w = (work.decode_step(conf, rows, n) if kind == "decode"
         else work.prefill_call(conf, rows, n))
    assert w == work.Work(flops, nbytes)


def _toy(monkeypatch, **parts):
    """A family module ``bench.reference.toy`` made of ``parts`` alone."""
    mod = types.ModuleType("bench.reference.toy")
    mod.__dict__.update(parts)
    monkeypatch.setitem(sys.modules, "bench.reference.toy", mod)
    return {"name": "toy", "reference": "toy"}


def test_a_new_family_module_feeds_the_roofline_and_mfu_readers(monkeypatch):
    conf = _toy(
        monkeypatch,
        decode_work=lambda cfg, rows, ctx: work.Work(1e9 * rows,
                                                     1e8 + 1e6 * ctx),
        prefill_work=lambda cfg, rows, S: work.Work(1e10 * rows * S, 1e8))
    launches = [SimpleNamespace(kind="prefill", rows=2, ctx_sum=0),
                SimpleNamespace(kind="decode", rows=2, ctx_sum=20),
                SimpleNamespace(kind="decode", rows=1, ctx_sum=12)]
    trace = SimpleNamespace(launch_device_s=[0.32, 0.004, 0.00224],
                            launch_step_s=[0.4, 0.005, 0.003], window_s=1.0)
    stub = run.Run(conf=conf, mix=SimpleNamespace(prompt_len=8),
                   peak={"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                   setup_s=0.0, t_open=0.0, t_close=1.0, served=[],
                   launches=launches, counters={}, trace=trace)
    got = {m: run.load_metric(m)(stub) for m in
           ("prefill_roofline", "decode_roofline", "mfu.prefill", "mfu")}
    # prefill: 1.6e11 FLOP, 0.16 s at the peak, over 0.32 s on the device
    # and 0.4 s of whole step; decodes: 0.002 s (compute) and 0.00112 s
    # (bytes) over 0.00624 s; the window: 1.63e11 FLOP in 1 s
    assert got == pytest.approx({"prefill_roofline": 50.0,
                                 "decode_roofline": 50.0,
                                 "mfu.prefill": 40.0, "mfu": 16.3})


def test_a_family_module_without_a_count_is_named(monkeypatch):
    conf = _toy(monkeypatch, prefill_work=lambda cfg, rows, S: work.Work(0, 0))
    with pytest.raises(NotImplementedError,
                       match=r"bench\.reference\.toy gives no decode_work\(\)"):
        work.decode_step(conf, 1, 1)
