"""DeepSeek-V3's family module counted by hand at the full chip-share
configuration, and the expert layer's two readers on a synthetic trace."""
import json
import sys
import types
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import reference, run, serve_trace, work
from bench.reference import deepseek_v3

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads(
    (ROOT / "bench" / "configs" / "deepseek-v3-ep32.json").read_text())

# deepseek-v3-ep32: d 7168, 7 layers (3 dense, 4 MoE), 128 heads, nope 128,
# rope 64, v 128, q-LoRA 1536, kv-LoRA 512, dense ff 18432, experts of 2048
# (8 held of 256, top 8, 1 shared), vocabulary 16160.
MLA = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 128
       + 512 * 128 * 128 + 128 * 128 * 7168)              # 187,105,280
EXPERT = 3 * 7168 * 2048
DENSE = 3 * 7168 * 18432
# bf16: 7 x (MLA, its two norms of 1536 and 512, two layer norms of d),
# 3 dense FFNs, 4 x (8 held experts and the shared one), final norm, head;
# f32: 4 routers of d x 256
WEIGHTS = ((7 * (MLA + 2048 + 2 * 7168) + 3 * DENSE + 4 * 9 * EXPERT + 7168
            + 7168 * 16160) * 2 + 4 * 7168 * 256 * 4)
LATENT = 7 * 576 * 2                                        # a position's cache
# multiply-adds of one token: MLA, dense FFNs, and per MoE layer the router,
# a quarter of an expert (8 picks x 8 held / 256) and the shared expert
MACS = 7 * MLA + 3 * DENSE + 4 * (7168 * 256 + EXPERT // 4 + EXPERT)


def test_decode_work_is_absorbed_mla_over_the_valid_latent_cache():
    rows, ctx = 64, 64 * 600
    keys = ctx + rows
    want = work.Work(
        rows * 2 * (MACS + 7168 * 16160) + 7 * 2 * 128 * (2 * 512 + 64) * keys,
        WEIGHTS + rows * 7168 * 2 + LATENT * (ctx + rows))
    assert work.decode_step(CONF, rows, ctx) == want
    assert WEIGHTS == pytest.approx(8.43e9, rel=1e-3)
    peak = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    # bound by bytes: 8.7 GB a step, 10.7 ms on the v5e
    assert want.least_s(peak) == pytest.approx(
        want.bytes / peak["hbm_bytes_per_s"])
    assert 0.0105 < want.least_s(peak) < 0.011


def test_prefill_work_is_decompressed_mla_over_the_causal_triangle():
    rows, S = 4, 256
    attn = 7 * 2 * 128 * (128 + 64 + 128) * S * (S + 1) // 2
    want = work.Work(rows * (2 * MACS * S + attn + 2 * 7168 * 16160),
                     WEIGHTS + rows * S * (7168 * 2 + LATENT))
    assert work.prefill_call(CONF, rows, S) == want


def test_moe_decode_work_is_the_expert_layers_of_a_step():
    rows = 64
    want = work.Work(
        4 * rows * 2 * (7168 * 256 + EXPERT // 4 + EXPERT),
        4 * (9 * EXPERT * 2 + 7168 * 256 * 4 + 2 * rows * 7168 * 2))
    assert deepseek_v3.moe_decode_work(CONF, rows) == want
    assert want.bytes == 3207593984


def _toy(monkeypatch, **parts):
    mod = types.ModuleType("bench.reference.toy")
    mod.__dict__.update(parts)
    monkeypatch.setitem(sys.modules, "bench.reference.toy", mod)
    return {"name": "toy", "reference": "toy"}


def _stub(conf, reduction, monkeypatch):
    monkeypatch.setattr(serve_trace, "for_run", lambda r: reduction)
    launches = [SimpleNamespace(kind="prefill", rows=1, ctx_sum=0),
                SimpleNamespace(kind="decode", rows=4, ctx_sum=40),
                SimpleNamespace(kind="decode", rows=2, ctx_sum=30)]
    trace = SimpleNamespace(launch_device_s=[0.02, 0.05, 0.05],
                            launch_step_s=[0.03, 0.06, 0.06], window_s=1.0)
    return run.Run(conf=conf, mix=SimpleNamespace(prompt_len=8),
                   peak={"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                   setup_s=0.0, t_open=0.0, t_close=1.0, served=[],
                   launches=launches, counters={}, trace=trace)


def _reduction(moe_s):
    scopes = {"attention": 0.03, "mlp": 0.01}
    if moe_s is not None:
        scopes["moe"] = moe_s
    return serve_trace.ServeReduction(
        window_s=1.0, decode_host_s=[], step_s={"prefill": 0.02, "decode": 0.1},
        scope_s={"prefill": {"moe": 0.005}, "decode": scopes}, idle_by_span=[])


def test_moe_share_and_roofline_read_the_moe_scope_of_decode(monkeypatch):
    conf = _toy(monkeypatch,
                moe_decode_work=lambda cfg, rows: work.Work(1e9 * rows, 1e9))
    stub = _stub(conf, _reduction(0.04), monkeypatch)
    # decodes of 4 and 2 rows: each bound by its 1e9 bytes, 10 ms at the
    # peaks, 20 ms against 40 ms under the moe scope; the scope's 40 of
    # the decodes' 100 ms
    assert run.load_metric("decode_moe_share")(stub) == pytest.approx(40.0)
    assert run.load_metric("decode_moe_roofline")(stub) == pytest.approx(50.0)


def test_moe_readers_read_nothing_where_there_is_nothing(monkeypatch):
    conf = _toy(monkeypatch,
                moe_decode_work=lambda cfg, rows: work.Work(1e9 * rows, 1e9))
    # no moe scope in the decode steps (a dense model)
    stub = _stub(conf, _reduction(None), monkeypatch)
    assert run.load_metric("decode_moe_share")(stub) is None
    assert run.load_metric("decode_moe_roofline")(stub) is None
    # no trace of the engine's scopes
    stub = _stub(conf, None, monkeypatch)
    assert run.load_metric("decode_moe_share")(stub) is None
    assert run.load_metric("decode_moe_roofline")(stub) is None
    # a family module without the expert layer's count
    stub = _stub(_toy(monkeypatch), _reduction(0.04), monkeypatch)
    assert run.load_metric("decode_moe_roofline")(stub) is None
    assert not hasattr(reference.family("dense"), "moe_decode_work")
