"""End-to-end behaviour tests: training driver (with checkpoint restart),
serving engine (continuous batching), and decode/prefill consistency for
the stateful families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model


def test_train_loop_end_to_end(tmp_path):
    from repro.launch.train import train_loop
    from repro.train.optimizer import OptConfig
    model = Model(get_smoke_config("phi4-mini-3.8b"))
    out = train_loop(model, steps=12, batch=4, seq=48,
                     opt_cfg=OptConfig(lr=2e-3, total_steps=12,
                                       warmup_steps=2),
                     ckpt_dir=str(tmp_path / "ck"), ckpt_every=5,
                     log_every=100)
    assert out["steps_done"] == 12
    assert np.isfinite(out["final_loss"])
    # restart continues from the checkpoint
    out2 = train_loop(model, steps=14, batch=4, seq=48,
                      ckpt_dir=str(tmp_path / "ck"), ckpt_every=5,
                      log_every=100)
    assert out2["steps_done"] == 2      # 12 -> 14 only


def test_serve_engine_continuous_batching():
    from repro.serve.engine import Request, ServeEngine
    cfg = get_smoke_config("glm4-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=5)
        for i in range(7)]                      # 7 requests through 3 slots
    eng = ServeEngine(model, params, batch_size=3, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    done = eng.run(reqs)
    assert all(len(r.output) == 5 for r in done)
    assert eng.stats["tokens_out"] == 35
    # slots are reused, but refilled slots ARE re-prefilled (batched per
    # step): 7 requests through 3 slots in same-length waves = 3 prefills
    assert eng.stats["prefill_calls"] == 3


@pytest.mark.parametrize("poison", [False, True])
def test_serve_engine_counts_nonfinite_tokens(poison):
    """argmax over a NaN logits row still returns an in-vocabulary id, so
    the engine counts every token taken from a non-finite row."""
    from repro.serve.engine import Request, ServeEngine
    cfg = get_smoke_config("glm4-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if poison:
        params = jax.tree.map(lambda x: x * jnp.nan if jnp.issubdtype(
            x.dtype, jnp.floating) else x, params)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=3)
        for i in range(2)]
    eng = ServeEngine(model, params, batch_size=2, cache_len=32,
                      prompt_len=16, plan_warmup=False)
    done = eng.run(reqs)
    assert all(len(r.output) == 3 for r in done)
    assert eng.stats["tokens_out"] == 6
    assert eng.stats["nonfinite_tokens"] == (6 if poison else 0)


def test_serve_engine_refill_matches_serial_decoding():
    """Regression for the continuous-batching bug: slots refilled
    mid-decode used to inherit the previous occupant's KV cache and last
    token (never prefilled).  With the queue exceeding batch_size, every
    request's output must match decoding it alone through a 1-slot
    engine."""
    from repro.serve.engine import Request, ServeEngine
    cfg = get_smoke_config("glm4-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
               for _ in range(7)]
    # staggered lengths so slots free at different steps (un-batched
    # refills as well as the same-step batched case)
    new_tokens = [5, 3, 4, 6, 2, 5, 3]
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, new_tokens))]
    eng = ServeEngine(model, params, batch_size=3, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    eng.run(reqs)

    one = ServeEngine(model, params, batch_size=1, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    for i, (p, n) in enumerate(zip(prompts, new_tokens)):
        ref = Request(rid=100 + i, prompt=p.copy(), max_new_tokens=n)
        one.run([ref])
        assert reqs[i].output == ref.output, f"request {i} diverged"


def test_ssm_decode_equals_prefill_continuation():
    """Mamba-2: decoding one token after prefill == full-seq forward."""
    cfg = get_smoke_config("mamba2-130m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    full = model.logits(params, {"tokens": toks}).astype(jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :S - 1]},
                             cache_len=S + 2)
    lg, _ = model.decode(params, cache, toks[:, S - 1:S])
    np.testing.assert_allclose(np.asarray(lg[:, 0].astype(jnp.float32)),
                               np.asarray(full[:, S - 1]), atol=3e-2,
                               rtol=3e-2)


def test_hybrid_window_ring_buffer():
    """Hymba: decode beyond the window uses the ring buffer correctly —
    prediction must match the teacher-forced forward at every step."""
    cfg = get_smoke_config("hymba-1.5b").with_(window=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 20
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    full = model.logits(params, {"tokens": toks}).astype(jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :12]}, cache_len=24)
    for t in range(12, 16):
        lg, cache = model.decode(params, cache, toks[:, t:t + 1])
        np.testing.assert_allclose(
            np.asarray(lg[:, 0].astype(jnp.float32)),
            np.asarray(full[:, t]), atol=5e-2, rtol=5e-2)


def test_mla_absorbed_decode_matches_forward():
    """DeepSeek MLA: the absorbed (latent-space) decode must agree with the
    decompressed training attention.  f32 so the check is exact (the two
    paths contract in different orders; bf16 noise is checked loosely by
    the per-arch smoke test instead)."""
    cfg = get_smoke_config("deepseek-v3-671b").with_(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    full = model.logits(params, {"tokens": toks}).astype(jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :S - 1]},
                             cache_len=S + 2)
    lg, _ = model.decode(params, cache, toks[:, S - 1:S])
    np.testing.assert_allclose(np.asarray(lg[:, 0].astype(jnp.float32)),
                               np.asarray(full[:, S - 1]), atol=1e-4,
                               rtol=1e-4)


# ------------------------------------------- refill at a row bucket, spliced

REFILL_B = 8
# one dense, SSM, hybrid (window 8 < cache: the ring wraps) and MLA config
# with a leading dense stack, in float32: in bfloat16 a near-tie can pick
# another greedy token at another batch shape, however rows are refilled.
# The MoE is dropless, so a row's tokens can be compared with a solo run.
REFILL_FAMILIES = {"glm4-9b": {}, "mamba2-130m": {},
                   "hymba-1.5b": {"window": 8}, "deepseek-v3-671b": {}}
# budgets that free 1, then 3, then all 8 slots at once, then 1, then 6
REFILL_BUDGETS = [2, 3, 3, 3, 5, 5, 5, 5, 3, 2, 2, 2,
                  2, 4, 4, 4, 4, 4, 4, 4, 2, 3, 3, 3, 3, 3, 3]


def _slot_refills(budgets, B):
    """Slots admitted by each prefill call under the engine's policy: a
    FIFO queue, freed slots refilled in slot order after each step."""
    queue, left, sizes = list(budgets), [0] * B, []
    while queue or any(left):
        free = [i for i in range(B) if not left[i]][:len(queue)]
        if free:
            sizes.append(len(free))
            for i in free:
                left[i] = queue.pop(0)
        left = [max(n - 1, 0) for n in left]
    return sizes


@pytest.fixture(scope="module", params=list(REFILL_FAMILIES))
def refill_engine(request):
    from repro.serve.engine import ServeEngine
    cfg = get_smoke_config(request.param).with_(
        dtype="float32", **REFILL_FAMILIES[request.param])
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params, batch_size=REFILL_B, cache_len=48,
                       prompt_len=16, plan_warmup=False)


def _refill_requests(cfg, budgets=REFILL_BUDGETS, seed=2):
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=12)
                    .astype(np.int32), max_new_tokens=n)
            for i, n in enumerate(budgets)]


def test_bucketed_refill_matches_serial_decoding(refill_engine):
    """Refills of 1, 3, 8 and 6 slots at B 8: each prefills only its row
    bucket (1, 4, 8, 8; the full refill whole, the others spliced), and
    every request decodes as it would alone in a 1-slot engine."""
    from repro.serve.engine import Request, ServeEngine
    eng = refill_engine
    assert eng.buckets == [1, 4, 8]
    sizes = _slot_refills(REFILL_BUDGETS, REFILL_B)
    assert sizes == [8, 1, 3, 8, 1, 6]
    rows = [REFILL_B] + [min(b for b in (1, 4, 8) if b >= k)
                         for k in sizes[1:]]
    calls, prefill = [], eng.prefill_step
    eng.prefill_step = lambda p, b: (calls.append(b["tokens"].shape[0])
                                     or prefill(p, b))
    stats0 = dict(eng.stats)
    try:
        reqs = _refill_requests(eng.model.cfg)
        eng.run(reqs)
    finally:
        eng.prefill_step = prefill
    assert calls == rows
    assert eng.stats["prefill_calls"] - stats0["prefill_calls"] == len(sizes)
    assert eng.stats["rows_prefilled"] - stats0["rows_prefilled"] == sum(rows)
    assert (eng.stats["rows_admitted"] - stats0["rows_admitted"]
            == len(REFILL_BUDGETS))

    one = ServeEngine(eng.model, eng.params, batch_size=1, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    for r in reqs:
        ref = Request(rid=r.rid, prompt=r.prompt.copy(),
                      max_new_tokens=r.max_new_tokens)
        one.run([ref])
        assert r.output == ref.output, f"request {r.rid} diverged"


def test_splice_writes_only_the_refilled_rows(refill_engine):
    """A partial refill's splice writes each fresh row at its slot and
    leaves every other row of the live state bit-identical; the padding
    rows of the bucket (index B) write nothing, not even to row B - 1."""
    eng = refill_engine
    reqs = _refill_requests(eng.model.cfg, [1] * (REFILL_B + 2), seed=3)
    logits, live = eng.prefill_step(eng.params, eng._batch(reqs[:REFILL_B]))
    last = jnp.argmax(logits[:, -1, :eng.model.cfg.vocab_size], -1)
    before = jax.device_get((last, live))
    logits4, fresh = eng.prefill_step(eng.params,
                                      eng._batch([None] * 2 + reqs[REFILL_B:]))
    want_first = np.asarray(jnp.argmax(
        logits4[:, -1, :eng.model.cfg.vocab_size], -1))
    fresh_h = jax.device_get(fresh)
    slots = [5, 2]
    idx = jnp.asarray([REFILL_B] * 2 + slots, jnp.int32)   # as the engine
    new_last, new = jax.device_get(
        eng.splice_rows(live, last, logits4, fresh, idx))

    kept = [i for i in range(REFILL_B) if i not in slots]
    np.testing.assert_array_equal(new_last[kept], before[0][kept])
    np.testing.assert_array_equal(new_last[slots], want_first[2:])
    np.testing.assert_array_equal(new["pos"][kept], before[1]["pos"][kept])
    np.testing.assert_array_equal(new["pos"][slots], fresh_h["pos"][2:])
    leaves = {k: v for k, v in new.items() if k != "pos"}
    for path, got in jax.tree_util.tree_leaves_with_path(leaves):
        old = before[1]
        src = fresh_h
        for key in path:
            old, src = old[key.key], src[key.key]
        # stacked leaves: (layers, batch, ...)
        np.testing.assert_array_equal(got[:, kept], old[:, kept],
                                      err_msg=str(path))
        np.testing.assert_array_equal(got[:, slots], src[:, 2:],
                                      err_msg=str(path))


def test_refill_buckets_compile_nothing_while_serving(refill_engine):
    """The engine builds every bucket's prefill and splice before it
    serves: after a run that only fills all slots at once, a run whose
    refills visit every bucket builds no executable."""
    eng = refill_engine
    eng.run(_refill_requests(eng.model.cfg, [2] * REFILL_B, seed=4))
    compiles = eng.stats["compiles"]
    rows0 = eng.stats["rows_prefilled"]
    eng.run(_refill_requests(eng.model.cfg, seed=5))
    assert eng.stats["rows_prefilled"] - rows0 == 8 + 1 + 4 + 8 + 1 + 8
    assert eng.stats["compiles"] == compiles


@pytest.mark.parametrize("batch,multiple,want", [
    (1, 1, [1]), (2, 1, [1, 2]), (16, 1, [1, 4, 16]),
    (32, 1, [1, 4, 16, 32]), (128, 1, [1, 4, 16, 64, 128]),
    (128, 4, [4, 16, 64, 128]), (8, 2, [2, 4, 8]), (12, 8, [8, 12])])
def test_row_buckets_ladder(batch, multiple, want):
    from repro.serve.engine import row_buckets
    assert row_buckets(batch, multiple) == want
