"""Multi-device tests: run in a subprocess with 8 virtual CPU devices
(XLA_FLAGS must be set before jax initializes, hence the subprocess).

Triage note: the suite failed at seed because the kernel/model stack it
exercises could not import against newer pltpu APIs; the PR 1 compat shim
fixed that and the suite passes under the sandbox now.  Environments that
cannot run it at all (no jax, or subprocess spawning disabled) skip with
an explicit reason instead of erroring; genuine assertion failures inside
the subprocess still fail the test.  CI runs this under the non-blocking
``slow-suite`` job so regressions stay visible without gating merges.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytest.importorskip("jax", reason="distributed suite needs jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    assert len(jax.devices()) == 8

    # ---- 1. sharded train step on the mesh, GSPMD loss
    from repro.configs import get_smoke_config
    from repro.models import Model
    from repro.parallel.sharding import (batch_sharding, param_shardings,
                                         zero1_shardings)
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import TrainState, make_train_step
    from repro.train.data import SyntheticLM

    cfg = get_smoke_config("qwen3-moe-30b-a3b").with_(d_model=64, n_experts=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ax, ab = model.param_axes(), model.abstract_params()
    psh = param_shardings(ax, ab, mesh)
    zsh = zero1_shardings(ax, ab, mesh)
    params = jax.device_put(params, psh)
    opt = init_opt_state(params)
    opt = opt._replace(m=jax.device_put(opt.m, zsh),
                       v=jax.device_put(opt.v, zsh))
    state = TrainState(params, opt)
    data = SyntheticLM(cfg.vocab_size, 32, 8, seed=0)
    step = jax.jit(make_train_step(model, OptConfig(lr=1e-3, total_steps=5),
                                   mesh), donate_argnums=(0,))
    losses = []
    for i in range(5):
        b = {k: jax.device_put(jnp.asarray(v),
                               batch_sharding(mesh, 8, v.ndim))
             for k, v in data.batch(i).items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), losses
    print("MESH_TRAIN_OK", losses[0], losses[-1])

    # ---- 2. planner loss: dist == gather == unsharded reference
    from repro.parallel.collective_planner import sharded_softmax_xent
    from repro.models.layers import cross_entropy_loss
    B, S, D, V = 4, 8, 32, 64
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(D, V)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 50, size=(B, S)), jnp.int32)
    ref = cross_entropy_loss((h @ W)[None][0], y, 50)
    ld = jax.jit(lambda *a: sharded_softmax_xent(*a, mesh, real_vocab=50,
                                                 strategy="dist"))(h, W, y)
    lg = jax.jit(lambda *a: sharded_softmax_xent(*a, mesh, real_vocab=50,
                                                 strategy="gather"))(h, W, y)
    np.testing.assert_allclose(float(ld), float(ref), rtol=1e-5)
    np.testing.assert_allclose(float(lg), float(ref), rtol=1e-5)
    print("PLANNER_LOSS_OK", float(ld), float(lg), float(ref))

    # ---- 3. MoE shard_map == no-mesh reference
    from repro.models.moe import moe_apply
    x = jnp.asarray(rng.normal(size=(8, 16, cfg.d_model)) * 0.1, jnp.float32)
    lp = jax.tree.map(lambda a: a[0], jax.device_get(state.params["layers"]["moe"]))
    lp = jax.tree.map(jnp.asarray, lp)
    y_ref = moe_apply(cfg, lp, x.astype(jnp.bfloat16))
    xs = jax.device_put(x.astype(jnp.bfloat16),
                        NamedSharding(mesh, P("data", None, None)))
    lps = {k: jax.device_put(v, NamedSharding(mesh, P("model") if k in
           ("wi", "wg", "wo") else P())) for k, v in lp.items()}
    y_mesh = jax.jit(lambda p, xx: moe_apply(cfg, p, xx, mesh=mesh))(lps, xs)
    err = float(jnp.abs(y_mesh.astype(jnp.float32)
                        - y_ref.astype(jnp.float32)).max())
    assert err < 0.1, err   # capacity drop differences only
    print("MOE_SHARD_OK", err)

    # ---- 4. elastic remesh: 2x4 -> 1x4 (lost a data replica)
    from repro.train.elastic import remesh, shrink_mesh
    small = shrink_mesh(failed_devices=4, model_parallel=4)
    psh_small = param_shardings(ax, ab, small)
    p_small = remesh(jax.device_get(state.params), psh_small)
    n_before = sum(x.size for x in jax.tree.leaves(state.params))
    n_after = sum(x.size for x in jax.tree.leaves(p_small))
    assert n_before == n_after
    print("ELASTIC_OK", small.devices.shape)

    # ---- 5. compressed psum over pod axis
    from repro.parallel.compression import compressed_psum
    g = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    out = compressed_psum(g, mesh, "data")
    # int8 quantization error <= absmax/127 per replica
    tol = 2.5 * float(jnp.abs(g).max()) / 127.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(g) * 2, atol=tol)
    print("COMPRESSED_PSUM_OK")

    # ---- 6. checkpoint saved on mesh restores onto the smaller mesh
    from repro.train.checkpoint import save_checkpoint, restore_checkpoint
    import tempfile
    d = tempfile.mkdtemp()
    save_checkpoint(d, 1, state.params)
    restored, _, _ = restore_checkpoint(d, state.params, shardings=psh_small)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ELASTIC_RESTORE_OK")

    # ---- 7. decode under the mesh: the layer scans carry the cache, which
    # comes out with the shardings it went in with (kpos excepted: its ring
    # axis comes out split over 'model' beside a sequence-split K/V)
    from repro.launch.specs import with_shardings
    from repro.parallel.sharding import cache_shardings
    for arch in ("glm4-9b", "mamba2-130m", "deepseek-v3-671b"):
        m = Model(get_smoke_config(arch))
        cab = jax.eval_shape(lambda: m.init_cache(8, 32))
        csh = cache_shardings(cab, mesh, 8)
        pab = with_shardings(m.abstract_params(),
                             param_shardings(m.param_axes(),
                                             m.abstract_params(), mesh))
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32,
                                   sharding=batch_sharding(mesh, 8, 2))
        with mesh:
            out = jax.jit(lambda p, c, t: m.decode(p, c, t, mesh),
                          donate_argnums=(1,)).lower(
                pab, with_shardings(cab, csh), tok).compile()
        for (path, want), got, leaf in zip(
                jax.tree_util.tree_flatten_with_path(csh)[0],
                jax.tree.leaves(out.output_shardings[1]),
                jax.tree.leaves(cab)):
            name = jax.tree_util.keystr(path)
            if "kpos" not in name:
                assert want.is_equivalent_to(got, leaf.ndim), \\
                    (arch, name, want, got)
    print("DECODE_CACHE_SHARDINGS_OK")
    print("ALL_DISTRIBUTED_OK")
""")


@pytest.mark.slow
def test_distributed_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    try:
        r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                           capture_output=True, text=True, timeout=1200)
    except (OSError, PermissionError) as e:
        pytest.skip(f"sandbox cannot spawn the 8-device subprocess: {e!r}")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ALL_DISTRIBUTED_OK" in r.stdout
