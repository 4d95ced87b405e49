"""The decode step's layer scans carry the stacked cache.

A cache leaf handed to ``lax.scan`` as ``xs`` and taken back as ``ys`` is
sliced out layer by layer, written whole into a second stacked cache and
copied across the loop boundary on every step.  Carried beside the
residual, it is updated in place at each layer's index.  These tests trace
``Model.decode`` (no compile, no run) and read the top-level scans of its
jaxpr: every stacked cache leaf enters a scan as a carry and leaves one as
a carry, and none is a scan's ``xs`` or ``ys``.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import Model

B, CACHE_LEN = 2, 24

# dense GQA; the SSD; MLA with leading dense layers (two stacks, two
# scans); a hybrid whose window of 8 is a ring inside the 24-long cache
FAMILIES = {"phi4-mini-3.8b": {}, "mamba2-130m": {}, "deepseek-v3-671b": {},
            "hymba-1.5b": {"window": 8}}


def _scan_roles(jaxpr):
    """The variables each top-level scan takes and gives, by role."""
    roles = {"carry_in": set(), "carry_out": set(), "xs": set(), "ys": set()}
    n_scans = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "scan":
            continue
        n_scans += 1
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        roles["carry_in"] |= set(eqn.invars[nc:nc + nk])
        roles["xs"] |= set(eqn.invars[nc + nk:])
        roles["carry_out"] |= set(eqn.outvars[:nk])
        roles["ys"] |= set(eqn.outvars[nk:])
    return n_scans, roles


def _trace_decode(cfg):
    model = Model(cfg)
    params = model.abstract_params()
    cache = jax.eval_shape(lambda: model.init_cache(B, CACHE_LEN))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    closed = jax.make_jaxpr(model.decode)(params, cache, tok)
    n_param = len(jax.tree.leaves(params))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(cache)[0]]
    jaxpr = closed.jaxpr
    cache_in = jaxpr.invars[n_param:n_param + len(paths)]
    cache_out = jaxpr.outvars[1:]          # after the logits
    assert len(cache_out) == len(paths)
    return jaxpr, dict(zip(paths, zip(cache_in, cache_out)))


@pytest.mark.parametrize("arch", list(FAMILIES))
def test_decode_scans_carry_every_stacked_cache_leaf(arch):
    cfg = get_smoke_config(arch).with_(**FAMILIES[arch])
    jaxpr, leaves = _trace_decode(cfg)
    n_scans, roles = _scan_roles(jaxpr)
    assert n_scans == (2 if cfg.first_dense_layers else 1)
    stacked = {p: v for p, v in leaves.items() if p != "['pos']"}
    assert stacked
    if cfg.window:
        k = jax.eval_shape(lambda: Model(cfg).init_cache(B, CACHE_LEN))
        assert k["layers"]["attn"]["k"].shape[2] == cfg.window < CACHE_LEN
    for path, (vin, vout) in stacked.items():
        assert vin in roles["carry_in"], f"{path} does not enter a carry"
        assert vout in roles["carry_out"], f"{path} does not leave a carry"
        assert vin not in roles["xs"], f"{path} is a scan's xs"
        assert vout not in roles["ys"], f"{path} is a scan's ys"


def test_encdec_decode_carries_its_self_attention_cache():
    """The encoder-decoder's self-attention cache rides in its decoder
    scan's carry; the cross cache is read there and handed back as it came,
    never rebuilt as a ``ys``."""
    cfg = get_smoke_config("seamless-m4t-medium")
    jaxpr, leaves = _trace_decode(cfg)
    n_scans, roles = _scan_roles(jaxpr)
    assert n_scans == 1
    for path, (vin, vout) in leaves.items():
        if path.startswith("['self']"):
            assert vin in roles["carry_in"] and vout in roles["carry_out"]
        elif path.startswith("['cross']"):
            assert vin in roles["xs"] and vout is vin
        assert vout not in roles["ys"], f"{path} is a scan's ys"
