"""The stack walker's scans carry indices, not weights (PR 48).

A ``lax.scan`` that takes a parameter (or a slice, reshape or cast of
one) as a scanned operand slices it at the top of its body, and a slice
that feeds an inner loop, or that several layers slice again, is a
buffer: XLA copies the period's matrices out of the stacked parameters
every step (a tenth of the LFM2 cell's decode step before PR 48).  So
``models/hybrid.py _period_scan`` scans index vectors, and every
sub-block slices a matrix where it reads it (``_LayerTensors``).  These
tests read the traced programs of every family the walker serves, at its
tiny preset, and fail on the CPU when a scan is handed weights again.
No compile: seconds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from vgate_tpu.models import decoder, hybrid
from vgate_tpu.models.specs import spec_for_model_id
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

# preset -> the page its caches are laid in (the family's own tests')
FAMILIES = {
    "tiny-hybrid": 4, "tiny-nemotron-h": 4, "tiny-mla-moe": 4,
    "tiny-swa-moe": 4, "tiny-dsa-moe": 8, "tiny-eva": 4,
    "tiny-lfm2-moe": 4,
}
SLOTS, PAGES, TABLE = 4, 64, 16

# a view of an array: what a scanned operand may be made of and still be
# the parameter it was taken from
_VIEWS = {
    "reshape", "squeeze", "expand_dims", "broadcast_in_dim", "transpose",
    "slice", "dynamic_slice", "gather", "convert_element_type", "copy",
    "concatenate",
}


def _sub_jaxprs(eqn):
    """(jaxpr, the eqn's operands its invars stand for) of every jaxpr
    an equation calls, as far as the operands can be told."""
    p, ins = eqn.params, list(eqn.invars)
    name = eqn.primitive.name
    if name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        return [(p["cond_jaxpr"].jaxpr, ins[:nc] + ins[nc + nb:]),
                (p["body_jaxpr"].jaxpr, ins[nc:])]
    if name == "cond":
        return [(b.jaxpr, ins[1:]) for b in p["branches"]]
    out = []
    for value in p.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns") and hasattr(inner, "invars"):
            out.append((inner, ins if len(inner.invars) == len(ins)
                        else None))
    return out


def _xs(eqn):
    """A ``scan`` equation's scanned operands."""
    return eqn.invars[eqn.params["num_consts"] + eqn.params["num_carry"]:]


def _walk(jaxpr, weights, found):
    """Appends to ``found`` the scanned operands, in ``jaxpr`` and every
    jaxpr it calls, that are views of one of ``weights`` (variables of
    ``jaxpr``); returns every variable of ``jaxpr`` that is such a
    view."""
    weights = set(weights)
    held = lambda v: not isinstance(v, Literal) and v in weights
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found += [(eqn.params["length"], v.aval)
                      for v in _xs(eqn) if held(v)]
        for inner, ins in _sub_jaxprs(eqn):
            views = _walk(inner, [iv for iv, v in zip(inner.invars, ins or ())
                                  if held(v)], found)
            if eqn.primitive.name == "pjit":  # jnp's own small programs
                weights.update(
                    v for v, iv in zip(eqn.outvars, inner.outvars)
                    if not isinstance(iv, Literal) and iv in views)
        if eqn.primitive.name in _VIEWS and any(map(held, eqn.invars)):
            weights.update(eqn.outvars)
    return weights


def scans_of_weights(jaxpr, weights):
    """``[(scan length, operand's aval)]`` of every scanned operand that
    is a parameter, or a view of one."""
    found = []
    _walk(jaxpr, weights, found)
    return found


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.eval_shape(tree))


def _caches(spec, ps):
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=PAGES, page_size=ps,
        kv_heads=spec.cache_heads, head_dim=spec.cache_head_dim,
        max_model_len=TABLE * ps * max(1, spec.cache_row_tokens),
        dtype_bytes=4, pools=spec.kv_pools,
        index_layers=spec.index_layers, index_dim=spec.index_head_dim,
        row_tokens=spec.cache_row_tokens,
        slot_pages=(SLOTS * hybrid.eva_window_pages(spec, ps)
                    if spec.eva_layers else 0))
    kp, vp = _abstract(lambda: make_kv_buffers(geo, jnp.float32))
    state = _abstract(lambda: hybrid.make_state(
        spec, SLOTS, jnp.float32, ps, PAGES)
    ) if spec.slot_state_layers else None
    return kp, vp, state


def _traced(spec, ps, which):
    """(jaxpr, its variables that are parameters) of one decode step or
    of a short prompt pass (two prompts in a bucket of 32)."""
    params = _abstract(lambda: decoder.init_params(
        spec, jax.random.PRNGKey(0), jnp.float32))
    kp, vp, state = _caches(spec, ps)
    ints = lambda *shape: jnp.ones(shape, jnp.int32)
    if which == "decode":
        fn = lambda params, kp, vp, state: decoder.decode_forward(
            params, spec, ints(SLOTS), ints(SLOTS) * 5, kp, vp,
            ints(SLOTS, TABLE), active=jnp.ones((SLOTS,), bool),
            state=state)
    else:
        B, S = 2, 32
        fn = lambda params, kp, vp, state: decoder.prefill_forward(
            params, spec, ints(B, S), ints(B) * 20, kp, vp,
            ints(B, S // ps), state=state,
            slots=jnp.arange(B, dtype=jnp.int32))
    jaxpr = jax.make_jaxpr(fn)(params, kp, vp, state).jaxpr
    return jaxpr, jaxpr.invars[:len(jax.tree.leaves(params))]


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for inner, _ in _sub_jaxprs(eqn):
            yield from _scans(inner)


@pytest.mark.parametrize("which", ["decode", "prompt"])
@pytest.mark.parametrize("preset", list(FAMILIES))
def test_no_scan_of_a_pass_is_handed_weights(preset, which):
    spec = spec_for_model_id(preset)
    jaxpr, weights = _traced(spec, FAMILIES[preset], which)
    found = scans_of_weights(jaxpr, weights)
    assert not found, f"scanned operands that are weights: {found}"
    # the walker's own scan is there, and all it scans is indices
    is_int = lambda v: jnp.issubdtype(v.aval.dtype, jnp.integer)
    over_periods = [
        eqn for eqn in _scans(jaxpr)
        if eqn.params["length"] == spec.num_periods and any(
            v.aval.shape == (spec.num_periods,) and is_int(v)
            for v in _xs(eqn))]
    assert over_periods, "no scan over the periods' indices"
    for eqn in over_periods:
        assert all(map(is_int, _xs(eqn))), [v.aval for v in _xs(eqn)]


def test_a_scan_over_weights_is_found():
    """The reading itself: a scan whose operand is a reshaped slice of a
    parameter is reported, one over an index is not."""
    w = jnp.ones((6, 4, 4))

    def handed(w, x):
        per = w.reshape(2, 3, 4, 4)[1]
        return jax.lax.scan(lambda c, m: (c @ m, None), x, per)[0]

    def indexed(w, x):
        return jax.lax.scan(
            lambda c, i: (c @ w.reshape(2, 3, 4, 4)[1, i], None), x,
            jnp.arange(3))[0]

    x = jnp.ones((4,))
    for fn, want in ((handed, [(3, (3, 4, 4))]), (indexed, [])):
        jaxpr = jax.make_jaxpr(fn)(w, x).jaxpr
        got = scans_of_weights(jaxpr, jaxpr.invars[:1])
        assert [(n, a.shape) for n, a in got] == want
    np.testing.assert_allclose(handed(w, x), indexed(w, x))
