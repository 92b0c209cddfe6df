"""The fused round builds its flat (members x parameters) matrix once.

* Structure, at the paper's §V shapes (784-200-10 MLP, N = 159,010) with
  the fused Eqn-6+19 kernel on: the traced scan holds exactly one
  member-sized concatenate, at the kernel's `padded_width`; no pad of a
  member-sized operand (the kernel wrapper's pad is gone); and nothing
  that concatenates with member rows comes from a broadcast of the
  cluster model (its (M, N) copy is gone).  The jaxpr is read, so the
  facts hold for the program as traced on any backend; on the CPU the
  Pallas call runs in interpret mode, so member rows are matched by
  shape, not by op name.
* Values: the flat kernel entry equals the pytree entry bit for bit on a
  width that is not a multiple of the lane block, with padded mask rows;
  the members' updates equal the two flattened stacks' difference exactly,
  and the tail columns are exactly zero.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, Federation,
                       FederationSpec, FleetSpec, TaskSpec)
from repro.api.engine import _flat_members
from repro.data import make_classification
from repro.kernels.ops import (flatten_rows, trust_aggregate_global_flat,
                               trust_aggregate_global_tree, unflatten_row)
from repro.kernels.trust_aggregate import lane_block, padded_width

# the paper's §V fleet: 16 devices in 4 clusters, 784-200-10 MLP
DEVICES, CLUSTERS, DIM, HIDDEN, CLASSES = 16, 4, 784, 200, 10


def _paper_v_engine():
    key = jax.random.PRNGKey(0)
    data = make_classification(key, n=1024, dim=DIM, n_classes=CLASSES)
    parts = np.array_split(np.arange(1024), DEVICES)
    spec = FederationSpec(
        seed=0, local_batch=16, execution="scanned",
        fleet=FleetSpec(n_devices=DEVICES),
        clustering=api.ClusteringSpec(n_clusters=CLUSTERS),
        controller=ControllerSpec("fixed", {"a": 2}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("mlp", {"hidden": HIDDEN, "n_classes": CLASSES}))
    return Federation.from_spec(spec, data=data, parts=parts).engine


def _jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr nested in its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _jaxprs(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _jaxprs(sub)


# ops that move or relabel data without computing on it
PASS_THROUGH = {"reshape", "transpose", "convert_element_type", "copy",
                "squeeze", "expand_dims"}


def _source(var, producers):
    """The first op behind ``var`` that is not a pass-through (None for an
    input of the jaxpr or a literal)."""
    eqn = producers.get(var)
    while eqn is not None and eqn.primitive.name in PASS_THROUGH:
        eqn = producers.get(eqn.invars[0])
    return eqn


def test_scan_builds_the_member_matrix_once_at_the_kernel_width():
    eng = _paper_v_engine()
    M = int(eng.tables.member_table.shape[1])
    N = sum(l[0].size for l in jax.tree.leaves(eng.state.cluster_params))
    assert N == DIM * HIDDEN + HIDDEN + HIDDEN * CLASSES + CLASSES
    assert M != CLUSTERS                 # member rows tell from stack rows
    width = padded_width(M + CLUSTERS, N)
    assert width > N                     # the paper's N needs a tail

    pol = eng.controller.scan_policy()
    traced = eng._build_scan_fn(1, pol).trace(
        eng.state, eng._scan_times, pol.state, eng._scan_energy_start(),
        eng.tables)
    concats, member_pads, broadcast_feeds = [], [], []
    for jaxpr in _jaxprs(traced.jaxpr.jaxpr):
        producers = {o: e for e in jaxpr.eqns for o in e.outvars}
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pad" and eqn.invars[0].aval.shape[:1] == (M,):
                member_pads.append(eqn.invars[0].aval.shape)
            if name != "concatenate":
                continue
            shape = eqn.outvars[0].aval.shape
            if len(shape) != 2 or shape[0] != M or shape[1] < N:
                continue
            concats.append(shape)
            for v in eqn.invars:
                src = _source(v, producers)
                if (src is not None and src.primitive.name == "broadcast_in_dim"
                        and src.invars[0].aval.shape != ()):
                    broadcast_feeds.append(v.aval.shape)
    assert concats == [(M, width)]
    assert member_pads == []
    assert broadcast_feeds == []


def _pytrees(key, C=5, B=4):
    """Member stack, cluster model and cluster stack of a tiny model whose
    parameter count N = 1,000 is not a multiple of the lane block."""
    ks = jax.random.split(key, 6)
    row = {"w": jax.random.normal(ks[0], (20, 40)),
           "b": jax.random.normal(ks[1], (200,))}
    new = {"w": row["w"] + 0.1 * jax.random.normal(ks[2], (C, 20, 40)),
           "b": row["b"] + 0.1 * jax.random.normal(ks[3], (C, 200))}
    stack = {"w": jax.random.normal(ks[4], (B, 20, 40)),
             "b": jax.random.normal(ks[5], (B, 200))}
    return new, row, stack


def test_flat_entry_matches_the_pytree_entry_bit_for_bit():
    C, B, N = 5, 4, 1000
    new, row, stack = _pytrees(jax.random.PRNGKey(7), C, B)
    width = padded_width(C + B, N)
    assert width % lane_block(C + B, N) == 0 and width > N

    flat_new, upd = _flat_members(new, row, width)
    assert flat_new.shape == (C, width) and upd.shape == (C, N)
    np.testing.assert_array_equal(np.asarray(flat_new[:, N:]), 0.0)
    stacked = jax.tree.map(lambda l: jnp.broadcast_to(l, (C,) + l.shape),
                           row)
    np.testing.assert_array_equal(
        np.asarray(upd),
        np.asarray(flatten_rows(new) - flatten_rows(stacked)))
    np.testing.assert_array_equal(np.asarray(flat_new[:, :N]),
                                  np.asarray(flatten_rows(new)))

    mask = jnp.arange(C) < 3                    # two padded member rows
    w = jnp.asarray([0.5, 0.3, 0.2, 0.7, 0.9])  # padded rows weighted too
    gw = jax.nn.softmax(jnp.arange(B, dtype=jnp.float32))
    for c in (0, B - 1):
        got = trust_aggregate_global_flat(flat_new, w, mask, stack, gw, c,
                                          interpret=True)
        want = trust_aggregate_global_tree(new, w, mask, stack, gw, c,
                                           interpret=True)
        for k in want:
            assert got[k].shape == want[k].shape == stack[k].shape[1:]
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))


@pytest.mark.parametrize("width", [None, 1024])
def test_unflatten_row_inverts_flatten_rows(width):
    new, _, _ = _pytrees(jax.random.PRNGKey(3))
    flat = flatten_rows(new, width)
    assert flat.shape == (5, width or 1000)
    for i in (0, 4):
        back = unflatten_row(flat[i], new)
        for k in new:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(new[k][i]))
