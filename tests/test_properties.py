"""Property-based tests: topology invariants and chunked-CE equivalence.

Complements the example-based suites with randomized coverage (the
reference's topology_test.go checks a handful of fixed sizes; these check
structural invariants for arbitrary cluster shapes).
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from kungfu_tpu.plan.graph import Graph  # noqa: E402
from kungfu_tpu.plan.topology import Strategy, generate  # noqa: E402
from testutil import peers_on  # noqa: E402


def peers_strategy():
    """Random multi-host peer lists: 1-4 hosts x 1-4 slots each."""
    return st.lists(st.integers(min_value=1, max_value=4),
                    min_size=1, max_size=4)


def build_peers(slots_per_host):
    return peers_on([(f"10.0.0.{h + 1}", slots)
                     for h, slots in enumerate(slots_per_host)])


def reachable_roots(g: Graph):
    """For each node, the self-loop root its reduce path terminates at
    (father-following; None on a cycle)."""
    father = g.to_forest_array()
    out = []
    for i in range(g.n):
        seen, j = set(), i
        while father[j] != j:
            if j in seen:
                out.append(None)
                break
            seen.add(j)
            j = father[j]
        else:
            out.append(j)
    return out


@settings(max_examples=40, deadline=None)
@given(peers_strategy(), st.sampled_from(list(Strategy)))
def test_generated_graphs_are_rooted_spanning_forests(slots, strategy):
    peers = build_peers(slots)
    pairs = generate(strategy, peers)
    assert pairs, strategy
    n = len(peers)
    for pair in pairs:
        # every reduce graph drains every node into exactly one root set,
        # and the bcast graph is its reverse — so reduce+bcast reaches all
        roots = reachable_roots(pair.reduce_graph)
        assert all(r is not None for r in roots), (strategy, slots)
        for i, r in enumerate(roots):
            assert pair.reduce_graph.has_self_loop(r), (strategy, i, r)
        # reverse-graph property: edges flip
        fwd = set(pair.reduce_graph.edges())
        rev = set(pair.bcast_graph.edges())
        assert rev == {(b, a) for a, b in fwd}


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=12))
def test_forest_array_roundtrip(n):
    rng = np.random.RandomState(n)
    # random forest: each node points at a lower index or itself
    father = [int(rng.randint(0, i + 1)) for i in range(n)]
    g = Graph.from_forest_array(father)
    assert g.to_forest_array() == father


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=3),    # batch
       st.integers(min_value=1, max_value=6),    # seq
       st.integers(min_value=1, max_value=5),    # d_model (pre-scale)
       st.sampled_from([16, 32, 64]),            # vocab
       st.sampled_from([8, 16, 32]))             # chunk
def test_chunked_ce_equals_dense(b, t, d, vocab, chunk):
    if vocab % chunk:
        chunk = vocab
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy

    rng = np.random.RandomState(b * 100 + t * 10 + d)
    x = jnp.asarray(rng.randn(b, t, 4 * d).astype(np.float32))
    w = jnp.asarray((rng.randn(4 * d, vocab) * 0.3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32)

    dense = lambda a: optax.softmax_cross_entropy_with_integer_labels(
        jnp.einsum("btd,dv->btv", a, w), y)
    chunked = lambda a: chunked_cross_entropy(a, w, y, chunk)

    # one compiled program an example: op by op, each new shape compiles
    # every primitive apart, eight times as long
    @jax.jit
    def both(x):
        return (chunked(x), dense(x),
                jax.grad(lambda a: chunked(a).sum())(x),
                jax.grad(lambda a: dense(a).sum())(x))

    got, want, gx_c, gx_d = both(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx_c), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-5)
