"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes, plus analytic invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import k_sparse, selection_matrix
from repro.kernels import ops, ref
from repro.kernels.pairwise_kl import pairwise_kl
from repro.kernels.soft_ce import soft_ce
from repro.kernels.neighbor_mean import neighbor_mean_dense

SHAPES = [(4, 8, 3), (7, 13, 5), (20, 100, 10), (32, 64, 2), (9, 50, 26)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _messengers(n, r, c, dtype, seed=0):
    logits = jax.random.normal(jax.random.key(seed), (n, r, c)) * 2.0
    return jax.nn.log_softmax(logits, -1).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_kl_matches_oracle(shape, dtype):
    n, r, c = shape
    logp = _messengers(n, r, c, dtype)
    got = pairwise_kl(logp, bn=8, bm=8, bk=32, interpret=True)
    want = ref.pairwise_kl_ref(logp)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_pairwise_kl_invariants():
    logp = _messengers(12, 30, 4, jnp.float32)
    d = np.asarray(ref.pairwise_kl_ref(logp))
    assert np.allclose(np.diag(d), 0.0, atol=1e-5)          # KL(p||p) = 0
    assert (d > -1e-5).all()                                 # KL >= 0
    # asymmetry: D is not symmetric in general
    assert not np.allclose(d, d.T, atol=1e-4)


def test_pairwise_kl_identical_clients():
    logp = _messengers(1, 20, 5, jnp.float32)
    stacked = jnp.tile(logp, (6, 1, 1))
    d = np.asarray(pairwise_kl(stacked, bn=8, bm=8, bk=16, interpret=True))
    assert np.allclose(d, 0.0, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_soft_ce_matches_oracle(shape, dtype):
    n, r, c = shape
    logits = (jax.random.normal(jax.random.key(1), (n, r, c)) * 3).astype(dtype)
    labels = jax.random.randint(jax.random.key(2), (r,), 0, c)
    got = soft_ce(logits, labels, bn=4, br=16, interpret=True)
    want = ref.soft_ce_ref(logits, labels)
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_soft_ce_perfect_prediction_low_loss():
    r, c = 40, 5
    labels = jax.random.randint(jax.random.key(3), (r,), 0, c)
    good = 10.0 * jax.nn.one_hot(labels, c)[None]            # confident right
    bad = 10.0 * jax.nn.one_hot((labels + 1) % c, c)[None]   # confident wrong
    g = np.asarray(ref.soft_ce_ref(jnp.concatenate([good, bad]), labels))
    assert g[0] < g[1]
    assert g[0] < 0.1 * r


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_neighbor_mean_matches_oracle(shape, dtype):
    n, r, c = shape
    probs = jnp.exp(_messengers(n, r, c, jnp.float32)).astype(dtype)
    w = jax.random.uniform(jax.random.key(4), (n, n))
    w = w / w.sum(1, keepdims=True)
    got = neighbor_mean_dense(w, probs, bn=8, bj=8, bk=32, interpret=True)
    want = ref.neighbor_mean_ref(w, probs)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_neighbor_mean_rows_are_distributions():
    n, r, c = 10, 20, 4
    probs = jnp.exp(_messengers(n, r, c, jnp.float32))
    w = jnp.eye(n)  # self-selection -> identity
    got = np.asarray(neighbor_mean_dense(w, probs, bn=8, bj=8, bk=16,
                                         interpret=True))
    np.testing.assert_allclose(got, np.asarray(probs), atol=1e-5)
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-4)


SPARSE_CASES = ("short_rows", "padded_repeat", "k0", "ragged_n")


def _sparse_graph(case):
    """(graph, probs) for one K-sparse Eq. 5 case, ids drawn from a
    4-member pool as SQMD's are."""
    n, k = {"short_rows": (12, 4), "padded_repeat": (10, 3),
            "k0": (9, 0), "ragged_n": (13, 8)}[case]
    r, c = 24, 5
    key = jax.random.key(SPARSE_CASES.index(case))
    kp, kn, kc = jax.random.split(key, 3)
    probs = jnp.exp(_messengers(n, r, c, jnp.float32, seed=31))
    nbrs = jax.random.randint(kn, (n, k), 0, n)
    count = jnp.full((n, 1), k)
    if case == "short_rows":      # rows realize 0..k edges
        count = jax.random.randint(kc, (n, 1), 0, k + 1)
    if case == "padded_repeat":   # unrealized slots repeat the first id
        count = jnp.full((n, 1), 1)
        nbrs = jnp.tile(nbrs[:, :1], (1, k))
    valid = jnp.arange(k)[None, :] < count
    w = jnp.where(valid, 1.0 / jnp.maximum(count, 1), 0.0)
    g = k_sparse(nbrs.astype(jnp.int32), w.astype(jnp.float32),
                 jnp.zeros((n, n)), jnp.ones((n,), bool))
    return g, probs


@pytest.mark.parametrize("case", SPARSE_CASES)
@pytest.mark.parametrize("backend", ("jnp", "interpret", "pallas"))
def test_neighbor_mean_sparse_matches_dense_oracle(backend, case):
    """K-sparse Eq. 5 on every backend equals the dense oracle over the
    graph's selection matrix; ``pallas`` is the compiled kernel and
    needs a TPU."""
    if backend == "pallas" and jax.devices()[0].platform != "tpu":
        pytest.skip("the compiled kernel needs a TPU")
    g, probs = _sparse_graph(case)
    blocks = {} if backend == "jnp" else {"bn": 4}
    got = ops.neighbor_mean(g.neighbors, g.edge_weights, probs,
                            backend=backend, **blocks)
    with jax.default_matmul_precision("highest"):   # fp32 on a TPU too
        want = ref.neighbor_mean_ref(selection_matrix(g), probs)
    assert got.shape == probs.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_ops_dispatch_backends_agree():
    logp = _messengers(8, 16, 4, jnp.float32)
    labels = jax.random.randint(jax.random.key(5), (16,), 0, 4)
    w = jnp.full((8, 8), 1.0 / 8)
    nbrs = jax.random.randint(jax.random.key(6), (8, 3), 0, 8)
    from repro.core.wire import Int8
    wire8 = Int8().encode(logp).arrays
    for fn, args in [(ops.pairwise_kl, (logp,)),
                     (ops.soft_ce, (logp, labels)),
                     (ops.neighbor_mean_dense, (w, jnp.exp(logp))),
                     (ops.neighbor_mean, (nbrs, w[:, :3], jnp.exp(logp))),
                     (ops.int8_pairwise_kl,
                      (wire8["q"], wire8["scale"], wire8["zp"]))]:
        a = fn(*args, backend="jnp")
        b = fn(*args, backend="interpret")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
