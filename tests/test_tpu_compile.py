"""Compile rehearsal for TPU v5e: every Pallas kernel of the federation's
main path, compiled (``interpret=False``) for a described chip at the
paper's widths, plus the sharded divergence rebuild on a described
four-chip mesh. Nothing runs; the TPU compiler refuses what the chip
would refuse (misaligned blocks, unsupported casts), which interpret mode
never checks.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so the call must happen in
the worker that runs these tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.analysis.hlo_rules import collective_violations
from repro.kernels import dequant_kl, neighbor_mean, ops, pairwise_kl, soft_ce

# (N, R, C) of sc_like, pad_like and fmnist_like
WIDTHS = {"sc_like": (32, 240, 3), "pad_like": (28, 200, 2),
          "fmnist_like": (20, 400, 10)}
UPLOADS = 4     # rows of a delta / IVF strip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cases(n, r, c, sharding):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    u = UPLOADS
    codes = lambda rows: [spec((rows, r, c), jnp.uint8),  # noqa: E731
                          spec((rows, r)), spec((rows, r))]
    return {
        "pairwise_kl": (
            lambda x: pairwise_kl.pairwise_kl(x, interpret=False),
            [spec((n, r, c))]),
        "pairwise_kl_pair": (
            lambda a, b: pairwise_kl.pairwise_kl_pair(a, b,
                                                      interpret=False),
            [spec((u, r, c)), spec((n, r, c))]),
        "soft_ce": (
            lambda z, y: soft_ce.soft_ce(z, y, interpret=False),
            [spec((n, r, c)), spec((r,), jnp.int32)]),
        "neighbor_mean": (
            lambda i, w, p: neighbor_mean.neighbor_mean(i, w, p,
                                                        interpret=False),
            [spec((n, 8), jnp.int32), spec((n, 8)), spec((n, r, c))]),
        "neighbor_mean_dense": (
            lambda w, p: neighbor_mean.neighbor_mean_dense(w, p,
                                                           interpret=False),
            [spec((n, n)), spec((n, r, c))]),
        "int8_pairwise_kl": (
            lambda q, s, z: dequant_kl.int8_pairwise_kl(q, s, z,
                                                        interpret=False),
            codes(n)),
        "int8_pairwise_kl_pair": (
            lambda *a: dequant_kl.int8_pairwise_kl_pair(*a,
                                                        interpret=False),
            codes(u) + codes(n)),
    }


KERNELS = ("pairwise_kl", "pairwise_kl_pair", "soft_ce", "neighbor_mean",
           "neighbor_mean_dense", "int8_pairwise_kl", "int8_pairwise_kl_pair")


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    fn, args = _cases(*WIDTHS[width], one_chip)[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,resident", ((16384, True), (65536, False)))
def test_k_sparse_neighbor_mean_compiles_at_server_size(one_chip, n,
                                                        resident):
    """The K-sparse Eq. 5 at the N=16384 server's size keeps the whole
    (N, R·C) stack in VMEM; a stack past the VMEM ceiling compiles as
    an XLA gather instead."""
    r, c, k = 240, 3, 8
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (((n, k), jnp.int32), ((n, k), jnp.float32),
                         ((n, r, c), jnp.float32))]
    text = jax.jit(lambda i, w, p: neighbor_mean.neighbor_mean(
        i, w, p, interpret=False)).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == resident


def _entry(text: str):
    """The instructions of an optimized HLO module's entry computation:
    the values the program materializes (fused ones live elsewhere)."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return lines[start + 1:end]


def _selection_args(n, b, div_sharding, sharding):
    return [jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=div_sharding),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=sharding)]


def test_pool_selection_reads_div_in_place(one_chip):
    """The server's neighbour selection at N=16384 (bucket 64, k=8) reads
    the pool columns of ``div`` as stored: no (N,N) float32 value but the
    parameter (no transpose, no similarity matrix), and temp under
    64 MiB."""
    from repro.core import graph
    n, b, k = 16384, 64, 8
    compiled = graph._select_pool_div.lower(
        *_selection_args(n, b, one_chip, one_chip), k=k).compile()
    square = [l for l in _entry(compiled.as_text())
              if f"f32[{n},{n}]" in l.split(" = ", 1)[-1].split(" ", 1)[0]
              and " parameter(" not in l]
    assert square == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_nemotron_h_cohort_step_fits_one_chip(one_chip):
    """The ``nemotron-h`` cohort step at its published widths (one client:
    16 local and 240 reference series of 3000 samples, Adam state,
    params and state donated) compiles for a v5e within 15 GB of its
    16, so the chip run cannot fail for memory."""
    from repro.core import client
    from repro.models.zoo import build_zoo
    n, b, r, length, c = 1, 16, 240, 3000, 3
    zoo = build_zoo("nemotron-h", length, c)
    init_fn, apply_fn = zoo["nemotron-h"]
    opt = zoo.optimizers["nemotron-h"]

    def stacks():
        params = jax.vmap(init_fn)(jax.random.split(jax.random.key(0), n))
        return params, jax.vmap(opt.init)(params)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, state = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                                 jax.eval_shape(stacks))
    compiled = client.expert_cohort_step.lower(
        apply_fn, opt, params, state, spec((n, b, length)),
        spec((n, b), jnp.int32), spec((r, length)), spec((n, r, c)),
        spec((n,), jnp.bool_), rho=0.8, use_ref=True).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 4e9          # params and Adam donated
    assert total < 15e9, total


@pytest.fixture(scope="module")
def four_chips(topo):
    from repro.sharding import CLIENT_AXIS
    return Mesh(np.asarray(topo.devices), (CLIENT_AXIS,))


@pytest.mark.parametrize("kernel", ("soft_ce", "neighbor_mean",
                                    "neighbor_mean_dense"))
def test_replicated_kernel_compiles_on_four_chips(four_chips, kernel):
    """The sharded server grades and emits targets on row-sharded
    operands: ``ops`` runs those kernels replicated over the mesh."""
    from repro.sharding import CLIENT_AXIS
    rows = NamedSharding(four_chips, PartitionSpec(CLIENT_AXIS))
    fn, args = _cases(*WIDTHS["sc_like"], rows)[kernel]
    text = jax.jit(ops.replicated(fn, four_chips)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_rebuild_compiles_on_four_chips(four_chips):
    from repro.core import similarity
    from repro.sharding import CLIENT_AXIS

    mesh = four_chips
    n, r, c = WIDTHS["sc_like"]
    rows = NamedSharding(mesh, PartitionSpec(CLIENT_AXIS))
    whole = NamedSharding(mesh, PartitionSpec())
    fn = similarity._sharded_strip_fn(mesh, "pallas")
    text = fn.lower(jax.ShapeDtypeStruct((n, r, c), jnp.float32,
                                         sharding=rows),
                    jax.ShapeDtypeStruct((n, r, c), jnp.float32,
                                         sharding=whole)).compile().as_text()
    assert "tpu_custom_call" in text
    assert collective_violations("divergence_matrix[v5e:2x2]", text) == []


@pytest.mark.parametrize("n", (32, 16384))
def test_pool_selection_keeps_row_split_on_four_chips(four_chips, n):
    """The sharded rebuild hands the selection a row-split ``div``: each
    chip selects for its own rows, with no collective (no chip gathers
    the (N,N) matrix)."""
    from repro.core import graph
    from repro.sharding import CLIENT_AXIS
    rows = NamedSharding(four_chips, PartitionSpec(CLIENT_AXIS, None))
    whole = NamedSharding(four_chips, PartitionSpec())
    b = 16 if n == 32 else 64
    text = graph._select_pool_div.lower(
        *_selection_args(n, b, rows, whole), k=8).compile().as_text()
    assert collective_violations("select_pool_div[v5e:2x2]", text) == []


@pytest.mark.parametrize("env_dir", (None, "elsewhere"))
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """The environment's cache directory wins and nothing is set over it;
    without one the cache is the checkout's fixed ``.jax_cache``."""
    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = compile_cache.REPO_CACHE
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    try:
        assert compile_cache.enable_compile_cache() == want
        set_to = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env_dir is None:
        assert set_to == os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    else:
        assert set_to == was
