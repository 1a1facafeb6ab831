"""Tests for the registered model zoo: the family registry, assignment
parsing (round-robin derived from the zoo size, weighted Table-I shares),
per-architecture forward/grad sanity, mixed-architecture federations
end-to-end under both engines, checkpoint round-trips with typed zoo
mismatches, cross-arch wire parity, and the MLP-only pinned-trajectory
guarantee (the registry path is bit-identical to ``hetero_mlp_zoo``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncFederationEngine, FederationConfig,
                        FederationEngine, sqmd)
from repro.data import make_splits, pad_like
from repro.models.zoo import (DEFAULT_ZOO, FamilySpec, Zoo, as_family,
                              build_zoo, get_family, parse_assignment,
                              register_family, registered_families)
from repro.optim import sgd

MIXED_ZOO = "mlp-s,resnet,transformer,ssm"
CFG = dict(rounds=2, batch_size=8, eval_every=1)


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_cache():
    # The mixed-zoo engines compile large vmapped transformer/ssm modules;
    # stacked on a few hundred suite tests' worth of resident executables,
    # XLA's CPU backend_compile can segfault. Drop the accumulated caches
    # so this module starts from the same state it sees standalone (the
    # benchmarks do the same between sweep sizes).
    jax.clear_caches()


@pytest.fixture(scope="module")
def setup_small():
    ds = pad_like(samples_per_client=16, ref_size=16, length=16)
    splits = make_splits(ds, seed=0)
    return ds, splits


# --- registry --------------------------------------------------------------

def test_registry_lists_all_architectures():
    fams = registered_families()
    assert set(DEFAULT_ZOO) <= set(fams)
    assert {"resnet", "transformer", "ssm", "rglru"} <= set(fams)
    assert fams == tuple(sorted(fams))


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @register_family("mlp-s")
        def _dup(in_dim, n_classes):  # pragma: no cover
            raise AssertionError


def test_get_family_unknown_lists_known():
    with pytest.raises(KeyError, match="mlp-s"):
        get_family("mlp-xxl")


def test_as_family_coerces_and_passes_through():
    spec = get_family("resnet")
    assert as_family("resnet") is spec
    assert as_family(spec) is spec
    assert isinstance(spec, FamilySpec)


def test_per_family_default_optimizers():
    from repro.optim.optimizers import AdamState, SGDState
    zoo = build_zoo(MIXED_ZOO, 16, 3)
    assert isinstance(zoo, Zoo)
    probe = {"w": jnp.zeros((2,))}
    # MLP tiers + resnet default to momentum-SGD; the sequence families
    # (adapter + mixer) default to adam
    for fam, state_t in (("mlp-s", SGDState), ("resnet", SGDState),
                         ("transformer", AdamState), ("ssm", AdamState)):
        assert isinstance(zoo.optimizers[fam].init(probe), state_t), fam
    assert zoo.optimizers["mlp-s"].init(probe).momentum is not None


def test_build_zoo_rejects_bad_specs():
    with pytest.raises(ValueError, match="duplicate"):
        build_zoo("mlp-s,mlp-s", 16, 3)
    with pytest.raises(ValueError, match="zero families"):
        build_zoo(",", 16, 3)
    with pytest.raises(KeyError, match="registered"):
        build_zoo("mlp-s,convnext", 16, 3)


@pytest.mark.parametrize("fam", registered_families())
def test_every_family_forward_and_grad(fam):
    """Each registered family initializes, classifies a flat healthcare
    feature batch, and yields finite grads — including the sequence
    adapters (transformer/ssm/rglru) and the 1-D ResNet."""
    feat, classes = 24, 3
    init_fn, apply_fn = get_family(fam).builder(feat, classes)
    params = init_fn(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (5, feat))
    logits = apply_fn(params, x)
    assert logits.shape == (5, classes)
    assert bool(jnp.isfinite(logits).all())

    def loss(p):
        lp = jax.nn.log_softmax(apply_fn(p, x), -1)
        return -lp[:, 0].mean()

    grads = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert flat and all(bool(jnp.isfinite(g).all()) for g in flat)


# --- assignment parsing ----------------------------------------------------

def test_default_assignment_derives_from_zoo_size():
    """The round-robin modulus is len(zoo), never a hard-coded 3 — the
    launch CLIs used to do ``i % 3`` and silently starved family #4."""
    four = ["a", "b", "c", "d"]
    got = parse_assignment(None, four, 10)
    assert got == [four[i % 4] for i in range(10)]
    assert set(got) == set(four)        # family #4 actually gets clients
    two = parse_assignment(None, ["x", "y"], 5)
    assert two == ["x", "y", "x", "y", "x"]


def test_bare_list_round_robins_the_listed_families():
    got = parse_assignment("mlp-s,ssm", ["mlp-s", "ssm", "resnet"], 4)
    assert got == ["mlp-s", "ssm", "mlp-s", "ssm"]


def test_weighted_assignment_counts_and_determinism():
    names = ["a", "b", "c"]
    got = parse_assignment("a:0.5,b:0.25,c:0.25", names, 8)
    assert got.count("a") == 4 and got.count("b") == 2 \
        and got.count("c") == 2
    assert got == parse_assignment("a:0.5,b:0.25,c:0.25", names, 8)
    # prefix-stable: growing the federation never reshuffles who has what
    longer = parse_assignment("a:0.5,b:0.25,c:0.25", names, 16)
    assert longer[:8] == got


def test_assignment_error_cases():
    names = ["a", "b"]
    with pytest.raises(ValueError, match="not in the zoo"):
        parse_assignment("a,z", names, 4)
    with pytest.raises(ValueError, match="mixes weighted and bare"):
        parse_assignment("a:0.5,b", names, 4)
    with pytest.raises(ValueError, match="bad weight"):
        parse_assignment("a:lots,b:1", names, 4)
    with pytest.raises(ValueError, match="must be > 0"):
        parse_assignment("a:0,b:1", names, 4)
    with pytest.raises(ValueError, match="listed twice"):
        parse_assignment("a:1,a:1", names, 4)
    with pytest.raises(ValueError, match="2 entries"):
        parse_assignment(["a", "b"], names, 3)
    with pytest.raises(ValueError, match="not in the zoo"):
        parse_assignment(["a", "z", "a"], names, 3)


# --- mixed-architecture federations end-to-end -----------------------------

def _mixed_engine(ds, splits, seed=3, devices=None, **cfg):
    zoo = build_zoo(MIXED_ZOO, ds.feature_len, ds.n_classes)
    spec = "mlp-s:0.4,resnet:0.3,transformer:0.2,ssm:0.1"
    return FederationEngine.build(
        ds, splits, zoo, spec, sqmd(q=8, k=4),
        config=FederationConfig(devices=devices, **(cfg or CFG)),
        seed=seed)


def test_mixed_federation_trains_sync(setup_small):
    ds, splits = setup_small
    engine = _mixed_engine(ds, splits, **CFG)
    fams = [c.family_name for c in engine.fed.cohorts]
    assert fams == ["mlp-s", "resnet", "transformer", "ssm"]
    # weighted shares realized over 28 clients; every family non-empty
    sizes = {c.family_name: c.n_clients for c in engine.fed.cohorts}
    assert sizes["mlp-s"] > sizes["ssm"] >= 1
    assert sum(sizes.values()) == ds.n_clients
    # per-family optimizers rode in from the zoo registry: the cohort
    # states are a MIX of SGD and Adam
    states = {c.family_name: type(c.opt_state).__name__
              for c in engine.fed.cohorts}
    assert states["mlp-s"] == "SGDState"
    assert states["resnet"] == "SGDState"
    assert states["transformer"] == "AdamState"
    assert states["ssm"] == "AdamState"
    h = engine.fit(splits)
    assert np.isfinite(h.mean_acc).all()
    assert h.mean_acc[-1] > 1.0 / ds.n_classes - 0.05


def test_mixed_federation_same_seed_deterministic(setup_small):
    ds, splits = setup_small
    h1 = _mixed_engine(ds, splits, **CFG).fit(splits)
    h2 = _mixed_engine(ds, splits, **CFG).fit(splits)
    np.testing.assert_allclose(h1.mean_acc, h2.mean_acc, rtol=0, atol=0)
    np.testing.assert_allclose(h1.val_acc, h2.val_acc, rtol=0, atol=0)


def test_mixed_federation_trains_async(setup_small):
    ds, splits = setup_small
    zoo = build_zoo(MIXED_ZOO, ds.feature_len, ds.n_classes)
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, None, sqmd(q=8, k=4),
        config=FederationConfig(**CFG), seed=3)
    assert {c.family_name for c in engine.fed.cohorts} \
        == set(MIXED_ZOO.split(","))
    h = engine.fit(splits, until=2.0)
    assert np.isfinite(h.mean_acc).all()


def test_explicit_optimizer_overrides_family_defaults(setup_small):
    """An engine-level ``optimizer=`` wins over every per-family default
    (the pre-zoo contract: one optimizer for the whole federation)."""
    ds, splits = setup_small
    zoo = build_zoo("mlp-s,transformer", ds.feature_len, ds.n_classes)
    engine = FederationEngine.build(
        ds, splits, zoo, None, sqmd(q=8, k=4),
        config=FederationConfig(**CFG), optimizer=sgd(0.01), seed=3)
    for coh in engine.fed.cohorts:
        # even the transformer cohort (family default: adam) carries the
        # explicit momentum-less SGD state
        assert type(coh.opt_state).__name__ == "SGDState"
        assert coh.opt_state.momentum is None


# --- cross-arch wire parity ------------------------------------------------

def test_wire_traffic_is_architecture_blind(setup_small):
    """The server-facing traffic contract: same codec, same (N, R, C)
    payload geometry, same bytes per messenger, normalized log-prob rows
    — whether the cohorts are MLP-only or a 4-architecture mix."""
    from repro.core import wire
    ds, splits = setup_small
    on = np.ones(ds.n_clients, bool)

    mixed = _mixed_engine(ds, splits, **CFG)
    mlp = FederationEngine.build(
        ds, splits, build_zoo(None, ds.feature_len, ds.n_classes), None,
        sqmd(q=8, k=4), config=FederationConfig(**CFG), seed=3)
    pay_mixed = mixed.clients.collect_messengers(on)
    pay_mlp = mlp.clients.collect_messengers(on)

    r = int(mixed.fed.ref_x.shape[0])
    assert pay_mixed.codec == pay_mlp.codec
    assert pay_mixed.shape == pay_mlp.shape \
        == (ds.n_clients, r, ds.n_classes)
    assert wire.bytes_per_messenger(pay_mixed) \
        == wire.bytes_per_messenger(pay_mlp)
    logp = wire.decode(pay_mixed)
    # every row is a normalized log-distribution, arch notwithstanding
    np.testing.assert_allclose(
        np.asarray(jax.scipy.special.logsumexp(logp, axis=-1)),
        np.zeros((ds.n_clients, r)), atol=1e-5)


# --- pinned trajectory (registry path == hetero_mlp_zoo, bit for bit) -----

def test_mlp_zoo_reproduces_pinned_trajectory():
    """build_zoo(None) + default assignment IS the legacy
    ``hetero_mlp_zoo`` + ``i % 3`` federation: the pinned History from
    test_runtime reproduces exactly through the registry path."""
    from tests.test_runtime import PINNED_MEAN_ACC, PINNED_VAL_ACC
    ds = pad_like(samples_per_client=30, ref_size=30, length=24)
    splits = make_splits(ds, seed=0)
    zoo = build_zoo(None, ds.feature_len, ds.n_classes)
    engine = FederationEngine.build(
        ds, splits, zoo, None, sqmd(q=8, k=4),
        config=FederationConfig(rounds=4, batch_size=8, eval_every=2),
        seed=7)
    h = engine.fit(splits)
    np.testing.assert_allclose(h.mean_acc, PINNED_MEAN_ACC, rtol=0,
                               atol=1e-9)
    np.testing.assert_allclose(h.val_acc, PINNED_VAL_ACC, rtol=0,
                               atol=1e-9)


# --- checkpoint round-trips ------------------------------------------------

def test_mixed_arch_checkpoint_roundtrip(tmp_path, setup_small):
    from repro.checkpoint import restore_federation, save_federation
    ds, splits = setup_small
    engine = _mixed_engine(ds, splits, **CFG)
    engine.run_round(0)
    save_federation(str(tmp_path), engine.fed, step=1)

    fresh = _mixed_engine(ds, splits, seed=11, **CFG)
    step = restore_federation(str(tmp_path), fresh.fed)
    assert step == 1
    for a, b in zip(engine.fed.cohorts, fresh.fed.cohorts):
        assert a.family_name == b.family_name
        for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                          jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        # mixed optimizer states round-trip too (SGD + Adam cohorts)
        assert jax.tree_util.tree_structure(a.opt_state) \
            == jax.tree_util.tree_structure(b.opt_state)


def test_checkpoint_zoo_mismatch_names_the_family(tmp_path, setup_small):
    """Restoring into a federation whose zoo lacks a checkpointed family
    fails with a typed error NAMING the family — before any state is
    partially assigned."""
    from repro.checkpoint import (ZooMismatchError, restore_federation,
                                  save_federation)
    ds, splits = setup_small
    engine = _mixed_engine(ds, splits, **CFG)
    save_federation(str(tmp_path), engine.fed, step=1)

    zoo3 = build_zoo("mlp-s,resnet,transformer", ds.feature_len,
                     ds.n_classes)
    other = FederationEngine.build(
        ds, splits, zoo3, None, sqmd(q=8, k=4),
        config=FederationConfig(**CFG), seed=3)
    before = [np.asarray(la).copy() for c in other.fed.cohorts
              for la in jax.tree_util.tree_leaves(c.params)]
    with pytest.raises(ZooMismatchError, match="ssm"):
        restore_federation(str(tmp_path), other.fed)
    # ZooMismatchError subclasses ValueError for legacy except-clauses
    assert issubclass(ZooMismatchError, ValueError)
    after = [np.asarray(la) for c in other.fed.cohorts
             for la in jax.tree_util.tree_leaves(c.params)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)   # nothing partially applied


# --- sharded tiny buckets (the 8-device CI lane) ---------------------------

@pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the sharding CI lane)")
def test_mixed_federation_sharded_matches_single_device(setup_small):
    """At 8 devices the small cohorts land on device SUBSETS (a 3-client
    ssm cohort gets a 3-device mesh) and the trajectory is bit-identical
    to the single-device run."""
    from repro.sharding import cohort_mesh
    ds, splits = setup_small
    base = _mixed_engine(ds, splits, **CFG)
    h0 = base.fit(splits)
    engine = _mixed_engine(ds, splits, devices=8, **CFG)
    meshes = {c.family_name: c.sharding.mesh.devices.size
              for c in engine.fed.cohorts}
    assert meshes["mlp-s"] == 8            # 11 clients -> full mesh
    assert meshes["ssm"] == 3              # 3 clients -> 3-device submesh
    assert all(m <= 8 for m in meshes.values())
    h8 = engine.fit(splits)
    np.testing.assert_allclose(h8.mean_acc, h0.mean_acc, rtol=0, atol=0)
    np.testing.assert_allclose(h8.val_acc, h0.val_acc, rtol=0, atol=0)
    # cohort_mesh never exceeds the cohort's client count
    assert cohort_mesh(engine.mesh, 2).devices.size == 2
    assert cohort_mesh(engine.mesh, 100) is engine.mesh


# --- the launch CLIs -------------------------------------------------------

def test_federate_cli_accepts_zoo_and_assignment(monkeypatch, capsys):
    import json
    from repro.kernels.backend import ENV_VAR
    from repro.launch import federate
    monkeypatch.setenv(ENV_VAR, "jnp")
    monkeypatch.setattr("sys.argv", [
        "federate", "--rounds", "1", "--batch", "4", "--eval-every", "1",
        "--samples-per-client", "12", "--ref-size", "9",
        "--zoo", "mlp-s,rglru", "--assignment", "mlp-s:0.75,rglru:0.25"])
    federate.main()
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    assert summary["zoo"] == "mlp-s,rglru"
    assert summary["assignment"] == "mlp-s:0.75,rglru:0.25"
    assert np.isfinite(summary["final_acc"])


def test_federate_cli_rejects_unknown_family(monkeypatch, capsys):
    from repro.launch import federate
    monkeypatch.setattr("sys.argv", ["federate", "--zoo", "mlp-s,vgg"])
    with pytest.raises(SystemExit):
        federate.main()
    assert "registered" in capsys.readouterr().err
