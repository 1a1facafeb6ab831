"""Messenger wire subsystem tests: codec registry + round trips, the
dense32 bit-identity guarantee (pinned sync trajectory), bandwidth
accounting end-to-end, the fused int8 dequant->KL kernel, checkpointed
codec names, and a federate-CLI smoke run."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FederationConfig, FederationEngine, ServerBus,
                        as_codec, bytes_per_messenger, decode, encode,
                        get_codec, init_server, payload_bytes,
                        registered_codecs, sqmd)
from repro.core.wire import Dense32, Int8, Payload, TopK
from repro.data import make_splits, pad_like
from repro.kernels import ops, ref
from repro.models.mlp import hetero_mlp_zoo


def _messengers(n, r, c, seed=0):
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * 3.0
    return jax.nn.log_softmax(z, -1)


# --- registry / coercion --------------------------------------------------

def test_codec_registry():
    assert set(registered_codecs()) >= {"dense32", "dense16", "int8",
                                        "topk"}
    assert get_codec("int8") is Int8
    assert isinstance(as_codec(None), Dense32)
    assert isinstance(as_codec("dense32"), Dense32)
    assert as_codec(TopK(k=3)).k == 3
    assert as_codec("topk:5").k == 5
    with pytest.raises(KeyError, match="unknown codec"):
        as_codec("no-such-codec")
    with pytest.raises(ValueError, match="no argument"):
        as_codec("int8:3")
    with pytest.raises(ValueError, match="k must"):
        TopK(k=0)
    with pytest.raises(ValueError, match="domain"):
        encode("dense32", _messengers(2, 3, 4), domain="nonsense")


# --- byte accounting is honest --------------------------------------------

def test_payload_bytes_per_codec():
    n, r, c = 5, 20, 32
    logp = _messengers(n, r, c)
    fp32 = n * r * c * 4
    assert payload_bytes(encode("dense32", logp)) == fp32
    assert payload_bytes(encode("dense16", logp)) == fp32 // 2
    # int8: C code bytes + bf16 scale + bf16 zero-point per row
    assert payload_bytes(encode("int8", logp)) == n * r * (c + 4)
    # topk: k (int16 idx + bf16 val) + bf16 tail per row
    assert payload_bytes(encode("topk:4", logp)) == n * r * (4 * 4 + 2)
    # acceptance: int8 cuts per-messenger bytes >= 3.5x vs fp32 at C >= 32
    ratio = (r * c * 4) / bytes_per_messenger(encode("int8", logp))
    assert ratio >= 3.5


# --- dense32 is the bit-identical oracle ----------------------------------

def test_dense32_roundtrip_is_identity():
    logp = _messengers(4, 10, 6)
    payload = encode("dense32", logp)
    out = decode(payload)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logp))
    assert out is payload.arrays["data"]       # no copy, no cast


def test_payload_is_a_pytree():
    logp = _messengers(3, 8, 5)
    payload = encode("int8", logp)
    leaves, treedef = jax.tree.flatten(payload)
    back = jax.tree.unflatten(treedef, leaves)
    assert back.codec == "int8" and back.shape == payload.shape
    # flows through jit
    dec = jax.jit(decode)(payload)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(decode(payload)), atol=1e-6)


# --- lossy round trips stay in-domain -------------------------------------

@pytest.mark.parametrize("name", ["dense16", "int8", "topk", "topk:3"])
def test_lossy_decode_is_normalized(name):
    logp = _messengers(6, 12, 7, seed=3)
    dec = decode(encode(name, logp))
    # normalized log-probs: logsumexp == 0
    np.testing.assert_allclose(
        np.asarray(jax.nn.logsumexp(dec, -1)), 0.0, atol=1e-5)
    probs = jnp.exp(logp)
    dec_p = decode(encode(name, probs, domain="prob"))
    np.testing.assert_allclose(np.asarray(dec_p.sum(-1)), 1.0, atol=1e-5)
    assert (np.asarray(dec_p) >= 0).all()


def test_int8_roundtrip_preserves_neighbor_selection():
    """The acceptance fidelity claim at unit scale: the SQMD graph built
    from int8-decoded messengers picks (nearly) the oracle's neighbors."""
    from repro.core.graph import select_neighbors
    n, r, c, k = 24, 40, 32, 4
    logp = _messengers(n, r, c, seed=7)
    cand = jnp.ones((n,), bool)
    div0 = ops.pairwise_kl(logp, backend="jnp")
    g0 = select_neighbors(div0, cand, k)
    dec = decode(encode("int8", logp))
    div1 = ops.pairwise_kl(dec, backend="jnp")
    g1 = select_neighbors(div1, cand, k)
    a, b = np.asarray(g0.neighbors), np.asarray(g1.neighbors)
    overlap = np.mean([len(set(a[i]) & set(b[i])) / k for i in range(n)])
    assert overlap >= 0.9


# --- the fused int8 dequant->KL kernel ------------------------------------

@pytest.mark.parametrize("shape", [(4, 8, 3), (7, 13, 5), (12, 40, 10)])
def test_int8_pairwise_kl_matches_oracle(shape):
    n, r, c = shape
    payload = encode("int8", _messengers(n, r, c, seed=1))
    q, s, z = (payload.arrays["q"], payload.arrays["scale"],
               payload.arrays["zp"])
    got = ops.int8_pairwise_kl(q, s, z, backend="interpret", bn=4, bm=8,
                               br=8)
    want = ref.int8_pairwise_kl_ref(q, s, z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # and the oracle itself == dense KL of the codec's decode
    dense = ops.pairwise_kl(decode(payload), backend="jnp")
    np.testing.assert_allclose(np.asarray(want), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


def test_int8_payload_pairwise_kl_helper():
    payload = encode("int8", _messengers(6, 10, 4, seed=2))
    d = Int8().pairwise_kl(payload)
    assert d.shape == (6, 6)
    assert np.allclose(np.diag(np.asarray(d)), 0.0, atol=1e-4)
    with pytest.raises(ValueError, match="log-domain"):
        Int8().pairwise_kl(encode("int8", jnp.full((2, 3, 4), 0.25),
                                  domain="prob"))


# --- engine integration ---------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    """EXACTLY the pinned-parity fixture of tests/test_runtime.py — the
    PINNED_* values below were captured at this scale."""
    ds = pad_like(samples_per_client=30, ref_size=30, length=24)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    return ds, splits, zoo, assignment


# The single source of truth for the pinned trajectory lives in
# tests/test_runtime.py — the wire refactor must reproduce it
# bit-for-bit under explicit uplink/downlink="dense32".
from test_runtime import PINNED_MEAN_ACC, PINNED_VAL_ACC  # noqa: E402


def test_dense32_wire_is_bit_identical_to_pinned_trajectory(setup):
    ds, splits, zoo, assignment = setup
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(rounds=4, batch_size=8, eval_every=2,
                                uplink="dense32", downlink="dense32"),
        seed=7)
    h = engine.fit(splits)
    np.testing.assert_allclose(h.mean_acc, PINNED_MEAN_ACC, rtol=0,
                               atol=1e-9)
    np.testing.assert_allclose(h.val_acc, PINNED_VAL_ACC, rtol=0, atol=1e-9)
    # bandwidth accounting rode along: every round uploads N fp32
    # messengers and downlinks N fp32 target stacks
    n, r, c = ds.n_clients, 30, ds.n_classes
    per = r * c * 4
    assert h.bytes_up[-1] == pytest.approx(4 * n * per)
    assert h.bytes_down[-1] == pytest.approx(4 * n * per)
    np.testing.assert_allclose(engine.bus.bytes_up, np.full(n, 4 * per))


def test_lossy_wire_trains_and_meters(setup):
    """int8 uplink + topk downlink: training stays finite, and the meter
    records exactly the codec's payload bytes."""
    ds, splits, zoo, assignment = setup
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(rounds=2, batch_size=8, eval_every=1,
                                uplink="int8", downlink="topk:1"),
        seed=7)
    h = engine.fit(splits)
    assert np.isfinite(h.mean_acc).all()
    r, c = 30, ds.n_classes
    assert engine.bus.bytes_up[0] == pytest.approx(2 * r * (c + 4))
    assert h.bytes_up[-1] == pytest.approx(
        2 * ds.n_clients * r * (c + 4))            # not 4C fp32 bytes
    assert h.bytes_down[-1] > 0


def test_config_rejects_unknown_codec():
    with pytest.raises(ValueError, match="uplink"):
        FederationConfig(uplink="no-such-codec")
    with pytest.raises(ValueError, match="downlink"):
        FederationConfig(downlink="dense64")


def test_bus_meters_superseded_uploads(setup):
    """An out-of-order upload is superseded by newer content but still
    burned the link — its bytes count."""
    from repro.core import Federation
    from repro.core.policies import as_policy
    from repro.optim import sgd
    n, r, c = 4, 6, 3
    fed = Federation(cohorts=[], server=init_server(n, r, c),
                     protocol=sqmd(q=n, k=2), ref_x=jnp.zeros((r, 4)),
                     ref_y=jnp.asarray(np.arange(r) % c),
                     optimizer=sgd(0.1), n_clients=n)
    bus = ServerBus(fed, as_policy(sqmd(q=4, k=2)), trigger="every-upload")
    only2 = np.zeros(4, bool)
    only2[2] = True
    msg = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(0), (n, r, c)), -1)
    bus.deliver(5.0, msg, only2, produced_at=4.0)
    bus.deliver(6.0, msg, only2, produced_at=2.0)   # superseded
    per = r * c * 4
    assert bus.bytes_up[2] == pytest.approx(2 * per)
    assert bus.bytes_up[[0, 1, 3]].sum() == 0


def test_checkpoint_stores_codec_names(tmp_path, setup):
    from repro.checkpoint import (restore_federation, save_federation,
                                  save_pytree)
    ds, splits, zoo, assignment = setup
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(rounds=1, batch_size=8, eval_every=1,
                                uplink="int8", downlink="topk"),
        seed=7)
    engine.run_round(0)
    save_federation(str(tmp_path), engine.fed, step=1)
    fed2 = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(rounds=1), seed=9).fed
    restore_federation(str(tmp_path), fed2)
    assert fed2.uplink == "int8" and fed2.downlink == "topk"
    # legacy file without the wire record restores as dense32
    from repro.checkpoint.io import restore_pytree
    tree = restore_pytree(str(tmp_path / "step_1.msgpack"))
    del tree["wire"]
    save_pytree(str(tmp_path / "legacy" / "step_1.msgpack"), tree)
    restore_federation(str(tmp_path / "legacy"), fed2)
    assert fed2.uplink == "dense32" and fed2.downlink == "dense32"


# --- the federate CLI (previously zero coverage) --------------------------

def test_federate_cli_event_clock_with_lossy_wire(monkeypatch, capsys):
    from repro.kernels.backend import ENV_VAR
    from repro.launch import federate
    monkeypatch.setenv(ENV_VAR, "jnp")
    monkeypatch.setattr("sys.argv", [
        "federate", "--rounds", "2", "--batch", "4", "--eval-every", "1",
        "--samples-per-client", "12", "--ref-size", "9",
        "--clock", "event", "--uplink", "int8", "--downlink", "topk"])
    federate.main()
    out = capsys.readouterr().out
    summary = json.loads(out[out.index("{"):])
    assert summary["uplink"] == "int8"
    assert summary["downlink"] == "topk"
    assert summary["bytes_up"] > 0 and summary["bytes_down"] > 0
    assert np.isfinite(summary["final_acc"])
