"""The ``nemotron-h`` client family against its plain reference
(``repro.models.ref_nemotron_h``) at a small size on the CPU: d_model 32,
8 routed experts of which a layer holds 2, SSD chunks of 8 over 24 tokens
(three chunks), seeded random weights.

Tolerances: the program and the reference both run in float32 at HIGHEST
precision here and differ only in the order of their sums (chunked SSD
against the recurrence, batched expert matmuls against per-token sums), so
forwards agree to 1e-5 relative and gradients, one more pass of such sums,
to 1e-4. Token-choice counts are integers and must agree exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import cohort_step, expert_cohort_step
from repro.models import ffn, ref_nemotron_h as ref, ssm
from repro.models.zoo import (NEMOTRON_H, ExpertFamilyApply, build_zoo,
                              nemotron_h_family)
from repro.optim import adam

PATCH, TOKENS, CLASSES = 5, 24, 3
FEAT = PATCH * TOKENS
TINY = dataclasses.replace(
    NEMOTRON_H, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=16,
    shared_d_ff=24, n_experts=8, moe_top_k=3, experts_held=2,
    ssm_state=4, ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_chunk=8)
FWD_TOL = 1e-5      # float32 sums in another order
GRAD_TOL = 1e-4     # one more pass of such sums


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


@pytest.fixture(scope="module")
def family():
    init_fn, apply_fn = nemotron_h_family(TINY, FEAT, CLASSES, PATCH,
                                          ref_block=4)
    return init_fn, apply_fn, init_fn(jax.random.key(0))


def _series(n, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, FEAT))


def _loss(logits, y):
    return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                         y[:, None], -1))


def test_logits_counts_loss_and_grads_match_reference(family):
    _, apply_fn, p = family
    x, y = _series(6), jnp.arange(6) % CLASSES
    with jax.default_matmul_precision("highest"):
        logits, counts = apply_fn.with_stats(p, x)
        loss, grads = jax.value_and_grad(
            lambda q: _loss(apply_fn(q, x), y))(p)
    r_logits, r_counts = ref.forward(p, TINY, x, PATCH)
    r_loss, r_grads = jax.value_and_grad(
        lambda q: _loss(ref.forward(q, TINY, x, PATCH)[0], y))(p)
    _close(logits, r_logits, FWD_TOL)
    np.testing.assert_array_equal(counts, r_counts)
    assert counts.shape == (3, TINY.experts_held)
    _close(loss, r_loss, FWD_TOL)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(r_grads)):
        _close(a, b, GRAD_TOL)


@pytest.mark.parametrize("chunk", [TOKENS, 8])
def test_grouped_ssd_matches_recurrence(chunk):
    """One chunk of 24 tokens, and three chunks of 8."""
    cfg = dataclasses.replace(TINY, ssm_chunk=chunk)
    p = ssm.init_ssd(jax.random.key(2), cfg)
    p["a_log"] = jnp.log(jnp.linspace(1.0, 4.0, cfg.ssm_heads))
    p["dt_bias"] = jnp.linspace(-3.0, 0.0, cfg.ssm_heads)
    x = jax.random.normal(jax.random.key(3), (2, TOKENS, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = ssm.ssd_forward(p, cfg, x)
        want = ref.mamba(p, cfg, x)
    _close(got, want, FWD_TOL)


def test_held_shares_add_up_to_the_uncut_layer():
    """Every share's held-expert part, with the shared expert counted
    once, adds up to the reference layer that holds all experts."""
    e, held = TINY.n_experts, TINY.experts_held
    whole = dataclasses.replace(TINY, experts_held=e)
    p = ffn.init_held_moe(jax.random.key(4), whole)
    x = jax.random.normal(jax.random.key(5), (2, TOKENS, TINY.d_model))
    shared = ref._relu2(x @ p["shared_up"]) @ p["shared_down"]
    total, counts = shared, []
    with jax.default_matmul_precision("highest"):
        for first in range(0, e, held):
            part = dict(p, w_up=p["w_up"][first:first + held],
                        w_down=p["w_down"][first:first + held])
            y, c = ffn.held_moe_forward(part, TINY, x, first=first)
            total, counts = total + (y - shared), counts + [c]
        want, want_counts = ref.experts(p, whole, x)
    _close(total, want, FWD_TOL)
    np.testing.assert_array_equal(np.concatenate(counts), want_counts)
    assert int(np.sum(counts)) == x.shape[0] * TOKENS * TINY.moe_top_k


def test_token_blocks_give_the_same_layer():
    p = ffn.init_held_moe(jax.random.key(6), TINY)
    x = jax.random.normal(jax.random.key(7), (3, 10, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        one, c1 = ffn.held_moe_forward(p, TINY, x)
        blocks, c4 = ffn.held_moe_forward(p, TINY, x, block=8)
    _close(blocks, one, FWD_TOL)
    np.testing.assert_array_equal(c1, c4)


def _cohort(family, n):
    init_fn, apply_fn, _ = family
    params = jax.vmap(init_fn)(jax.random.split(jax.random.key(8), n))
    opt = adam(3e-3)
    return apply_fn, opt, params, jax.vmap(opt.init)(params)


def _step_args(n, r=12, seed=9):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (n, 4, FEAT)),
            jax.random.randint(ks[1], (n, 4), 0, CLASSES),
            jax.random.normal(ks[2], (r, FEAT)),
            jax.nn.softmax(jax.random.normal(ks[3], (n, r, CLASSES)), -1),
            jnp.ones((n,), bool))


def test_cohort_of_two_under_vmap_matches_each_client_alone(family):
    """The expert layer under the cohort step's vmap: a cohort of 2 gives
    each client what it gets stepped alone, with the reference term
    differentiated in three blocks of 4."""
    apply_fn, opt, params, state = _cohort(family, 2)
    bx, by, rx, tg, on = _step_args(2)
    with jax.default_matmul_precision("highest"):
        p2, s2, loss2, counts2 = cohort_step(apply_fn, opt, params, state,
                                             bx, by, rx, tg, on, 0.8, True)
        for i in range(2):
            one = lambda t: jax.tree.map(lambda a: a[i:i + 1], t)  # noqa
            p1, _, loss1, counts1 = cohort_step(
                apply_fn, opt, one(params), one(state), bx[i:i + 1],
                by[i:i + 1], rx, tg[i:i + 1], on[:1], 0.8, True)
            _close(loss2[i], loss1[0], FWD_TOL)
            np.testing.assert_array_equal(counts2[i], counts1[0])
            for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p1)):
                _close(a[i], b[0], GRAD_TOL)
    assert counts2.shape == (2, 3, TINY.experts_held)
    # 4 local and 12 reference series of 24 tokens, 3 choices a token
    assert int(counts2[0].sum(axis=-1).max()) <= 16 * TOKENS * 3


def test_blocked_reference_gradient_is_the_same_sum(family):
    """``ref_block`` splits the reference term's gradient into blocks:
    the step matches the unblocked one, and the Eq. 6 gradient of the
    plain reference."""
    init_fn, blocked, _ = family
    whole = ExpertFamilyApply(blocked.with_stats, blocked.experts_held,
                              blocked.seq_len)
    _, opt, params, state = _cohort(family, 1)
    bx, by, rx, tg, on = _step_args(1)
    with jax.default_matmul_precision("highest"):
        a = cohort_step(blocked, opt, params, state, bx, by, rx, tg, on,
                        0.8, True)
        b = cohort_step(whole, opt, params, state, bx, by, rx, tg, on,
                        0.8, True)
    _close(a[2], b[2], FWD_TOL)
    np.testing.assert_array_equal(a[3], b[3])
    for u, v in zip(jax.tree.leaves(a[1].mu), jax.tree.leaves(b[1].mu)):
        _close(u, v, GRAD_TOL)

    def eq6(q):
        logp = jax.nn.log_softmax(ref.forward(q, TINY, bx[0], PATCH)[0], -1)
        loc = -jnp.mean(jnp.take_along_axis(logp, by[0][:, None], -1))
        probs = jax.nn.softmax(ref.forward(q, TINY, rx, PATCH)[0], -1)
        return 0.2 * loc + 0.8 * jnp.mean(jnp.sum((probs - tg[0]) ** 2, -1))

    r_grads = jax.grad(eq6)(jax.tree.map(lambda t: t[0], params))
    for mu, g in zip(jax.tree.leaves(a[1].mu), jax.tree.leaves(r_grads)):
        _close(mu[0] / 0.1, g, GRAD_TOL)      # Adam's mu = (1 - b1) g


def test_router_bias_takes_no_gradient_and_stays(family):
    apply_fn, opt, params, state = _cohort(family, 1)
    bias = params["stack"]["groups"]["pos1"]["mixer"]["router_bias"]
    params["stack"]["groups"]["pos1"]["mixer"]["router_bias"] = bias + 0.1
    out = expert_cohort_step(apply_fn, opt, params, state,
                              *_step_args(1), 0.8, True)
    after = out[0]["stack"]["groups"]["pos1"]["mixer"]["router_bias"]
    np.testing.assert_array_equal(after, bias + 0.1)
    assert not np.asarray(out[1].mu["stack"]["groups"]["pos1"]["mixer"]
                          ["router_bias"]).any()


def test_federation_round_beside_mlp(family):
    """Two sync rounds of a federation whose first client runs the family
    and the rest ``mlp-s``: the engine steps it with the donating step,
    sums its expert loads on the device and uploads its messengers."""
    from repro.core import FederationConfig, FederationEngine, Protocol
    from repro.data import make_splits, sc_like
    init_fn, apply_fn, _ = family
    ds = sc_like(samples_per_client=20, ref_size=12, length=FEAT)
    splits = make_splits(ds, seed=0)
    zoo = build_zoo("mlp-s", FEAT, ds.n_classes)
    zoo["nemo"] = (init_fn, apply_fn)
    zoo.optimizers["nemo"] = adam(3e-3)
    eng = FederationEngine.build(
        ds, splits, zoo, ["nemo"] + ["mlp-s"] * (ds.n_clients - 1),
        Protocol("sqmd", rho=0.8, q=8, k=4),
        config=FederationConfig(rounds=2, batch_size=4))
    for rnd in range(2):
        eng.run_round(rnd)
    loads = eng.clients.counters.read()["expert_load.nemo"]
    assert loads.shape == (3, TINY.experts_held)
    # round 0 steps 4 local series, round 1 also the 12 reference ones
    assert loads.sum(axis=-1).max() <= (4 + 16) * TOKENS * 3
    repo = np.asarray(eng.fed.server.repo_logp)
    assert np.isfinite(repo).all() and (repo[0] < 0).all()
    assert not np.allclose(repo[0], repo[1])
